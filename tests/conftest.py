"""Test configuration: force an 8-virtual-device CPU platform BEFORE any jax
backend initialization so sharding/collective tests run anywhere
(SURVEY.md §4).  The recipe (env forcing, jax.config pin) lives in
znicz_tpu/virtdev.py, shared with __graft_entry__.dryrun_multichip.  The
chip is never reached from here: ``python chip_smoke.py`` proves the
program on it."""

from znicz_tpu.virtdev import provision_cpu_devices

provision_cpu_devices(8)

import time  # noqa: E402

import pytest  # noqa: E402

def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long soak tests excluded from tier-1 (-m 'not slow')")
    config._znicz_session_t0 = time.perf_counter()


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """The ten slowest tests and the session's wall time: where the
    seconds went, for whoever has to trim or ``slow``-mark next.  The
    limit is the runner's (the driver's command carries its own
    ``timeout``); none is stated here."""
    durations = []
    for reports in terminalreporter.stats.values():
        for rep in reports:
            if getattr(rep, "when", None) == "call":
                durations.append((rep.duration, rep.nodeid))
    if not durations:
        return
    tr = terminalreporter
    wall = time.perf_counter() - getattr(config, "_znicz_session_t0",
                                         time.perf_counter())
    tr.write_sep("-", "slowest tests")
    for dur, nodeid in sorted(durations, reverse=True)[:10]:
        tr.write_line(f"  {dur:7.2f}s  {nodeid}")
    tr.write_line(f"  session wall {wall:.1f}s over {len(durations)} "
                  f"test calls")


@pytest.fixture
def restore_root():
    """The config tree as it was, after a test that ran a whole job's
    overrides through it: the saved values come back (``Config.update``
    merges) and the engine knobs the job ADDED go — a ``compute_dtype``
    left behind trains every later test of the worker in bf16."""
    from znicz_tpu.core.config import root

    saved = root.to_dict()
    yield
    root.update(saved)
    for key in set(root.common.engine.to_dict()) - set(
            saved["common"]["engine"]):
        delattr(root.common.engine, key)


@pytest.fixture(autouse=True)
def _fixed_seed():
    """Every test starts from the same global seed and a clean stream table."""
    from znicz_tpu.core import prng

    prng.reset(1013)
    yield
