"""CI lints, now riding znicz-lint (ISSUE 9): no NEW ad-hoc counter
attributes (ISSUE 5 satellite) and no silently-ignored serving/engine
config knobs (ISSUE 6/7 satellites).

Historical note: these started as three hand-rolled regexes in this
file.  The regexes were line-anchored (missed ``self.x = self.x + 1``)
and blind to aliasing — binding a config subtree to a variable hid
every later ``.get()`` read, so the lint had to REFUSE aliasing itself
(the old ``SERVING_ALIAS`` / ``ENGINE_ALIAS`` patterns).  ISSUE 9
ported all three onto the AST checkers in ``znicz_tpu/analysis/``:
alias-bound reads now RESOLVE (see ``_admission_from_config`` in
serving/frontend.py, which binds the admission subtree to a local),
and the refusals are retired.  The test names survive; each is a thin
wrapper over the corresponding analyzer rule.

The counter ALLOWLIST (attributes that look counter-ish but are STATE,
not metrics — e.g. ``parallel/fused.py steps_done``, the PRNG/step-key
stream position; ``loader/base.py samples_served``, the loader cursor;
the kohonen epoch accumulators) moved WITH its rationale comments to
``znicz_tpu/analysis/counters.py`` so the ``python -m
znicz_tpu.analysis`` CLI and this test share one source of truth;
``test_allowlist_is_the_single_source_of_truth`` below pins the
historical entries so they cannot silently vanish.
"""

import pathlib
import textwrap

from znicz_tpu.analysis import run
from znicz_tpu.analysis.config_knob import (ConfigKnobChecker,
                                            load_declared_tables)
from znicz_tpu.analysis.counters import (ALLOWLIST,
                                         CounterRegistryChecker)
from znicz_tpu.analysis.core import Module

PKG = pathlib.Path(__file__).resolve().parent.parent / "znicz_tpu"


def _check(checker, code, rel="fixture.py"):
    """Run one checker over a fixture snippet."""
    module = Module(pathlib.Path(rel), rel, textwrap.dedent(code))
    return [f.message for f in checker.check(module)]


def _live(rule):
    """Unbaselined findings of one rule over the real package."""
    analysis = run(PKG, rules=[rule])
    assert not analysis.parse_errors, analysis.parse_errors
    return [f.render() for f in analysis.findings]


# -- ad-hoc counter lint (ISSUE 5 satellite) -----------------------------------


def test_no_adhoc_counters_outside_the_registry():
    offenders = _live("counter-registry")
    assert not offenders, (
        "ad-hoc counter increments found — register them in "
        "znicz_tpu/telemetry instead (telemetry.scope(...).counter(...)"
        ".inc()), or allowlist non-metric state with a justification in "
        "znicz_tpu/analysis/counters.py:\n  " + "\n  ".join(offenders))


def test_lint_pattern_catches_the_regression_class():
    """The checker must actually fire on the style it polices — and on
    the ``self.x = self.x + 1`` spelling the old regex never saw."""
    checker = CounterRegistryChecker(allowlist=())
    tp = _check(checker, """
        class S:
            def f(self):
                self.bad_frames += 1
                self.retry_count += n
                self.bad_frames = self.bad_frames + 1   # regex blind spot
                if fast: self.served += 1               # one-liner too
    """)
    assert len(tp) == 4, tp
    tn = _check(checker, """
        class S:
            def f(self):
                self._pos += 1                  # cursor, not metric
                unit.run_count += 1             # not self.
                self.total = other.total + 1    # copy, not increment
    """)
    assert not tn, tn


def test_allowlist_is_the_single_source_of_truth():
    """The historical allowlist entries (with their reasons) moved to
    the checker module; pin them so they cannot silently vanish."""
    for pair in {("parallel/fused.py", "steps_done"),
                 ("loader/base.py", "samples_served"),
                 ("graphics.py", "received"),
                 ("kohonen.py", "_batches"),
                 ("kohonen.py", "total")}:
        assert pair in ALLOWLIST, pair
    # and every allowlisted site still exists in the package — a stale
    # allowlist entry is a hole waiting for a regression to crawl in
    for rel, attr in ALLOWLIST:
        text = (PKG / rel).read_text()
        assert f"self.{attr}" in text, (rel, attr)


# -- serving config-knob lint (ISSUE 6 satellite) ------------------------------


def test_every_serving_config_read_is_declared_in_defaults():
    offenders = _live("config-knob")
    assert not offenders, (
        "config keys read in code but missing from the declaration "
        "tables — an undeclared knob is silently ignored by dotted "
        "overrides; declare it (or fix the typo):\n  "
        + "\n  ".join(offenders))


def test_serving_config_lint_catches_the_regression_class():
    """Undeclared keys fire (literal OR alias-bound), declared keys and
    the dynamic ``.get(variable)`` read stay quiet."""
    checker = ConfigKnobChecker(PKG)
    assert _check(checker, """
        from znicz_tpu.core.config import root
        x = root.common.serving.get("bogus_knob", 1)
    """)
    assert not _check(checker, """
        from znicz_tpu.core.config import root
        x = root.common.serving.get("max_batch", 32)
        y = root.common.serving.admission.get("rate_limit", 0)
    """)
    # the frontend's dynamic read (variable key) contributes no path
    assert not _check(checker, """
        from znicz_tpu.core.config import root
        def _cfg(name):
            return root.common.serving.get(name, DEFAULTS[name])
    """)
    # ALIASING NOW RESOLVES (the old lint refused it outright): a
    # declared read through the alias passes, a typo through it fires
    assert not _check(checker, """
        from znicz_tpu.core.config import root
        def f():
            adm = root.common.serving.admission
            return adm.get("rate_limit", 0)
    """)
    offenders = _check(checker, """
        from znicz_tpu.core.config import root
        def f():
            adm = root.common.serving.admission
            return adm.get("rate_limi", 0)
    """)
    assert offenders and "admission.rate_limi" in offenders[0]
    # what alias resolution CANNOT follow — a subtree escaping the
    # local scope — is still refused, preserving the old guarantee
    assert _check(checker, """
        from znicz_tpu.core.config import root
        def f(g):
            g(root.common.serving.admission)
    """)


# -- engine config-knob lint (ISSUE 7 satellite) -------------------------------


def test_every_engine_config_read_is_declared_in_defaults():
    # same analyzer rule covers both trees; the package-wide run in
    # test_every_serving_config_read_is_declared_in_defaults already
    # proves zero live findings — here we pin the engine table contents
    # the old test asserted, plus the AST-extracted tables matching the
    # imported Python ones (table-extraction rot guard)
    tables = load_declared_tables(PKG)
    from znicz_tpu.core.config import ENGINE_DEFAULTS
    from znicz_tpu.serving.frontend import DEFAULTS

    def flat(d, prefix=""):
        out = set()
        for k, v in d.items():
            out.add(prefix + k)
            if isinstance(v, dict):
                out |= flat(v, prefix + k + ".")
        return out

    # the engine tree nests since ISSUE 18 (mesh.{data,model}), so the
    # AST tables flatten to dotted leaves + subtree keys like serving's
    assert tables["engine"][0] | tables["engine"][1] == flat(ENGINE_DEFAULTS)
    assert tables["serving"][0] | tables["serving"][1] == flat(DEFAULTS)


def test_every_declared_engine_knob_has_a_reader():
    """The converse of the check above: a knob ENGINE_DEFAULTS declares
    and nothing in the package touches is an option that selects
    nothing.  Run the same analyzer with an EMPTY engine table, so that
    every access it resolves is reported by name, over every module but
    the one that holds the table."""
    import re

    from znicz_tpu.core.config import ENGINE_DEFAULTS

    tables = dict(load_declared_tables(PKG))
    tables["engine"] = (set(), set())
    checker = ConfigKnobChecker(PKG, tables=tables)
    touched = set()
    for path in sorted(PKG.rglob("*.py")):
        rel = str(path.relative_to(PKG))
        if rel == "core/config.py":
            continue
        for f in checker.check(Module(path, rel, path.read_text())):
            m = re.search(r"'root\.common\.engine\.([\w.]+)'", f.message)
            if m:
                touched.add(m.group(1))

    def leaves(d, prefix=""):
        return {leaf for k, v in d.items() for leaf in (
            leaves(v, prefix + k + ".") if isinstance(v, dict)
            else {prefix + k})}

    declared = leaves(ENGINE_DEFAULTS)
    assert len(declared) == 54
    assert not declared - touched, sorted(declared - touched)


def test_engine_config_lint_catches_the_regression_class():
    checker = ConfigKnobChecker(PKG)
    assert _check(checker, """
        from znicz_tpu.core.config import root
        x = root.common.engine.get("bogus_knob", 1)
    """)
    # a WRITE of an undeclared key is an offense too (sample configs
    # SET knobs the engine later reads)
    assert _check(checker, """
        from znicz_tpu.core.config import root
        root.common.engine.compute_dtyp = "bf16"
    """)
    assert not _check(checker, """
        from znicz_tpu.core.config import root
        root.common.engine.compute_dtype = "bf16"
        chunk = root.common.engine.get("scan_chunk", 8)
        if x == root.common.engine:
            pass
    """)
    for key in ("compute_dtype", "fused_tail", "async_staging",
                "staging_donate", "xla_latency_hiding", "scan_chunk"):
        assert key in load_declared_tables(PKG)["engine"][0], key
    # engine-tree aliasing resolves now as well
    assert not _check(checker, """
        from znicz_tpu.core.config import root
        def f():
            eng = root.common.engine
            return eng.get("scan_chunk", 8)
    """)
    offenders = _check(checker, """
        from znicz_tpu.core.config import root
        def f():
            eng = root.common.engine
            return eng.get("scan_chunky", 8)
    """)
    assert offenders and "scan_chunky" in offenders[0]
