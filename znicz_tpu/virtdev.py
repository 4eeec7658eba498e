"""Virtual-device provisioning.

Forces an n-virtual-device CPU platform so sharding/collective code runs on
hosts without n real chips (SURVEY.md §4 "multi-device tests on CPU via
XLA_FLAGS=--xla_force_host_platform_device_count").  Shared by
``tests/conftest.py``, the launcher's ``--backend cpu`` and
``__graft_entry__.dryrun_multichip``.

Must be called BEFORE the first jax *backend initialization*; calling it
after ``import jax`` is fine (XLA parses the flags at first client creation).
"""

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def provision_cpu_devices(n: int, verify: bool = True) -> None:
    """Pin this process to a CPU platform exposing >= n virtual devices.

    Safe to call repeatedly; an existing forced count is only ever raised,
    never lowered.  The platform is pinned through ``jax.config`` as well
    as the environment: the config value wins once jax is imported.

    ``verify=False`` skips the device-count check, leaving backends
    UNinitialized — required before ``jax.distributed.initialize`` (which
    must precede the first backend creation).
    """
    import jax

    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(_COUNT_FLAG + r"=(\d+)", flags)
    if m is None:
        flags = (flags + f" {_COUNT_FLAG}={n}").strip()
    elif int(m.group(1)) < n:
        flags = re.sub(_COUNT_FLAG + r"=\d+", f"{_COUNT_FLAG}={n}", flags)
    os.environ["XLA_FLAGS"] = flags
    jax.config.update("jax_platforms", "cpu")
    if not verify:
        return
    # XLA parses the flags at FIRST client creation only: if backends were
    # already initialized with fewer devices, the env rewrite above silently
    # did nothing — fail here with the real cause instead of a confusing
    # device-count error far downstream.
    have = len(jax.devices())
    if have < n:
        raise RuntimeError(
            f"provision_cpu_devices({n}): jax already initialized with "
            f"{have} device(s); virtual CPU devices must be provisioned "
            "before the first backend creation (re-exec in a fresh process)")
