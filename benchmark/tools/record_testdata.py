"""Record the small trace kept under ``benchmark/testdata/``.

    python benchmark/tools/record_testdata.py <out.xplane.pb>

A scan of a convolution, an elementwise pass and a matrix product, run
three times under the profiler with the benchmark's own annotations, so
that the reduction's test has a real file of the backend it ran on
(device plane, ``XLA Ops`` line, nested ``while``) and stays a few tens of
kilobytes.  Beside it go ``<out>.json`` (the sync reading, three
``TraceRing``-shaped spans on ``perf_counter`` and what the test expects)
and ``<out>.hlo.txt``, the compiled text of the program, from which the
reduction learns what each fusion computes.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from benchmark.reduce import xplane

    def body(h, w):
        y = lax.conv_general_dilated(
            h, w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        y = jnp.maximum(y, 0) * 0.5
        return y, jnp.sum(y.reshape(y.shape[0], -1) @ jnp.ones(
            (y.shape[1] * y.shape[2] * y.shape[3], 8), y.dtype))

    @jax.jit
    def program(h, ws):
        return lax.scan(body, h, ws)

    h = jnp.ones((8, 16, 16, 32), jnp.bfloat16)
    ws = jnp.full((4, 3, 3, 32, 32), 0.01, jnp.bfloat16)
    jax.block_until_ready(program(h, ws))
    tmp = tempfile.mkdtemp(prefix="bench_testdata_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(tmp, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench:sync"):
        sync = time.perf_counter()
    ring = []       # TraceRing tuples on perf_counter, as the program's are
    for i in range(3):
        with jax.profiler.TraceAnnotation(f"bench:epoch:{i}"):
            jax.block_until_ready(program(h, ws))
            t0 = time.perf_counter()
            time.sleep(0.002)
            ring.append(("train", "flush", int(t0 * 1e6),
                         int((time.perf_counter() - t0) * 1e6), 0, None))
    jax.profiler.stop_trace()
    out = sys.argv[1]
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    shutil.copy(xplane.newest_xplane(tmp), out)
    shutil.rmtree(tmp, ignore_errors=True)
    with open(out.replace(".xplane.pb", ".hlo.txt"), "w") as f:
        f.write(program.lower(h, ws).compile().as_text())
    with open(out.replace(".xplane.pb", ".json"), "w") as f:
        json.dump({"device_kind": jax.devices()[0].device_kind,
                   "devices": 1, "sync_perf_s": sync, "ring_events": ring,
                   "expected_gap_labels": ["train:flush", "bench:epoch:0",
                                           "bench:epoch:1",
                                           "bench:epoch:2"]}, f, indent=1)
    print(out, os.path.getsize(out), "bytes; sync perf_counter", sync,
          "on", jax.devices()[0].device_kind)
    return 0


if __name__ == "__main__":
    sys.exit(main())
