"""Seconds of set-up that jax spent tracing, lowering and compiling (or
fetching from the persistent cache).

Layer: compile (jax persistent cache, ``backends.configure_compile_cache``).
Source: jax's own monitoring events, summed by ``benchmark/meter.py`` up to
the start of the window; cache hits and misses go on an earlier line.
Moves ``setup_s``.
"""


def read(run):
    return run.get("setup_compile_s")
