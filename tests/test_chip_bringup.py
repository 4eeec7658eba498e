"""What the first run on the chip pinned down (ISSUE 21): where the jax
compile cache goes, a native library that follows its source, and a
``chip_smoke.py`` that refuses to run without the chip."""

import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def jax_cache_config():
    """The process-wide jax cache settings, restored after the test."""
    import jax

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield jax.config
    for n, v in saved.items():
        jax.config.update(n, v)


def test_compile_cache_env_var_places_it(monkeypatch, jax_cache_config):
    from znicz_tpu.backends import configure_compile_cache

    before = jax_cache_config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert configure_compile_cache() == "/somewhere/else"
    # whoever set the variable owns the placement: nothing set in code
    assert jax_cache_config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_into_the_checkout(monkeypatch, tmp_path,
                                                  jax_cache_config):
    from znicz_tpu.backends import configure_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)             # never the working directory
    want = str(REPO / ".znicz_cache" / "jax")
    assert configure_compile_cache() == want
    assert jax_cache_config.jax_compilation_cache_dir == want
    assert not list(tmp_path.iterdir())


def test_native_library_is_named_by_its_source(monkeypatch, tmp_path):
    """A library another revision left in the cache is not this source's:
    the name carries the source hash, so it is built again, not loaded."""
    import hashlib

    from znicz_tpu import native
    from znicz_tpu.core.config import root

    monkeypatch.setattr(root.common.dirs, "cache", str(tmp_path))
    (tmp_path / "libznicz_native.so").write_bytes(b"not this revision")
    built = pathlib.Path(native.build())
    source = pathlib.Path(native._source_path()).read_bytes()
    assert built.parent == tmp_path
    assert hashlib.sha256(source).hexdigest()[:16] in built.name


def test_chip_smoke_refuses_to_run_without_the_chip(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=tmp_path,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""                # no result of any kind
    assert "TPU" in proc.stderr
    assert not list(tmp_path.iterdir())     # and nothing was built
