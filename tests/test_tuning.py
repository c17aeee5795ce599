"""launch/tuning.py: where compiler flags and the compile cache go."""
import os
import subprocess
import sys

import jax
import pytest

from repro.launch import tuning

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_tpu_flags_append_to_libtpu_init_args(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/nonexistent")
    monkeypatch.setenv("LIBTPU_INIT_ARGS", "--xla_keep_me=1")
    monkeypatch.setenv("XLA_FLAGS", "--xla_cpu_flag=1")
    monkeypatch.delenv("REPRO_TUNE", raising=False)
    added = tuning.apply_tuning()
    assert added == list(tuning.TPU_FLAGS)
    libtpu = os.environ["LIBTPU_INIT_ARGS"]
    assert libtpu.startswith("--xla_keep_me=1 ")     # appended, not replaced
    assert all(f in libtpu for f in tuning.TPU_FLAGS)
    assert os.environ["XLA_FLAGS"] == "--xla_cpu_flag=1"
    assert tuning.apply_tuning() == []                # idempotent
    assert os.environ["LIBTPU_INIT_ARGS"] == libtpu


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_compile_cache_defaults_to_checkout_dir(monkeypatch, backend):
    """Without JAX_COMPILATION_CACHE_DIR a TPU process caches in the
    checkout; a host-only process keeps no cache."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    old = jax.config.jax_compilation_cache_dir
    want = str(tuning.CACHE_DIR) if backend == "tpu" else None
    try:
        assert tuning.setup_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == (want or old)
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
    # a fixed path at the checkout root, never a temporary one
    assert tuning.CACHE_DIR.name == ".jax_cache"
    assert (tuning.CACHE_DIR.parent / "src" / "repro").is_dir()


def test_compile_cache_env_dir_written_then_read(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the cache lands there, and a
    second identical run compiles nothing: it reads the entry back."""
    prog = ("from repro.launch.tuning import apply_tuning; apply_tuning(); "
            "import jax, jax.numpy as jnp; "
            "print(jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64)))"
            ".sum())")
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_LOG_COMPILES="1")
    runs = [subprocess.run([sys.executable, "-c", prog], env=env,
                           capture_output=True, text=True, timeout=120)
            for _ in range(2)]
    for r in runs:
        assert r.returncode == 0, r.stderr[-2000:]
    assert any(tmp_path.iterdir())
    assert "Persistent compilation cache hit" not in runs[0].stderr
    assert "Persistent compilation cache hit" in runs[1].stderr


@pytest.mark.parametrize("tune", ["0", "1"])
def test_repro_tune_off_keeps_compile_cache(monkeypatch, tmp_path, tune):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("REPRO_TUNE", tune)
    monkeypatch.delenv("LIBTPU_INIT_ARGS", raising=False)
    added = tuning.apply_tuning()
    assert (added == []) == (tune == "0")
    assert tuning.setup_compile_cache() == str(tmp_path)
