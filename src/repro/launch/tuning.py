"""Compiler flags and the persistent compile cache, set up in one place.

Every entry point (the train and serve drivers, the benchmarks and
``chip_smoke.py``) calls :func:`apply_tuning` before its first device
computation, because XLA and libtpu read their flags when the backend
initialises:

  * TPU compiler flags (the latency-hiding scheduler, which overlaps the
    ParameterDB all-gathers / reduce-scatters with compute) are appended to
    ``LIBTPU_INIT_ARGS``.  They never go into ``XLA_FLAGS``, whose parser
    rejects TPU flags fatally, and what the variable already holds is kept;
  * JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when it
    is set (JAX reads it itself), else, on a TPU, the fixed
    ``<checkout>/.jax_cache``.  The path is part of the cache key, so it
    must not move between runs.

Flags are appended idempotently; ``REPRO_TUNE=0`` disables the flags
(untuned A/B baseline) but not the compile cache, which changes no
program.
"""
from __future__ import annotations

import os
from pathlib import Path

TPU_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str | None:
    """Point JAX's persistent compile cache at its one directory; returns
    that directory (None: no cache, because no TPU backs this process)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    if jax.default_backend() != "tpu":
        # host-compiled entries are not kept: they would land in the
        # checkout and be loaded back on whatever machine it is copied to
        return None
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def apply_tuning() -> list[str]:
    """Append the TPU flags to ``LIBTPU_INIT_ARGS``, then set up the
    compile cache, which initialises the JAX backend; returns the flags
    added.  Safe to call repeatedly — flags already present are skipped.
    The flags are a no-op when REPRO_TUNE=0."""
    added: list[str] = []
    if os.environ.get("REPRO_TUNE", "1") != "0":
        current = os.environ.get("LIBTPU_INIT_ARGS", "")
        added = [f for f in TPU_FLAGS if f.split("=")[0] not in current]
        if added:
            os.environ["LIBTPU_INIT_ARGS"] = " ".join([current] + added).strip()
    setup_compile_cache()
    return added
