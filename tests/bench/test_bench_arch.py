"""Each architecture's module, found by the configuration's ``model_type``:
what the harness resolves, what it refuses, and that the reference half
imports nothing of the program."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench import harness, program
from conftest import OLMO, ROOT, SMOKE_MODELS

ARCHS = sorted(p.stem for p in (ROOT / "bench" / "arch").glob("*.py")
               if p.stem != "__init__")


def test_resolve_sets_the_arch_module():
    cell = harness.resolve("smollm360m.train.ring_d2")
    assert cell.arch.__name__ == "bench.arch.llama"
    assert cell.arch is harness.arch(cell.config)
    assert harness.arch(OLMO).__name__ == "bench.arch.olmo"


def test_a_model_type_without_a_module_is_refused():
    with pytest.raises(harness.BenchError, match="bench/arch/nosuch.py"):
        harness.arch({"model_type": "nosuch"})


@pytest.mark.parametrize("eps", [1e-5, None])
def test_an_epsilon_the_program_cannot_run_is_refused(eps):
    config = {k: v for k, v in OLMO.items() if k != "layer_norm_eps"}
    if eps is not None:
        config["layer_norm_eps"] = eps        # None: OLMo's own 1e-5
    with pytest.raises(harness.BenchError, match="norm epsilon"):
        program.model_config(config)


# imports the module and, given a configuration, runs its forward once
_IMPORT = """
import importlib, json, sys
import jax.numpy as jnp
from bench import weights
arch = importlib.import_module("bench.arch." + sys.argv[1])
config = json.loads(sys.argv[2])
if config:
    w = weights.make(config, 5)
    h = arch.hidden(w, jnp.zeros((1, 8), jnp.int32), arch.dims(config), "f32")
    arch.logits_at(w, h, "f32")
print(json.dumps(sorted(k for k in sys.modules if k.split(".")[0] == "repro")))
"""


@pytest.mark.parametrize("name", ARCHS)
def test_arch_module_imports_nothing_of_the_program(name):
    """Without the program on the path; a module that has no smoke
    configuration here is imported only."""
    base = harness.resolve("smollm360m.train.ring_d2").config
    config = next((c for c in (make(base) for make in SMOKE_MODELS.values())
                   if c["model_type"] == name), None)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}
    out = subprocess.run([sys.executable, "-c", _IMPORT, name,
                          json.dumps(config)], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.splitlines()[-1]) == []
