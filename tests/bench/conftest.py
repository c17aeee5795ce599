"""Smoke-size copies of the benchmark's cells, for runs on the CPU."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

# wide enough that the float8 control's logit gaps stand clear of the
# chat cell's limit, small enough for a test run
SMOKE_MODEL = {"hidden_size": 256, "intermediate_size": 512,
               "num_hidden_layers": 2, "num_attention_heads": 4,
               "num_key_value_heads": 2, "head_dim": 64, "vocab_size": 2048}
SMOKE_TRAFFIC = {
    "train": {"batch": 4, "seq": 128, "trace_steps": 2},
    "serve": {"engine": {"batch_size": 4, "page_size": 16, "cache_len": 256,
                         "prefill_chunk": 32},
              "rate": 40.0,
              "prompt": {"median": 40, "sigma": 0.8, "min": 8, "max": 96},
              "output": {"median": 16, "sigma": 0.8, "min": 4, "max": 48},
              "check_requests": 8},
}
# a larger step than the cell's 3e-4: at smoke widths the versions the ring
# serves must differ by more than the bfloat16 drift the chip-set limits
# allow, or a ring that is never written could not be told from a sound one
SMOKE_LR = 1e-3


# OLMo-1B as allenai/OLMo-1B-hf's config.json states it, at smoke widths;
# the norm's epsilon is the one the program's norms add (the published
# LayerNorm adds 1e-5, which the olmo module takes where the file is silent)
OLMO = {"model_type": "olmo", "arch": "olmo-1b", "hidden_act": "silu",
        "rope_theta": 10000.0, "tie_word_embeddings": True,
        "max_position_embeddings": 2048, "compute_dtype": "bfloat16",
        "param_dtype": "float32", "layer_norm_eps": 1e-6,
        **SMOKE_MODEL, "num_key_value_heads": 4}
del OLMO["head_dim"]                 # OLMo states none: d / H
# the configurations a cell is rehearsed with, from the cell's own
SMOKE_MODELS = {
    "llama": lambda config: {**config, **SMOKE_MODEL},
    "llama_untied": lambda config: {**config, **SMOKE_MODEL,
                                    "tie_word_embeddings": False},
    "olmo": lambda config: dict(OLMO),
}


def smoke(cell, model: str = "llama"):
    """The cell at smoke widths, with the Pallas kernels interpreted, its
    configuration one of ``SMOKE_MODELS``."""
    from bench import harness
    cell = copy.copy(cell)
    cell.config = SMOKE_MODELS[model](cell.config)
    cell.arch = harness.arch(cell.config)
    cell.traffic = {**cell.traffic, **SMOKE_TRAFFIC[cell.traffic["kind"]],
                    "kernels": "interpret"}
    if "optimizer" in cell.traffic:
        cell.traffic["optimizer"] = {**cell.traffic["optimizer"],
                                     "lr": SMOKE_LR}
    return cell


@pytest.fixture
def smoke_cell(monkeypatch):
    from bench import harness

    def make(name, model="llama"):
        cell = smoke(harness.resolve(name), model)
        monkeypatch.setenv("REPRO_KERNEL_IMPL", cell.traffic["kernels"])
        return cell
    return make
