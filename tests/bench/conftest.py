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


def smoke(cell):
    """The cell at smoke widths, with the Pallas kernels interpreted."""
    cell = copy.copy(cell)
    cell.config = {**cell.config, **SMOKE_MODEL}
    cell.traffic = {**cell.traffic, **SMOKE_TRAFFIC[cell.traffic["kind"]],
                    "kernels": "interpret"}
    if "optimizer" in cell.traffic:
        cell.traffic["optimizer"] = {**cell.traffic["optimizer"],
                                     "lr": SMOKE_LR}
    return cell


@pytest.fixture
def smoke_cell(monkeypatch):
    from bench import harness

    def make(name):
        cell = smoke(harness.resolve(name))
        monkeypatch.setenv("REPRO_KERNEL_IMPL", cell.traffic["kernels"])
        return cell
    return make
