"""The comparisons that decide ``correct``, each number beside its limit.

Training (the first ``delta + 3`` steps of the run the window continues,
``reference.check_steps``):

* ``loss_gap``   the widest relative gap between the program's loss and the
  reference's, over those steps;
* ``grad_gap``   the first gradient as the optimizer got it, by the worst
  leaf: the gap between the program's norm and the reference's, over the
  larger of the reference's norm of that leaf and of the median leaf;
* ``change_gap`` the same measure on the change of the weights after those
  steps, over the leaves whose reference gradient is at least a thousandth
  of the median leaf's (the others move under AdamW by round-off alone).

Serving: ``logit_gap``, the widest gap by which a served token's logit lies
below the reference's best logit at its position, over a sample of the
finished requests drawn from the seed, the longest among them.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness

MOVED = 1e-3     # a leaf moves if its reference gradient is >= this x median


@jax.jit
def leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))), tree)


@jax.jit
def diff_norms(a, b):
    return jax.tree.map(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b)


def _worst_leaf(prog: dict, ref: dict, keys) -> float:
    keys = list(keys)
    med = float(np.median([ref[k] for k in keys]))
    return max(abs(float(prog[k]) - float(ref[k])) / max(float(ref[k]), med)
               for k in keys)


def train_readings(prog: dict, ref: dict) -> dict:
    """{name: value} of the three training numbers."""
    if set(prog["grad"]) != set(ref["grad"]):
        raise harness.BenchError("program and reference leaves differ")
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"]))
    med = float(np.median(list(ref["grad"].values())))
    moved = [k for k, g in ref["grad"].items() if g >= MOVED * med]
    return {"loss_gap": loss,
            "grad_gap": _worst_leaf(prog["grad"], ref["grad"], ref["grad"]),
            "change_gap": _worst_leaf(prog["change"], ref["change"], moved)}


def served_gaps(ref_logits: np.ndarray, tokens) -> np.ndarray:
    """Per position: the reference's best logit less the served token's."""
    tokens = np.asarray(tokens)
    return ref_logits.max(-1) - ref_logits[np.arange(len(tokens)), tokens]


def sample_requests(finished: dict, seed: int, n: int) -> list[int]:
    """``n`` finished request ids drawn from the seed, the one with the most
    prompt and served tokens always among them; ``finished`` maps a request
    id to its prompt and served token length."""
    ids = sorted(finished)
    longest = max(ids, key=lambda r: (finished[r], -r))
    rest = [r for r in ids if r != longest]
    rng = harness.np_rng(seed, harness.STREAM_SAMPLE)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def checks(readings: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}} for every limit the cell has."""
    missing = set(limits) - set(readings)
    if missing:
        raise harness.BenchError(f"no reading for limits {sorted(missing)}")
    return {k: {"value": float(readings[k]), "limit": float(limits[k]["limit"])}
            for k in limits}


def verdict(checks_: dict) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks_.values())
