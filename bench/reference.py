"""The plain reference of a decoder, and the control beside it.

Written from the configuration file alone: it imports nothing of the
program and reads only the weights that ``bench/weights.py`` makes from the
seed.  ``numerics="f32"`` is the reference: every matrix product in float32
at ``Precision.HIGHEST``.  ``numerics="fp8"`` is the control: the same
computation with the operands of every matrix product rounded to float8
(e4m3 forward, e5m2 for the cotangents in the backward pass), each tensor
scaled by its own largest magnitude first, as fp8 training and serving do.

The forward itself, ``hidden`` and ``logits_at``, is the architecture's
(``bench/arch/<model_type>.py``), every product through ``mm``; the loss,
the training steps and the served logits here are the same for all.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, weights as W

HIGHEST = jax.lax.Precision.HIGHEST
F8, F8_GRAD = jnp.float8_e4m3fn, jnp.float8_e5m2


def _round(x, dtype):
    """x rounded to ``dtype`` after scaling its largest magnitude to the
    format's largest finite value, then scaled back (float32 result)."""
    big = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / big, 1.0)
    return ((x / s).astype(dtype).astype(jnp.float32) * s).astype(x.dtype)


@jax.custom_vjp
def fp8(x):
    return _round(x, F8)


def _fp8_fwd(x):
    return _round(x, F8), None


def _fp8_bwd(_, g):
    return (_round(g, F8_GRAD),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


def mm(eq: str, a, b, numerics: str):
    if numerics == "fp8":
        a, b = fp8(a), fp8(b)
    elif numerics != "f32":
        raise ValueError(f"unknown numerics {numerics!r}")
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def loss(arch, w: dict, tokens, labels, m: dict, numerics: str,
         chunk: int = 128):
    """Mean next-token cross entropy over every position, the logits made
    ``chunk`` positions at a time so that (B, S, V) never exists;
    ``arch`` is the architecture's module, ``m`` its ``dims``."""
    h = arch.hidden(w, tokens, m, numerics)
    B, S, d = h.shape
    n = S // chunk
    hs = jnp.moveaxis(h.reshape(B, n, chunk, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(B, n, chunk), 1, 0)

    @jax.checkpoint
    def part(hc, lc):
        lg = arch.logits_at(w, hc, numerics)
        lse = jax.scipy.special.logsumexp(lg, -1)
        pick = jnp.take_along_axis(lg, lc[..., None], -1)[..., 0]
        return jnp.sum(lse - pick)

    tot = jax.lax.map(lambda a: part(*a), (hs, ls)).sum()
    return tot / (B * S)


# ---------------------------------------------------------------------------
# Training: the first steps of a delayed AdamW run
# ---------------------------------------------------------------------------

def _global_clip(g: dict, max_norm: float) -> dict:
    if max_norm <= 0:
        return g
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    s = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda x: x * s, g)


@functools.lru_cache(maxsize=8)
def _train_fns(config_json: str, numerics: str, opt_json: str):
    import json
    config = json.loads(config_json)
    arch, m = harness.arch(config), W.dims(config)
    o = json.loads(opt_json)

    @jax.jit
    def grad(w, tokens, labels):
        return jax.value_and_grad(loss, argnums=1)(arch, w, tokens, labels,
                                                   m, numerics)

    # the gradient and the moments are dead after the update: donated, so
    # that the versions the ring keeps fit beside them at the cell's size
    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def adamw(g, mu, nu, p, step):
        g = _global_clip(g, o["grad_clip"])
        t = step.astype(jnp.float32)

        def one(g, mu, nu, p):
            mu = o["b1"] * mu + (1 - o["b1"]) * g
            nu = o["b2"] * nu + (1 - o["b2"]) * g * g
            upd = (mu / (1 - o["b1"] ** t)) / (
                jnp.sqrt(nu / (1 - o["b2"] ** t)) + o["eps"])
            return p - o["lr"] * (upd + o["weight_decay"] * p), mu, nu

        out = jax.tree.map(one, g, mu, nu, p)
        pick = lambda i: jax.tree.map(  # noqa: E731
            lambda x: x[i], out, is_leaf=lambda x: isinstance(x, tuple))
        return pick(0), pick(1), pick(2), g

    return grad, adamw


def check_steps(traffic: dict) -> int:
    """The first steps compared: ``delta + 3``.  Steps up to ``delta`` read
    the initial weights the ring was filled with; steps ``delta + 1`` and
    ``delta + 2`` read the versions the first two steps wrote into it."""
    return int(traffic["delta"]) + 3


def train_readings(config: dict, traffic: dict, seed: int,
                   numerics: str = "f32", half_batch: bool = False,
                   ring_unwritten: bool = False) -> dict:
    """Losses of the first ``check_steps`` steps, the per-leaf norm of the
    first clipped gradient and of the change of the weights after those
    steps.  Step ``k`` takes its gradient at the weights of step ``max(k -
    delta, 0)``, as the ParameterDB ring serves them, and applies it to the
    current weights.  Two faults: ``half_batch`` drops half the rows and
    averages over the rest; ``ring_unwritten`` reads the initial weights at
    every step, as a ring that is never written serves them."""
    import json
    from bench import generator
    from bench.compare import diff_norms, leaf_norms
    grad, adamw = _train_fns(json.dumps(config, sort_keys=True), numerics,
                             json.dumps(traffic["optimizer"], sort_keys=True))
    delta, n_steps = int(traffic["delta"]), check_steps(traffic)

    def version(k: int) -> int:
        return 0 if ring_unwritten else max(k - delta, 0)

    w0 = W.make(config, seed)
    reads = {version(k) for k in range(n_steps)}
    kept = {0: w0}
    p = w0
    mu = jax.tree.map(jnp.zeros_like, w0)
    nu = jax.tree.map(jnp.zeros_like, w0)
    losses, first = [], None
    for k in range(n_steps):
        tokens, labels = generator.train_batch(traffic, config, seed, k)
        if half_batch:
            tokens, labels = tokens[: len(tokens) // 2], labels[: len(labels) // 2]
        lval, g = grad(kept[version(k)], tokens, labels)
        p, mu, nu, g = adamw(g, mu, nu, p, jnp.asarray(k + 1, jnp.int32))
        losses.append(float(lval))
        if k == 0:
            first = jax.device_get(leaf_norms(g))
        del g
        if k + 1 in reads:
            kept[k + 1] = p
        kept = {v: x for v, x in kept.items()
                if any(version(j) == v for j in range(k + 1, n_steps))}
    change = jax.device_get(diff_norms(p, w0))
    return {"loss": losses, "grad": W.leaves(first), "change": W.leaves(change)}


# ---------------------------------------------------------------------------
# Serving: logits at the positions that produced served tokens
# ---------------------------------------------------------------------------

BUCKET = 256


@functools.lru_cache(maxsize=8)
def _serve_fn(config_json: str, numerics: str):
    import json
    config = json.loads(config_json)
    arch, m = harness.arch(config), W.dims(config)

    @jax.jit
    def fn(w, tokens, positions):
        h = arch.hidden(w, tokens[None], m, numerics)[0]
        return arch.logits_at(w, h[positions], numerics)
    return fn


def served_logits(config: dict, w: dict, prompt, served,
                  numerics: str = "f32") -> np.ndarray:
    """Logits (n, V) at the positions whose argmax is served token i: the
    prompt followed by the served tokens, teacher-forced, padded at the end
    to a multiple of ``BUCKET`` (causal, so padding changes nothing
    before it)."""
    import json
    seq = list(prompt) + list(served[:-1])
    n = len(seq) + (-len(seq)) % BUCKET
    tokens = np.zeros((n,), np.int32)
    tokens[: len(seq)] = seq
    positions = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served),
                          dtype=np.int32)
    fn = _serve_fn(json.dumps(config, sort_keys=True), numerics)
    return np.asarray(fn(w, jnp.asarray(tokens), jnp.asarray(positions)))
