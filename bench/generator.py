"""The one traffic generator: reads a mix's data file and the seed.

Every seed gets the same work: the same set of requests (pairs of prompt
and output lengths) and the same set of gaps between arrivals, drawn at
fixed quantiles of the mix's distributions and paired in one fixed way, in
an order the seed shuffles, with token ids the seed draws.  So runs with
different seeds differ in the order of the requests, not in what they are.

Training mixes (``kind: "train"``): a batch of ``batch`` rows of ``seq``
tokens per step, uniform over the vocabulary, labels the next token; made on
the device from (seed, step), and no two rows alike.

Serving mixes (``kind: "serve"``): open-loop arrivals at ``rate`` requests a
second over the window, prompt and output lengths lognormal (``median``,
``sigma``) clipped to ``[min, max]``.
"""
from __future__ import annotations

import functools
import math
from statistics import NormalDist

import numpy as np

from bench import harness


# ---------------------------------------------------------------------------
# Training batches
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=8)
def _batch_fn(batch: int, seq: int, vocab: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key, step):
        x = jax.random.randint(jax.random.fold_in(key, step),
                               (batch, seq + 1), 0, vocab, jnp.int32)
        return x[:, :-1], x[:, 1:]
    return make


def train_batch(traffic: dict, config: dict, seed: int, step: int):
    """(tokens, labels), each (batch, seq) int32 on the default device."""
    make = _batch_fn(traffic["batch"], traffic["seq"], config["vocab_size"])
    return make(harness.seed_key(seed, harness.STREAM_TRAFFIC), step)


# ---------------------------------------------------------------------------
# Serving requests
# ---------------------------------------------------------------------------

def quantile_lengths(n: int, spec: dict) -> np.ndarray:
    """``n`` lengths at the mid-quantiles (i + 1/2)/n of a lognormal with
    the given median and sigma, rounded and clipped."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def quantile_gaps(n: int, rate: float) -> np.ndarray:
    """``n`` gaps at the mid-quantiles of an exponential of ``rate``."""
    return np.array([-math.log(1.0 - (i + 0.5) / n) / rate
                     for i in range(n)])


def request_count(traffic: dict, seconds: float) -> int:
    return max(1, int(round(traffic["rate"] * seconds)))


def serve_requests(traffic: dict, config: dict, seed: int,
                   seconds: float) -> list:
    """The window's requests: arrivals from 0 through about ``seconds``."""
    from repro.serve.workload import Request
    n = request_count(traffic, seconds)
    rng = harness.np_rng(seed, harness.STREAM_TRAFFIC)
    gaps = rng.permutation(quantile_gaps(n, traffic["rate"]))
    arrivals = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    # the pairing is fixed (by n alone); the seed orders the pairs
    pair = np.random.default_rng(n).permutation(n)
    order = rng.permutation(n)
    prompt = quantile_lengths(n, traffic["prompt"])[order]
    output = quantile_lengths(n, traffic["output"])[pair][order]
    if (prompt + output).max() > traffic["engine"]["cache_len"]:
        raise harness.BenchError("a request would not fit cache_len")
    vocab = config["vocab_size"]
    return [Request(rid=i, arrival=float(arrivals[i]),
                    prompt=tuple(int(t) for t in
                                 rng.integers(0, vocab, int(prompt[i]))),
                    gen_len=int(output[i]))
            for i in range(n)]


def warmup_requests(traffic: dict, config: dict, seed: int) -> list:
    """A few requests, due at once, that take every path the window's
    requests take: a prompt of at least one chunk and one shorter."""
    from repro.serve.workload import Request
    rng = harness.np_rng(seed, harness.STREAM_TRAFFIC + 100)
    C = traffic["engine"]["prefill_chunk"]
    lens = sorted({traffic["prompt"]["max"], max(C, traffic["prompt"]["min"]),
                   traffic["prompt"]["min"]})
    return [Request(rid=10 ** 6 + i, arrival=0.0,
                    prompt=tuple(int(t) for t in rng.integers(
                        0, config["vocab_size"], n)), gen_len=3)
            for i, n in enumerate(lens)]
