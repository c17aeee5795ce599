"""Serving driver: the continuous-batching engine on an open-loop workload.

Thin CLI wrapper over :class:`repro.serve.ServeEngine`: prompts are
prefilled into paged per-sequence KV/recurrent caches, then decoded
with sequences joining and leaving the batch mid-decode (``--static``
restores the drain-the-batch baseline — same engine, same cache,
admission barrier only).  Arrivals follow a Poisson process at
``--rate`` requests/second.

Prompt-path knobs:

* ``--prefill-chunk C`` — chunked prefill: at most one C-token chunk per
  scheduler tick, interleaved with decode (no drain barrier).
* ``--prefix-cache`` — prompt-prefix caching: requests adopt the KV
  pages of their longest already-computed prefix (implies chunked
  prefill; use ``--shared-prefix`` traffic to see hits).
* ``--temperature`` / ``--top-p`` / ``--sample-seed`` — nucleus
  sampling, deterministically keyed per (request, token index);
  temperature 0 (default) is greedy argmax.

The decode loop dispatches through the kernel layer (repro.kernels.ops):
``--kernel-impl pallas`` runs the fused GQA decode-attention, paged
gather/prefill-attention and grouped MoE kernels on TPU; ``interpret``
emulates them on CPU (slow — parity checks only); the default follows
``REPRO_KERNEL_IMPL`` (XLA reference).

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --requests 16 --rate 4 --batch 4 --prefix-cache --shared-prefix
"""
from __future__ import annotations

import argparse
import os

import jax
import numpy as np

from ..configs import get_config, get_smoke_config
from ..models import paramlib
from ..models.transformer import model_specs
from ..serve import (ServeConfig, ServeEngine, open_loop_requests,
                     shared_prefix_requests)
from .tuning import apply_tuning


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="sequence slots (B_max)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=4.0,
                    help="Poisson arrival rate, requests/second")
    ap.add_argument("--prompt-len", type=int, default=None,
                    help="fix the prompt length (default: sample 8/16/32)")
    ap.add_argument("--gen", type=int, default=None,
                    help="fix the generation length (default: sample "
                         "4/8/16/48)")
    ap.add_argument("--static", action="store_true",
                    help="drain-the-batch baseline (continuous off)")
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--cache-len", type=int, default=None,
                    help="logical KV ring length (default: fits the "
                         "longest prompt+gen, page-aligned)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: tokens per chunk, one chunk "
                         "per tick interleaved with decode (0 = whole-"
                         "prompt prefill at admission)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="prompt-prefix caching: adopt cached KV pages "
                         "for shared prompt prefixes (implies chunked "
                         "prefill at --page-size granularity)")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="shared-prefix traffic (hot system prompts + "
                         "unique suffixes) instead of fully random "
                         "prompts")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (with --temperature)")
    ap.add_argument("--sample-seed", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kernel-impl", choices=["ref", "pallas", "interpret"],
                    default=None, help="kernel dispatch (REPRO_KERNEL_IMPL)")
    args = ap.parse_args(argv)
    if args.kernel_impl:
        os.environ["REPRO_KERNEL_IMPL"] = args.kernel_impl
    apply_tuning()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = paramlib.init_tree(model_specs(cfg), jax.random.PRNGKey(0),
                                dtype=cfg.param_dtype)

    gen_lens = (args.gen,) if args.gen else (4, 8, 16, 48)
    if args.shared_prefix:
        plen = args.prompt_len or 32
        requests = shared_prefix_requests(
            args.requests, args.rate, cfg.vocab_size,
            prefix_len=plen - plen // 4, suffix_lens=(plen // 4,),
            gen_lens=gen_lens, seed=args.seed)
        max_prompt = plen
    else:
        prompt_lens = (args.prompt_len,) if args.prompt_len else (8, 16, 32)
        requests = open_loop_requests(
            args.requests, args.rate, cfg.vocab_size,
            prompt_lens=prompt_lens, gen_lens=gen_lens, seed=args.seed)
        max_prompt = max(prompt_lens)
    page = args.page_size
    need = max_prompt + max(gen_lens)
    cache_len = args.cache_len or -(-need // page) * page

    scfg = ServeConfig(batch_size=args.batch, page_size=page,
                       cache_len=cache_len, continuous=not args.static,
                       prefill_chunk=args.prefill_chunk,
                       prefix_cache=args.prefix_cache,
                       temperature=args.temperature, top_p=args.top_p,
                       sample_seed=args.sample_seed)
    report = ServeEngine(cfg, params, scfg).run(requests)

    print(f"{report.mode}: {report.total_tokens} tokens / "
          f"{report.n_requests} requests in {report.duration:.2f}s "
          f"({report.tokens_per_sec:.1f} tok/s, "
          f"slot utilization {report.utilization:.0%})")
    print(f"latency p50 {report.latency_p50*1e3:.0f}ms "
          f"p99 {report.latency_p99*1e3:.0f}ms over {report.decode_steps} "
          f"decode steps")
    print(f"ttft p50 {report.ttft_p50*1e3:.0f}ms "
          f"p99 {report.ttft_p99*1e3:.0f}ms; "
          f"{report.prefill_chunks} prefill chunks, "
          f"prefix hit rate {report.prefix_hit_rate:.0%}")
    done = report.requests
    wait = [f.t_start - f.arrival for f in done]
    prompt = [f.t_first - f.t_start for f in done]
    tail = sum(f.tail_tokens for f in done)
    print(f"ttft p99 split: waiting {np.percentile(wait, 99)*1e3:.0f}ms, "
          f"prompt {np.percentile(prompt, 99)*1e3:.0f}ms; "
          f"{tail / sum(len(r.prompt) for r in requests):.0%} of prompt "
          f"tokens fed one a tick through decode")
    first = report.outputs[min(report.outputs)]
    print("first request:", list(first[:12]))
    return {"report": report, "tok_per_s": report.tokens_per_sec}


if __name__ == "__main__":
    main()
