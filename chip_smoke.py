"""Bring-up check: the ParameterDB train, serve and parameter-server paths on
a TPU at the published widths of SmolLM-360M (random weights from a seed).

Run from the root of a checkout:

    python chip_smoke.py             # one chip, the three phases below
    python chip_smoke.py --chips 4   # four chips: sharded training only

One chip, in one process, with ``REPRO_KERNEL_IMPL=pallas``:

1. train   — the ``repro.launch.train`` engine path: the device ParameterDB
   ring (``delta=2``, packed layout read through the Pallas ``ring_gather``),
   AdamW, batch 8 x seq 1024, full remat.  Prints the losses, the compile
   time, the warm step times and the peak device memory; checks that the
   losses are finite, that no step was skipped and that the Pallas ring read
   equals the XLA reference bit for bit.
2. serve   — ``ServeEngine`` (the ``repro.launch.serve`` path) answers a few
   requests through chunked prefill and paged decode on the Pallas kernels;
   then one paged decode step's logits, in float32, are compared with the
   ``ref`` kernels within the float32 tolerance of tests/test_kernels.py.
3. pserver — ``run_server_backend``: 2 shard processes (CPU only) x 2 worker
   threads for 2 iterations, gradients computed on the chip; checks
   ``seq_correct=True``.

Four chips (``--chips 4``): the sharded SPMD train step of
``launch/dryrun.py`` on a (data=4, model=1) mesh of the real devices —
``datacentric`` (parameters sharded over ``data``) and ``bsp`` (replicated)
— against the same step on one chip, all with ``REPRO_KERNEL_IMPL=ref``
(GSPMD cannot partition a Pallas kernel).  Their losses must agree within
bf16 tolerance, and the datacentric parameters must be spread over all four
devices.

Nothing is caught: a phase that fails ends the run with a traceback and a
non-zero exit.  Only when every phase passed is the last line of stdout the
JSON object ``{"ok": true, "device": {...}}``.  With no TPU (for example
``JAX_PLATFORMS=cpu``) the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from pathlib import Path

ARCH = "smollm-360m"
SEED = 0
TRAIN_STEPS = 4
SHARDED_STEPS = 3
F32_TOL = dict(rtol=2e-5, atol=2e-5)      # tests/test_kernels.py TOL[f32]
BF16_RTOL = 2e-2                          # tests/test_kernels.py TOL[bf16]


def log(msg: str) -> None:
    print(msg, flush=True)


def phase_train(jax, train) -> None:
    import numpy as np
    from repro.data import make_lm_batch
    from repro.kernels import ops as kops
    from repro.launch.steps import make_train_engine

    args = train.make_parser().parse_args(
        ["--arch", ARCH, "--delta", "2", "--batch", "8", "--seq", "1024",
         "--remat", "full", "--optimizer", "adamw", "--lr", "1e-4",
         "--seed", str(SEED)])
    cfg, params, opt, sync, spec = train.build(args)
    engine = make_train_engine(cfg, opt, sync, params)
    state = engine.init_state()
    del params
    n_params = sum(x.size for x in jax.tree.leaves(state.params))

    batch = make_lm_batch(spec, 0)
    t0 = time.perf_counter()
    step = engine.step_fn.lower(state, batch).compile()
    compile_s = time.perf_counter() - t0
    assert "tpu_custom_call" in step.as_text(), \
        "the compiled train step holds no Pallas kernel"

    losses, step_s = [], []
    for i in range(TRAIN_STEPS):
        batch = jax.block_until_ready(make_lm_batch(spec, i))
        t = time.perf_counter()
        state, metrics = step(state, batch)
        jax.block_until_ready((state, metrics))
        step_s.append(time.perf_counter() - t)
        engine.record_step()
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)), f"non-finite train loss: {losses}"
    assert int(state.step) == TRAIN_STEPS, "a train step was skipped"

    # the stale read the next step takes, through the Pallas kernel and the
    # XLA reference, on the real ring
    ring_ok = True
    idx = jax.numpy.mod(state.ptr - sync.delta, sync.delta + 1)
    for hist in state.hist.values():
        ring_ok &= bool(jax.numpy.array_equal(
            kops.ring_gather(hist, idx),
            kops.ring_gather(hist, idx, impl="ref")))
    assert ring_ok, "Pallas ring_gather differs from the reference"

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"train: {ARCH} params={n_params} delta=2 batch=8x1024 "
        f"ring_rows={[tuple(h.shape) for h in state.hist.values()]}")
    log(f"train: losses={losses}")
    log(f"train: compile_s={compile_s} warm_step_s={step_s[1:]} "
        f"(one-chip bring-up observation, not a benchmark)")
    log(f"train: peak_bytes_in_use={peak} "
        f"bytes_limit={stats.get('bytes_limit')} ring_gather==ref {ring_ok}")
    assert peak is None or peak < 16 * 2**30, "train step exceeds 16 GiB"


def phase_serve(jax) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models import paramlib
    from repro.models.transformer import decode_step, model_specs, prefill
    from repro.serve import ServeConfig, ServeEngine, open_loop_requests
    from repro.serve.paged_cache import (PageAllocator, init_paged_cache,
                                         make_join_fn)

    cfg = get_config(ARCH)
    params = paramlib.init_tree(model_specs(cfg), jax.random.PRNGKey(SEED),
                                dtype=cfg.param_dtype)

    scfg = ServeConfig(batch_size=8, page_size=16, cache_len=512,
                       prefill_chunk=128)
    reqs = open_loop_requests(8, 1000.0, cfg.vocab_size,
                              prompt_lens=(200, 300), gen_lens=(16, 32),
                              seed=SEED)
    report = ServeEngine(cfg, params, scfg).run(reqs)
    assert report.n_requests == len(reqs)
    for r in reqs:
        out = report.outputs[r.rid]
        assert len(out) == r.gen_len, (r.rid, len(out), r.gen_len)
        assert all(0 <= t < cfg.vocab_size for t in out)
    log(f"serve: {report.n_requests} requests, {report.total_tokens} tokens, "
        f"{report.decode_steps} decode steps, {report.prefill_chunks} "
        f"prefill chunks in {report.duration}s (one-chip bring-up "
        f"observation, not a benchmark); first request "
        f"{list(report.outputs[reqs[0].rid][:8])}")

    # one paged decode step in float32: Pallas kernels vs the XLA reference
    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32)
    B, L, page = 4, 512, 16
    alloc = PageAllocator(cfg32, B, L, page)
    cache = init_paged_cache(cfg32, B, L, page)
    join = jax.jit(make_join_fn(cfg32, L, page))
    pre = jax.jit(lambda p, t: prefill(p, t, cfg32, cache_len=L))
    rng = np.random.default_rng(SEED)
    tok = np.zeros((B, 1), np.int32)
    pos = np.zeros((B,), np.int32)
    for b in range(B):
        S = (96, 160)[b % 2]                 # sequences at their own position
        prompt = rng.integers(0, cfg.vocab_size, (1, S), dtype=np.int32)
        logits, dense = pre(params, jnp.asarray(prompt))
        rows = {Lc: jnp.asarray(ids) for Lc, ids in alloc.alloc(b).items()}
        cache = join(cache, dense, jnp.asarray(b, jnp.int32), rows)
        tok[b, 0] = int(jnp.argmax(logits[0]))
        pos[b] = S
    tok, pos = jnp.asarray(tok), jnp.asarray(pos)

    def decode_logits(impl):
        prev = os.environ["REPRO_KERNEL_IMPL"]
        os.environ["REPRO_KERNEL_IMPL"] = impl      # read at trace time
        try:
            fn = jax.jit(lambda p, c, t, q: decode_step(p, c, t, q, cfg32)[0])
            compiled = fn.lower(params, cache, tok, pos).compile()
        finally:
            os.environ["REPRO_KERNEL_IMPL"] = prev
        return compiled, np.asarray(compiled(params, cache, tok, pos))

    with jax.default_matmul_precision("highest"):
        c_pallas, got = decode_logits(os.environ["REPRO_KERNEL_IMPL"])
        c_ref, want = decode_logits("ref")
    assert "tpu_custom_call" in c_pallas.as_text(), \
        "the compiled decode step holds no Pallas kernel"
    assert "tpu_custom_call" not in c_ref.as_text()
    assert got.shape == (B, 1, cfg.vocab_size) and np.isfinite(got).all()
    log(f"serve: decode logits f32 pallas vs ref: max_abs_err="
        f"{np.abs(got - want).max()} max_abs_logit={np.abs(want).max()} "
        f"tol={F32_TOL}")
    np.testing.assert_allclose(got, want, **F32_TOL)


def phase_pserver(train) -> None:
    import numpy as np

    args = train.make_parser().parse_args(
        ["--arch", ARCH, "--backend", "server", "--shards", "2",
         "--workers", "2", "--steps", "2", "--batch", "2", "--seq", "1024",
         "--remat", "full", "--lr", "1e-4", "--log-every", "1",
         "--seed", str(SEED)])
    t0 = time.perf_counter()
    res = train.run_server_backend(args)
    log(f"pserver: 2 shards x 2 workers x 2 iterations in "
        f"{time.perf_counter() - t0}s (one-chip bring-up observation); "
        f"seq_correct={res['sequentially_correct']}")
    assert np.isfinite([res["first_loss"], res["final_loss"]]).all()
    assert res["sequentially_correct"], "parameter-server history is not " \
        "sequentially correct"


def phase_sharded(jax) -> None:
    import numpy as np
    from jax.sharding import Mesh
    from repro.configs import get_config
    from repro.core.sync_jax import SyncConfig
    from repro.data import LMBatchSpec, make_lm_batch
    from repro.launch.mesh import make_test_mesh
    from repro.launch.steps import make_sharded_train_step
    from repro.models import paramlib
    from repro.models.transformer import model_specs
    from repro.optim import OptConfig, make_optimizer

    cfg = get_config(ARCH)
    specs = model_specs(cfg)
    opt = make_optimizer(OptConfig(name="adamw", lr=1e-4))
    spec = LMBatchSpec(batch=8, seq_len=1024, vocab_size=cfg.vocab_size,
                       seed=SEED)
    batch_abs = jax.eval_shape(lambda: make_lm_batch(spec, 0))
    batch_axes = {k: ("batch", "seq") for k in batch_abs}
    four = make_test_mesh((4, 1))
    one = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))

    losses = {}
    for name, mode, mesh in (("datacentric", "datacentric", four),
                             ("bsp", "bsp", four),
                             ("one-chip", "datacentric", one)):
        sync = SyncConfig(mode=mode, remat="full")
        fn, _, _, (p_sh, o_sh, b_sh) = make_sharded_train_step(
            cfg, opt, sync, mesh, batch_abs, batch_axes)
        params = jax.jit(lambda: paramlib.init_tree(
            specs, jax.random.PRNGKey(SEED), dtype=cfg.param_dtype),
            out_shardings=p_sh)()
        opt_state = jax.jit(opt.init, out_shardings=o_sh)(params)

        leaves = jax.tree.leaves(params)
        dev0 = mesh.devices.flat[0]
        total = sum(x.nbytes for x in leaves)
        on_dev0 = sum(s.data.nbytes for x in leaves
                      for s in x.addressable_shards if s.device == dev0)
        holders = {s.device for x in leaves for s in x.addressable_shards}
        log(f"sharded[{name}]: mesh={dict(mesh.shape)} param bytes on "
            f"device 0: {on_dev0} of {total}; devices holding params: "
            f"{len(holders)}")
        if name == "datacentric":
            assert len(holders) == 4 and on_dev0 < 0.3 * total, \
                "datacentric parameters are not spread over the 4 devices"
        elif name == "bsp":
            assert len(holders) == 4 and on_dev0 == total, \
                "bsp parameters are not replicated"

        batch = jax.device_put(make_lm_batch(spec, 0), b_sh)
        t0 = time.perf_counter()
        step = fn.lower(params, opt_state, batch).compile()
        compile_s = time.perf_counter() - t0
        hlo = step.as_text()
        colls = {c: hlo.count(c) for c in ("all-gather", "reduce-scatter",
                                           "all-reduce")}
        losses[name] = []
        for i in range(SHARDED_STEPS):
            batch = jax.device_put(make_lm_batch(spec, i), b_sh)
            params, opt_state, metrics = step(params, opt_state, batch)
            losses[name].append(float(metrics["loss"]))
        log(f"sharded[{name}]: losses={losses[name]} compile_s={compile_s} "
            f"collectives={colls}")
        del params, opt_state, step
        gc.collect()

    for name in ("datacentric", "bsp"):
        np.testing.assert_allclose(losses[name], losses["one-chip"],
                                   rtol=BF16_RTOL)
    log(f"sharded: datacentric, bsp and one-chip losses agree within "
        f"rtol={BF16_RTOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded training comparison")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    # Mosaic kernels cannot be partitioned by GSPMD, so the sharded step
    # runs the XLA kernels
    os.environ["REPRO_KERNEL_IMPL"] = "pallas" if args.chips == 1 else "ref"
    from repro.launch import train
    from repro.launch.tuning import apply_tuning
    apply_tuning()
    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX default backend is "
              f"{jax.default_backend()!r})", file=sys.stderr)
        return 1
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked for, "
              f"{len(devices)} found", file=sys.stderr)
        return 1
    log(f"chip_smoke: {len(devices)} x {devices[0].device_kind}, "
        f"jax {jax.__version__}")

    if args.chips == 4:
        phase_sharded(jax)
    else:
        phase_train(jax, train)
        gc.collect()
        phase_serve(jax)
        gc.collect()
        phase_pserver(train)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
