"""Training driver: the full stack in one process, on the default device.

Runs the real training loop (any zoo arch: ``--smoke`` widths on a CPU,
published widths on a TPU — ``chip_smoke.py`` runs SmolLM-360M on one
v5e) with:
  * the sync mode (bsp / datacentric / ssp).  It selects a sharded
    parameter layout only where a mesh is built (launch/dryrun.py and
    ``chip_smoke.py --chips 4``); this single-process driver builds none,
    so here ``--mode`` changes no layout (it picks the server backend's
    policy),
  * delta-staleness via the unified ParameterDB train engine
    (repro.pdb.jax_backend), with Op/staleness telemetry,
  * a multi-process sharded parameter-server backend (--backend server):
    the raveled parameter vector is split into --param-chunks chunks,
    hash-sharded over --shards server processes, and trained by --workers
    client threads under the same consistency policies (Def-3 partitioned
    SGD: each worker reads all chunks, updates its own chunk group),
  * atomic checkpointing + auto-resume (--resume),
  * failure injection drills (--fail-at-step; --kill-shard-at-step for a
    parameter-server shard death + snapshot-restart drill), and
  * deterministic data (batch t depends only on (seed, t)).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
      --steps 50 --ckpt-dir /tmp/ck
  PYTHONPATH=src python -m repro.launch.train --arch rwkv6-1.6b --smoke \
      --steps 50 --delta 2
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
      --steps 8 --backend server --shards 2 --workers 2 --delta 1
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from ..checkpoint import latest_step, load_checkpoint, save_checkpoint
from ..configs import get_config, get_smoke_config
from ..core.sync_jax import SyncConfig
from ..data import LMBatchSpec, make_lm_batch
from ..models import paramlib
from ..models.transformer import model_specs
from ..optim import OptConfig, make_optimizer
from ..runtime.fault import FailureInjector, InjectedFailure, RetryPolicy, \
    run_with_recovery
from .steps import make_lm_grad_fn, make_train_engine
from .tuning import apply_tuning


def build(args):
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    specs = model_specs(cfg)
    params = paramlib.init_tree(specs, jax.random.PRNGKey(args.seed),
                                dtype=cfg.param_dtype)
    opt = make_optimizer(OptConfig(name=args.optimizer, lr=args.lr,
                                   compression=args.compression))
    sync = SyncConfig(mode=args.mode, delta=args.delta,
                      compression=args.compression, remat=args.remat)
    spec = LMBatchSpec(batch=args.batch, seq_len=args.seq,
                       vocab_size=cfg.vocab_size,
                       media_tokens=cfg.n_frontend_tokens,
                       media_dim=cfg.d_frontend, seed=args.seed)
    return cfg, params, opt, sync, spec


def run_server_backend(args) -> dict:
    """Train against the multi-process sharded ParameterDB
    (:mod:`repro.pdb.server`): parameter-server-style SGD on the raveled
    parameter vector.  Worker ``k`` reads every chunk (policy-admitted,
    cache-served when admissible), computes LM grads on its own
    deterministic batch stream, and writes its owned chunk group — the
    Def-3 program with one logical worker owning many chunks."""
    import numpy as np
    from jax.flatten_util import ravel_pytree

    from ..core.history import is_sequentially_correct
    from ..pdb.server import ShardCluster
    from ..runtime.fault import Backoff, ShardDeathPlan

    cfg, params, _opt, sync, spec = build(args)
    grad_fn = jax.jit(make_lm_grad_fn(cfg, sync))
    flat, unravel = ravel_pytree(params)
    theta0 = jax.device_get(flat)
    p = args.workers
    m = args.param_chunks if args.param_chunks > 0 else 2 * p
    bounds = np.linspace(0, theta0.size, m + 1).astype(int)
    chunks = [theta0[a:b].copy() for a, b in zip(bounds[:-1], bounds[1:])]
    owned = {k: [c for c in range(m) if c % p == k] for k in range(p)}
    policy = {"datacentric": "dc", "bsp": "bsp", "ssp": "ssp"}[args.mode]

    plan = None
    snapshot_dir = args.snapshot_dir or None
    if args.kill_shard_at_step >= 0:
        plan = ShardDeathPlan(kill_at_step=args.kill_shard_at_step,
                              shard=args.shards - 1, restart=True)
        if snapshot_dir is None:
            import tempfile
            snapshot_dir = tempfile.mkdtemp(prefix="pdb-shards-")

    cluster = ShardCluster(chunks, p, args.shards, policy=policy,
                           delta=args.delta, record=True,
                           snapshot_dir=snapshot_dir,
                           batched=args.rpc != "per-op")
    losses: list[float] = []
    errors: list[BaseException] = []
    t0 = time.time()

    def worker(k: int, db) -> None:
        try:
            for itr in range(1, args.steps + 1):
                if k == 0 and plan is not None:
                    plan.maybe_kill(itr, cluster)
                theta = np.concatenate(db.read_all(k, itr))
                pk = unravel(jnp.asarray(theta, dtype=flat.dtype))
                batch = make_lm_batch(spec, (itr - 1) * p + k)
                loss, grads = grad_fn(pk, batch)
                g = jax.device_get(ravel_pytree(grads)[0])
                # one write_batch per owner shard for the whole owned group
                # (per-chunk round-trips on the per-op path)
                db.write_many(k, [
                    (c, itr, theta[int(bounds[c]):int(bounds[c + 1])]
                     - args.lr * g[int(bounds[c]):int(bounds[c + 1])])
                    for c in owned[k]])
                if k == 0:
                    losses.append(float(loss))
                    if (itr - 1) % args.log_every == 0 or itr == args.steps:
                        print(f"step {itr - 1:5d} loss {float(loss):.4f} "
                              f"[server] {(time.time() - t0):.1f}s",
                              flush=True)
        except BaseException as e:
            errors.append(e)
            raise

    import threading
    with cluster:
        clients = [cluster.make_client(k, backoff=Backoff(max_retries=12))
                   for k in range(p)]
        threads = [threading.Thread(target=worker, args=(k, clients[k]),
                                    daemon=True) for k in range(p)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        pulled = cluster.pull()
        retries = sum(c.telemetry.stats.retried_steps for c in clients)
        cache_hits = sum(c.stats["cache_hits"] + c.stats["cache_validated"]
                         for c in clients)
        for c in clients:
            c.close()
    tele = pulled.summary()
    tele["retried_steps"] += retries
    seq_ok = is_sequentially_correct(pulled.history, p)
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f}) "
          f"[server: {args.shards} shards x {p} workers, {m} chunks, "
          f"{tele['reads']}r/{tele['writes']}w "
          f"max_staleness={tele['max_staleness']:.0f} "
          f"cache_served={cache_hits} rpc_retries={retries} "
          f"seq_correct={seq_ok}]")
    return {"first_loss": losses[0], "final_loss": losses[-1],
            "telemetry": tele, "sequentially_correct": seq_ok,
            "rpc_retries": retries}


def make_parser() -> argparse.ArgumentParser:
    """The driver's command line (also used by ``chip_smoke.py``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--mode", choices=["datacentric", "bsp", "ssp"],
                    default="datacentric")
    ap.add_argument("--delta", type=int, default=0)
    ap.add_argument("--compression", choices=["none", "int8"], default="none")
    ap.add_argument("--remat", default="none")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at-step", type=int, default=-1,
                    help="inject a crash (restart drill)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--backend", choices=["engine", "server"],
                    default="engine",
                    help="engine: in-process ParameterDB train engine; "
                         "server: multi-process sharded parameter server")
    ap.add_argument("--shards", type=int, default=2,
                    help="server backend: number of shard processes")
    ap.add_argument("--workers", type=int, default=2,
                    help="server backend: number of client worker threads")
    ap.add_argument("--param-chunks", type=int, default=0,
                    help="server backend: chunks the raveled parameter "
                         "vector is split into (0 = 2*workers)")
    ap.add_argument("--kill-shard-at-step", type=int, default=-1,
                    help="server backend: kill+restart the last shard at "
                         "this step (shard-death drill)")
    ap.add_argument("--rpc", choices=["batched", "per-op"], default="batched",
                    help="server backend: protocol-v2 batched/pipelined "
                         "RPC (default) or per-chunk v1 round-trips")
    ap.add_argument("--snapshot-dir", default="",
                    help="server backend: shard snapshot directory "
                         "(crash-restart survival)")
    return ap


def main(argv=None) -> dict:
    args = make_parser().parse_args(argv)
    apply_tuning()

    if args.backend == "server":
        return run_server_backend(args)

    cfg, params, opt, sync, spec = build(args)
    start = 0

    # one ParameterDB engine for both paths (sync dict state at delta=0,
    # device ring buffer otherwise) — see repro.pdb.jax_backend
    engine = make_train_engine(cfg, opt, sync, params)
    state = engine.init_state()

    if args.resume and args.ckpt_dir:
        ls = latest_step(args.ckpt_dir)
        if ls is not None:
            state = load_checkpoint(args.ckpt_dir, ls, state)
            state = jax.tree.map(jnp.asarray, state)
            start = ls
            print(f"resumed from step {ls}")

    injector = FailureInjector(
        fail_steps=(args.fail_at_step,) if args.fail_at_step >= 0 else ())
    policy = RetryPolicy()
    losses = []
    t0 = time.time()
    for step in range(start, args.steps):
        batch = make_lm_batch(spec, step)
        try:
            state, metrics, outcome = run_with_recovery(
                engine.step_fn, state, batch, step, policy, injector,
                is_finite=lambda m: bool(jnp.isfinite(m["loss"]).all()),
                telemetry=engine.telemetry)
        except InjectedFailure:
            print(f"CRASH at step {step} (injected); restart with --resume")
            raise SystemExit(17)
        if outcome != "skipped":    # skipped steps never updated parameters
            engine.record_step()
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {loss:.4f} [{outcome}] "
                  f"{(time.time()-t0):.1f}s", flush=True)
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, step + 1, state)
    tele = engine.telemetry.summary()
    if not losses:   # resumed from a checkpoint at/after the last step:
        # don't re-save — it would label step-`start` weights as args.steps
        print(f"nothing to do: resumed at step {start} >= {args.steps}")
        return {"first_loss": None, "final_loss": None, "telemetry": tele}
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, args.steps, state)
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f}) "
          f"[pdb: {tele['reads']}r/{tele['writes']}w "
          f"max_staleness={tele['max_staleness']:.0f} "
          f"retried={tele['retried_steps']} skipped={tele['skipped_steps']}]")
    return {"first_loss": losses[0], "final_loss": losses[-1],
            "telemetry": tele}


if __name__ == "__main__":
    main()
