"""Driver: training steps through the program's ParameterDB train engine.

Set-up builds one engine (``repro.launch.steps.make_train_engine``) with its
state from weights made from the seed, and drives it through its first
``delta + 3`` steps (``reference.check_steps``: the last two read versions
the ring's own writes put there) exactly as ``repro.launch.train.main``
drives steps: ``run_with_recovery(engine.step_fn, ...)``, ``record_step``
for a step not skipped, and the loss read back to the host.  Those steps
compile the step and give the readings ``correct`` compares with the
reference; the window then continues the same engine and state with the
same calls for ``--seconds``.

The traffic file gives the batch, the sequence length, the admissible
staleness ``delta`` of the ring, the remat policy, the kernels and the
AdamW settings.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp

from bench import compare, generator, harness, program, reference, weights


def build_engine(cell: harness.Cell, seed: int):
    """(engine, state) of the program, holding the weights of ``seed``."""
    from repro.core.sync_jax import SyncConfig
    from repro.launch.steps import make_train_engine
    from repro.optim import OptConfig, make_optimizer
    t = cell.traffic
    cfg = program.model_config(cell.config)
    o = t["optimizer"]
    opt = make_optimizer(OptConfig(
        name="adamw", lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
        weight_decay=o["weight_decay"], grad_clip=o["grad_clip"]))
    sync = SyncConfig(delta=t["delta"], remat=t["remat"])
    engine = make_train_engine(cfg, opt, sync, weights.make(cell.config, seed))
    return engine, engine.init_state()


def run(cell: harness.Cell, seed: int, seconds: float, trace_dir,
        t_start: float, devices) -> dict:
    from repro.runtime.fault import RetryPolicy, run_with_recovery
    t = cell.traffic
    check = reference.check_steps(t)
    engine, state = build_engine(cell, seed)
    mask = jnp.ones((t["batch"], t["seq"]), jnp.float32)
    policy = RetryPolicy()
    losses: list[float] = []
    skipped = 0

    def step(state, i):
        nonlocal skipped
        with jax.profiler.TraceAnnotation("bench.batch"):
            tokens, labels = generator.train_batch(t, cell.config, seed, i)
            batch = {"tokens": tokens, "labels": labels, "mask": mask}
        with jax.profiler.TraceAnnotation("bench.step"):
            state, metrics, outcome = run_with_recovery(
                engine.step_fn, state, batch, i, policy,
                is_finite=lambda m: bool(jnp.isfinite(m["loss"]).all()),
                telemetry=engine.telemetry)
        with jax.profiler.TraceAnnotation("bench.host"):
            if outcome == "skipped":
                skipped += 1
            else:
                engine.record_step()
            losses.append(float(metrics["loss"]))
        return state

    # set-up: the first steps, read for `correct`
    grad = None
    for i in range(check):
        state = step(state, i)
        if i == 0:
            b1 = t["optimizer"]["b1"]
            m = jax.device_get(compare.leaf_norms(state.opt_state["m"]))
            grad = {k: float(v) / (1 - b1)
                    for k, v in weights.leaves(m).items()}
    change = weights.leaves(jax.device_get(compare.diff_norms(
        state.params, weights.make(cell.config, seed))))
    prog = {"loss": list(losses), "grad": grad,
            "change": {k: float(v) for k, v in change.items()}}
    counter = harness.CompileCounter()
    setup_s = time.perf_counter() - t_start

    # the window
    n_trace = int(t["trace_steps"])
    traced = None
    i = check
    t0 = time.perf_counter()
    while True:
        if trace_dir is not None and i == check:
            jax.profiler.start_trace(str(trace_dir))
            span = jax.profiler.TraceAnnotation("bench.window")
            span.__enter__()
            t_tr = time.perf_counter()
        state = step(state, i)
        i += 1
        if trace_dir is not None and i == check + n_trace:
            span.__exit__(None, None, None)
            traced = {"steps": n_trace, "host_s": time.perf_counter() - t_tr}
            jax.profiler.stop_trace()
        if time.perf_counter() - t0 >= seconds and (
                trace_dir is None or traced is not None):
            break
    elapsed = time.perf_counter() - t0
    n_steps = i - check
    compiles_in_window = counter.n

    peak = harness.memory_peak_bytes(devices)
    del state, engine
    gc.collect()

    ref = reference.train_readings(cell.config, t, seed)
    readings = compare.train_readings(prog, ref)
    tokens_per_step = t["batch"] * t["seq"]
    return {
        "setup_s": setup_s,
        "e2e": {"train_tok_s": n_steps * tokens_per_step / elapsed},
        "attempted": n_steps + check,
        "failed": skipped,
        "checks": compare.checks(readings, cell.limits),
        "memory_peak_bytes": peak,
        "notes": {"window_steps": n_steps, "window_s": elapsed,
                  "compiles_in_window": compiles_in_window,
                  "losses": losses[:check] + losses[-1:]},
        "layer": {"kind": "train", "tokens_per_step": tokens_per_step,
                  "traced": traced},
    }
