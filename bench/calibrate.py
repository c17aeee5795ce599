"""Readings that set the limits of ``correct``: the program on many seeds,
the control and the faults on a few, each against the plain reference.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2,3 --seconds 5

On the chip, in one process.  The program's readings come from whole runs of
the cell (``bench/run.py``'s ``execute``) at the cell's own size and load,
with a short window.  The control is the reference put in the program's
place and computed in float8 (``bench/reference.py``); for a served model
it reads, at each position of the same prompts and served tokens, the gap
of the token the control puts first.  A training cell is also read with two
faults planted in the reference in the program's place: half the batch
dropped and the mean taken over the rest, and a ring that is never written
(every step reads the initial weights).  Prints one JSON object per seed and
kind; nothing here runs in the benchmark's own runs.
"""
from __future__ import annotations

import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


# what stands in the program's place, as options of the reference
TRAIN_KINDS = {"control": {"numerics": "fp8"},
               "fault_half_batch": {"half_batch": True},
               "fault_ring_unwritten": {"ring_unwritten": True}}


def train_control(cell, seed: int, kind: str = "control",
                  ref: dict | None = None) -> dict:
    """The readings of the control or a fault (``TRAIN_KINDS``) against the
    float32 reference ``ref`` (made here if not given), at the cell's
    size."""
    from bench import compare, reference
    if ref is None:
        ref = reference.train_readings(cell.config, cell.traffic, seed)
    other = reference.train_readings(cell.config, cell.traffic, seed,
                                     **TRAIN_KINDS[kind])
    return compare.train_readings(other, ref)


def serve_control(cell, seed: int, served: dict) -> dict:
    """The widest reference gap of the tokens the float8 control puts first
    at each position of the served requests ``{rid: (prompt, tokens)}``."""
    import numpy as np
    from bench import compare, reference, weights
    w = weights.make(cell.config, seed)
    worst = 0.0
    for prompt, tokens in served.values():
        ref = reference.served_logits(cell.config, w, prompt, tokens)
        low = reference.served_logits(cell.config, w, prompt, tokens, "fp8")
        worst = max(worst, float(compare.served_gaps(
            ref, np.argmax(low, -1)).max()))
    return {"logit_gap": worst}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import harness, run
    cell = harness.resolve(args.workload)
    run.environment(cell.traffic)
    import jax
    from repro.launch.tuning import apply_tuning
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    apply_tuning()
    devices = harness.check_devices(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    kind = cell.traffic["kind"]

    def emit(**row):
        print(json.dumps(row), flush=True)

    for seed in seeds:
        t = time.perf_counter()
        res = run.execute(cell, seed, args.seconds, False, devices)
        emit(kind="program", seed=seed, correct=res["correct"],
             readings={k: v["value"] for k, v in res["checks"].items()},
             metrics={k: v["value"] for k, v in res["metrics"].items()},
             notes=res["notes"], seconds=time.perf_counter() - t)
        del res
        gc.collect()
    for seed in controls:
        t = time.perf_counter()
        if kind == "train":
            from bench import reference
            ref = reference.train_readings(cell.config, cell.traffic, seed)
            for k in TRAIN_KINDS:
                emit(kind=k, seed=seed,
                     readings=train_control(cell, seed, k, ref),
                     seconds=time.perf_counter() - t)
        else:
            served = serve_sample(cell, seed, args.seconds, devices)
            emit(kind="control", seed=seed,
                 readings=serve_control(cell, seed, served),
                 seconds=time.perf_counter() - t)
        gc.collect()
    return 0


def serve_sample(cell, seed: int, seconds: float, devices) -> dict:
    """A run of the serving cell; the sample of requests it served, as
    ``{rid: (prompt, tokens)}``, the one ``correct`` compares."""
    from bench import compare, generator
    drv = cell.driver
    engine = drv.build_engine(cell, seed)
    engine.run(generator.warmup_requests(cell.traffic, cell.config, seed))
    reqs = generator.serve_requests(cell.traffic, cell.config, seed, seconds)
    engine.run(reqs)
    finished = {f.rid: f for f in engine._finished}
    by_rid = {r.rid: r for r in reqs}
    ids = compare.sample_requests(
        {rid: len(by_rid[rid].prompt) + len(f.tokens)
         for rid, f in finished.items()},
        seed, int(cell.traffic["check_requests"]))
    out = {rid: (by_rid[rid].prompt, finished[rid].tokens) for rid in ids}
    del engine, finished
    gc.collect()
    return out


if __name__ == "__main__":
    sys.exit(main())
