"""The sweep that finds a serving cell's knee: the highest rate at which the
queue does not grow over the window.

    python3 bench/knee.py --workload <serve cell> --rates 4,6,8,10 --seconds 30

On the chip, in one process, one engine for every rate.  For each rate it
serves the cell's traffic at that rate for the window and prints the time
to first token of the first and the last third of the arrivals (where the
queue grows, the last third waits longer) and the share of the slots live
in a decode step.  The rate found goes into the
cell's traffic file by hand; no run of the benchmark searches for it.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import harness, run
    cell = harness.resolve(args.workload)
    run.environment(cell.traffic)
    import jax
    import numpy as np
    from repro.launch.tuning import apply_tuning
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    apply_tuning()
    harness.check_devices(cell.chips)
    from bench import generator
    engine = cell.driver.build_engine(cell, args.seed)
    engine.run(generator.warmup_requests(cell.traffic, cell.config,
                                         args.seed))
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = {**cell.traffic, "rate": rate}
        reqs = generator.serve_requests(traffic, cell.config, args.seed,
                                        args.seconds)
        steps0, live0 = engine.decode_steps, engine._live_slot_steps
        report = engine.run(reqs)
        fin = {f.rid: f for f in engine._finished}
        ttft = np.array([fin[r.rid].t_first - r.arrival for r in reqs])
        tpot = [(f.t_done - f.t_first) / (len(f.tokens) - 1)
                for f in fin.values() if len(f.tokens) > 1]
        third = max(1, len(reqs) // 3)
        tokens = sum(len(r.prompt) + r.gen_len for r in reqs)
        print(json.dumps({
            "rate": rate, "requests": len(reqs),
            "ttft_p50_first_third_s": float(np.median(ttft[:third])),
            "ttft_p50_last_third_s": float(np.median(ttft[-third:])),
            "ttft_p95_s": float(np.quantile(ttft, 0.95)),
            "tpot_p95_ms": 1e3 * float(np.quantile(tpot, 0.95)),
            "tok_s": tokens / report.duration,
            "drain_s": report.duration - reqs[-1].arrival,
            "live_share": (engine._live_slot_steps - live0) / max(
                1, engine.decode_steps - steps0) / engine.scfg.batch_size,
            "tick_ms": 1e3 * report.duration / max(
                1, engine.decode_steps - steps0)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
