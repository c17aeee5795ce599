"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine holding the chips the cell asks
for.  Everything the cell needs is found by the names in ``BENCHMARK.json``
(see ``bench/harness.py``).  A run loads, warms up every shape it uses
(set-up, ``setup_s``), measures for ``--seconds``, then checks what the timed
path produced against the plain reference (``correct``).  ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics
from a profiler trace of part of the window.  The last line of standard
output is one JSON object; the numbers compared for ``correct`` close
standard error.  With no TPU, or fewer chips than the cell asks for, the run
exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def environment(cell_traffic: dict) -> None:
    """Before JAX starts: the compile cache at its one fixed place inside
    the checkout, and the kernels the traffic file names."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["REPRO_KERNEL_IMPL"] = cell_traffic["kernels"]
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


def layer_metrics(cell, outcome: dict, trace_dir: Path, devices) -> tuple:
    """(metrics, busy_s, window_s, breakdown) of a traced run."""
    from bench import harness, trace
    tr = trace.load(trace_dir)
    lo, hi = tr.span("bench.window")
    names = sorted(tr.devices)[: len(devices)]
    if not names:
        raise harness.BenchError("the trace holds no device operations")
    busy = [trace.busy_ns(tr.devices[n], lo, hi) for n in names]
    ctx = {"trace": tr, "trace_window": (lo, hi), "devices": names,
           "config": cell.config, "traffic": cell.traffic,
           "peaks": harness.peaks(devices[0].device_kind),
           "chips": len(devices), **outcome["layer"]}
    metrics = {}
    for m in cell.per_layer:
        value = harness.metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    d0 = tr.devices[names[0]]
    breakdown = {"device_ops": trace.top_ops(d0, lo, hi),
                 "idle_gaps": trace.attribute_gaps(d0, tr.host, lo, hi)}
    return metrics, sum(busy) / len(busy) * 1e-9, (hi - lo) * 1e-9, breakdown


def execute(cell, seed: int, seconds: float, trace: bool, devices) -> dict:
    """One run of ``cell`` on ``devices``: the result line as a dict, the
    numbers compared for ``correct`` under its last key, ``checks``."""
    from bench import compare, harness
    trace_dir = None
    if trace:
        trace_dir = ROOT / ".bench_trace" / cell.name
        shutil.rmtree(trace_dir, ignore_errors=True)
    outcome = cell.driver.run(cell, seed, seconds, trace_dir, T_START,
                              devices)
    checks = outcome["checks"]
    result = {"correct": compare.verdict(checks) and outcome["failed"] == 0,
              "attempted": outcome["attempted"],
              "failed": outcome["failed"]}
    if trace:
        metrics, busy_s, window_s, breakdown = layer_metrics(
            cell, outcome, trace_dir, devices)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = {"setup_s": outcome["setup_s"], **outcome["e2e"]}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result["metrics"] = metrics
    result["device"] = harness.device_block(devices,
                                            outcome["memory_peak_bytes"])
    if trace:
        result["device"].update(busy_s=busy_s, window_s=window_s)
        result["breakdown"] = breakdown
    result["notes"] = outcome["notes"]
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, str(ROOT))
    from bench import harness
    cell = harness.resolve(args.workload)
    environment(cell.traffic)
    import jax
    from repro.launch.tuning import apply_tuning
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    apply_tuning()
    try:
        devices = harness.check_devices(cell.chips)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result = execute(cell, args.seed, args.seconds, bool(args.trace),
                     devices)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
