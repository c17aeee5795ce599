"""What every cell shares: finding a cell's files by name, seeds, the chip
check, the compile counter, the table of peaks and the result line.

A cell is one entry of ``BENCHMARK.json``'s ``workloads``.  Everything that
belongs to it is found by name:

* ``bench/configs/<config>.json``   the model as it is run (HF key names);
* ``bench/traffic/<traffic>.json``  the traffic mix and the driver's settings,
  read by the one generator in ``bench/generator.py``; its ``driver`` key
  names ``bench/drivers/<driver>.py``;
* ``bench/limits/<cell>.json``      the limit of each number ``correct``
  compares, with the readings it was set from;
* ``bench/metrics/<metric>.py``     one reader per per-layer metric;
* ``bench/arch/<model_type>.py``    everything that depends on the
  architecture, found by the configuration's ``model_type`` (``arch``).

How to add an architecture.  One module, ``bench/arch/<model_type>.py``,
holds what the shared files dispatch to:

* ``dims(config)``: the shapes and constants the rest of the module reads;
* ``layout(config)``: ``{path: (shape, kind, std)}`` of every weight, in the
  program's parameter tree (kind ``normal`` with its std, or ``scale``, a
  norm's gain); ``empty_groups(config)``: the paths of the groups the
  program's tree holds with no weights in them (a norm without a gain);
* ``hidden(w, tokens, m, numerics)`` and ``logits_at(w, h, numerics)``: the
  plain float32 forward, every matrix product through ``reference.mm`` so
  that the float8 control covers each one;
* ``model_config(config)``: the program's ``ModelConfig``, importing the
  program inside the function, so that the rest of the module imports
  nothing of it;
* ``matmul_params(config)``, ``attn_flops(config, kv_positions)``,
  ``kv_bytes(config, positions)`` and ``attn_call(config, batch, seq)``:
  the counts the readers take (``bench/counts.py``).

A new model then comes as new files alone: its arch module (where its
``model_type`` has none yet), its configuration, its traffic, its cell's
limits and its metric readers, with the cell's name added to the
``workloads`` lists of the end-to-end metrics it reports in
``BENCHMARK.json``.  It edits no file that is here already: no shared
module, driver, reader, arch module, configuration or limits of another
cell.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Any

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


class BenchError(RuntimeError):
    """A cell or a run that cannot be measured as asked."""


def load_json(path: Path) -> Any:
    if not path.is_file():
        raise BenchError(f"missing benchmark file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_module(path: Path) -> ModuleType:
    """Import a benchmark file by path (names may hold dots)."""
    if not path.is_file():
        raise BenchError(f"missing benchmark file {path.relative_to(ROOT)}")
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    driver: ModuleType
    arch: ModuleType
    limits: dict
    end_to_end: list[dict]        # BENCHMARK.json entries this cell reports
    per_layer: list[dict]


def resolve(name: str, bench: dict | None = None) -> Cell:
    """Everything a cell needs, found by the names in ``BENCHMARK.json``."""
    bench = bench or benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    config = load_json(BENCH / "configs" / f"{entry['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{entry['traffic']}.json")
    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py")
    limits = load_json(BENCH / "limits" / f"{name}.json")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    per_layer = [m for m in bench["per_layer"] if name in m["workloads"]]
    return Cell(name=name, chips=int(entry["chips"]), config=config,
                traffic=traffic, driver=driver, arch=arch(config),
                limits=limits,
                end_to_end=e2e, per_layer=per_layer)


def arch(config: dict) -> ModuleType:
    """The module of the configuration's architecture,
    ``bench/arch/<model_type>.py``, imported as ``bench.arch.<model_type>``
    (the name by which one arch module imports another)."""
    path = BENCH / "arch" / f"{config['model_type']}.py"
    if not path.is_file():
        raise BenchError(f"missing benchmark file {path.relative_to(ROOT)}")
    return importlib.import_module(f"bench.arch.{path.stem}")


def metric_reader(name: str) -> ModuleType:
    return load_module(BENCH / "metrics" / f"{name}.py")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of this kind; an unknown kind is an
    error, never a default."""
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json (known: "
                         f"{sorted(table['devices'])})")
    return table["devices"][device_kind]


# ---------------------------------------------------------------------------
# Seeds
# ---------------------------------------------------------------------------

def seed_words(seed: int) -> tuple[int, int]:
    """A seed of up to 64 bits as two 32-bit words (hi, lo)."""
    if not 0 <= seed < 2 ** 64:
        raise BenchError(f"--seed must lie in [0, 2**64), got {seed}")
    return (seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF


def seed_key(seed: int, stream: int):
    """The legacy threefry key of (seed, stream): every use of randomness
    in a run takes its own stream, so no two draw the same numbers."""
    import jax
    import numpy as np
    hi, lo = seed_words(seed)
    key = jax.numpy.asarray(np.array([hi, lo], np.uint32))
    return jax.random.fold_in(key, stream)


def np_rng(seed: int, stream: int):
    import numpy as np
    return np.random.default_rng([*seed_words(seed), stream])


# the streams of randomness a run draws from
STREAM_WEIGHTS, STREAM_TRAFFIC, STREAM_SAMPLE = 1, 2, 3


# ---------------------------------------------------------------------------
# Compiles, devices
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache, through
    JAX's monitoring events, so that a window can show it compiled none."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as mon
        self.n = 0
        self.seconds = 0.0
        self.hits = 0

        def on_duration(event, secs, **_):
            if event == self.EVENT:
                self.n += 1
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)


def check_devices(chips: int) -> list:
    """The chips this cell asks for, or an error: the benchmark never runs
    on the CPU in place of the accelerator."""
    import jax
    if jax.default_backend() != "tpu":
        raise BenchError(f"no TPU: JAX's default backend is "
                         f"{jax.default_backend()!r}")
    devices = jax.devices()
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devices)}")
    return devices[:chips]


def memory_peak_bytes(devices) -> int | None:
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in devices]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


def device_block(devices, peak: int | None) -> dict:
    import jax
    d0 = jax.devices()[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def quantile(values, q: float) -> float:
    """The q-quantile by linear interpolation between order statistics
    (numpy's default), over every value given."""
    import numpy as np
    if len(values) == 0:
        raise BenchError("quantile of no values")
    return float(np.quantile(np.asarray(values, np.float64), q))
