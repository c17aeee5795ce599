import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# The two lines above MUST run before any other import (jax locks the device
# count at first initialization).  Do not move them.

__doc__ = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces a JSON artifact with:
  memory_analysis   — per-device argument/output/temp bytes (proves HBM fit)
  cost_analysis     — per-device HLO flops / bytes accessed
  collectives       — per-op-kind byte totals parsed from the post-SPMD
                      per-device HLO (the roofline collective term)
  roofline terms    — seconds per step for compute / memory / collective

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  python -m repro.launch.dryrun --all                 # full 34-cell sweep
  python -m repro.launch.dryrun --all --mesh multi    # 512-chip pass
"""

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs import all_arch_ids, get_config
from ..core.sync_jax import ACTIVATION_RULES, SyncConfig
from ..models import paramlib
from ..models.transformer import model_specs
from ..optim import OptConfig, make_optimizer
from .mesh import (DCI_FACTOR, HBM_BW, ICI_BW, PEAK_FLOPS_BF16,
                   make_production_mesh)
from .shapes import SHAPES, applicable_shapes, decode_cache_specs, input_specs
from .sharding import activation_rules, batch_shardings, make_act_specs, \
    replicated, tree_shardings
from .steps import make_decode_step, make_prefill_step, \
    make_sharded_train_step

# In post-optimization (scheduled) HLO the operands are bare %names, so we
# read each collective's RESULT type(s) from the LHS instead:
#   %all-reduce.5 = f32[16,512]{1,0} all-reduce(%fusion.3), ...
#   %ag = (bf16[2,8]{...}, bf16[2,8]{...}) all-gather-start(...)
# For all-reduce / all-to-all / collective-permute the result size equals the
# operand size; for all-gather the result is the gathered buffer and for
# reduce-scatter the operand is the pre-scatter buffer — we record result
# bytes and convert to wire bytes with the per-op ring factors in
# benchmarks/roofline.py (kept separate so the raw parse stays mechanical).
_COLLECTIVE_LINE_RE = re.compile(
    r"=\s+(\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_TYPE_RE = re.compile(r"\b([a-z]+\d+|pred)\[([0-9,]*)\]")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1}


def parse_collective_bytes(hlo_text: str) -> dict[str, float]:
    """Sum result bytes of every collective in the per-device HLO.
    Returns {kind: bytes, kind+'_count': n}."""
    out: dict[str, float] = {}
    for m in _COLLECTIVE_LINE_RE.finditer(hlo_text):
        types, kind = m.group(1), m.group(2)
        total = 0
        for tm in _TYPE_RE.finditer(types):
            dt, dims = tm.group(1), tm.group(2)
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0) + total
        out[kind + "_count"] = out.get(kind + "_count", 0) + 1
    return out


def _tree_flat_shardings(tree):
    return jax.tree.leaves(tree, is_leaf=lambda x: hasattr(x, "spec"))


def build_cell(arch: str, shape_name: str, mesh, sync: SyncConfig):
    """Returns (jitted_fn, example_args (abstract), out_shardings_note)."""
    cfg = get_config(arch)
    cell = SHAPES[shape_name]
    batch_abs, batch_axes = input_specs(cfg, shape_name)

    if cell.kind == "train":
        opt = make_optimizer(OptConfig(name="adamw",
                                       compression=sync.compression))
        fn, params_abs, opt_abs, _ = make_sharded_train_step(
            cfg, opt, sync, mesh, batch_abs, batch_axes)
        return cfg, fn, (params_abs, opt_abs, batch_abs)

    specs = model_specs(cfg)
    params_abs = paramlib.abstract_tree(specs, cfg.param_dtype)
    p_shard = tree_shardings(paramlib.axes_tree(specs), params_abs, mesh,
                             sync.param_rules)
    act_specs = make_act_specs(mesh, sp=os.environ.get("REPRO_SP") == "1")
    b_shard = batch_shardings(batch_axes, batch_abs, mesh)
    if cell.kind == "prefill":
        step = make_prefill_step(cfg, cache_len=cell.seq_len,
                                 remat=sync.remat, act_specs=act_specs)
        cache_abs, cache_ax = decode_cache_specs(cfg, shape_name)
        c_shard = tree_shardings(cache_ax, cache_abs, mesh, activation_rules())
        fn = jax.jit(step, in_shardings=(p_shard, b_shard),
                     out_shardings=(None, c_shard))
        args = (params_abs, batch_abs)
    else:  # decode
        step = make_decode_step(cfg, act_specs=act_specs)
        cache_abs, cache_ax = decode_cache_specs(cfg, shape_name)
        c_shard = tree_shardings(cache_ax, cache_abs, mesh, activation_rules())
        fn = jax.jit(step,
                     in_shardings=(p_shard, c_shard, b_shard),
                     out_shardings=(None, c_shard),
                     donate_argnums=(1,))
        args = (params_abs, cache_abs, batch_abs)
    return cfg, fn, args


def model_flops(cfg, shape_name: str) -> float:
    """MODEL_FLOPS: 6*N*D for training (N = active params), 2*N per decoded
    token; prefill = 2*N*D.  MoE counts activated experts only."""
    specs = model_specs(cfg)
    n_total = paramlib.param_count(specs)
    if cfg.is_moe:
        # subtract inactive expert params
        moe_per_layer = 3 * cfg.d_model * cfg.d_ff * cfg.n_experts
        n_moe_layers = sum(1 for k in cfg.layer_kinds if k != "xattn")
        inactive = (cfg.n_experts - cfg.top_k) / cfg.n_experts
        n_active = n_total - moe_per_layer * n_moe_layers * inactive
    else:
        n_active = n_total
    cell = SHAPES[shape_name]
    D = cell.global_batch * cell.seq_len
    if cell.kind == "train":
        return 6.0 * n_active * D
    if cell.kind == "prefill":
        return 2.0 * n_active * D
    return 2.0 * n_active * cell.global_batch      # one token per sequence


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             sync: SyncConfig, out_dir: str,
             correct_tripcount: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.time()
    cfg, fn, args = build_cell(arch, shape_name, mesh, sync)
    with mesh:
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        from .costmodel import cost_dict
        cost = cost_dict(compiled)
        hlo = compiled.as_text()
    coll = parse_collective_bytes(hlo)

    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))

    # XLA counts scan (while) bodies once — add (n-1) x per-layer body cost
    # for every term (see costmodel.py; validated in tests/test_costmodel.py)
    if correct_tripcount:
        from .costmodel import corrected_terms, group_body_cost
        cell = SHAPES[shape_name]
        bodies = []
        for gi in range(len(cfg.groups)):
            bodies.append(group_body_cost(
                cfg, gi, mesh, sync.param_rules, cell.kind,
                cell.global_batch, cell.seq_len, sync.remat,
                lambda txt: {k: v for k, v in
                             parse_collective_bytes(txt).items()
                             if not k.endswith("_count")}))
        corr = corrected_terms(
            {"cost": {"flops_per_device": flops_dev,
                      "bytes_per_device": bytes_dev},
             "collectives": {k: v for k, v in coll.items()
                             if not k.endswith("_count")}},
            bodies)
        flops_dev = corr["flops_per_device"]
        bytes_dev = corr["bytes_per_device"]
        coll = {**coll, **corr["collectives"]}

    coll_bytes = float(sum(v for k, v in coll.items()
                           if not k.endswith("_count")))
    # cost_analysis is on the per-device (post-SPMD) executable
    compute_s = flops_dev / PEAK_FLOPS_BF16
    memory_s = bytes_dev / HBM_BW
    link_bw = ICI_BW / (DCI_FACTOR if multi_pod else 1.0)
    collective_s = coll_bytes / link_bw

    mf = model_flops(cfg, shape_name)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": int(n_chips),
        "sync_mode": sync.mode, "remat": sync.remat,
        "env": {"dp_over_model":
                os.environ.get("REPRO_DP_OVER_MODEL") == "1",
                "sp": os.environ.get("REPRO_SP") == "1",
                "chunked_ce": os.environ.get("REPRO_CHUNKED_CE") == "1",
                "onehot_cache": os.environ.get("REPRO_ONEHOT_CACHE") == "1"},
        "status": "ok",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes_per_device": int(
                getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes_per_device": int(
                getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes_per_device": int(
                getattr(mem, "temp_size_in_bytes", 0)),
            "peak_bytes_per_device": int(
                getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
                + getattr(mem, "temp_size_in_bytes", 0)),
        },
        "cost": {"flops_per_device": flops_dev,
                 "bytes_per_device": bytes_dev},
        "collectives": coll,
        "collective_bytes_per_device": coll_bytes,
        "roofline": {
            "compute_s": compute_s,
            "memory_s": memory_s,
            "collective_s": collective_s,
            "bottleneck": max(
                [("compute", compute_s), ("memory", memory_s),
                 ("collective", collective_s)], key=lambda kv: kv[1])[0],
        },
        "model_flops_total": mf,
        "model_flops_per_device": mf / n_chips,
        "useful_flops_ratio": (mf / n_chips) / flops_dev if flops_dev else 0.0,
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch.replace('.', '_')}__{shape_name}__" \
              f"{'multi' if multi_pod else 'single'}__{sync.mode}" \
              + (f"__{sync.remat}" if sync.remat != "full" else "")
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(result, f, indent=1)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--mode", choices=["datacentric", "bsp"],
                    default="datacentric")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    sync = SyncConfig(mode=args.mode, remat=args.remat)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    cells: list[tuple[str, str]] = []
    if args.all:
        for arch in all_arch_ids():
            for shp in applicable_shapes(get_config(arch)):
                cells.append((arch, shp))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells.append((args.arch, args.shape))

    failures = 0
    for multi in meshes:
        for arch, shp in cells:
            tag = f"{arch}/{shp}/{'multi' if multi else 'single'}"
            out_tag = f"{arch.replace('.', '_')}__{shp}__" \
                      f"{'multi' if multi else 'single'}__{sync.mode}" \
                      + (f"__{sync.remat}" if sync.remat != "full" else "")
            if args.skip_existing and os.path.exists(
                    os.path.join(args.out, out_tag + ".json")):
                print(f"SKIP {tag}")
                continue
            try:
                r = run_cell(arch, shp, multi, sync, args.out)
                rl = r["roofline"]
                print(f"OK   {tag}: compile={r['compile_s']}s "
                      f"peak={r['memory']['peak_bytes_per_device']/2**30:.2f}GiB "
                      f"compute={rl['compute_s']*1e3:.2f}ms "
                      f"memory={rl['memory_s']*1e3:.2f}ms "
                      f"coll={rl['collective_s']*1e3:.2f}ms "
                      f"-> {rl['bottleneck']}", flush=True)
            except Exception as e:
                failures += 1
                print(f"FAIL {tag}: {type(e).__name__}: {e}", flush=True)
                traceback.print_exc()
                if args.out:
                    os.makedirs(args.out, exist_ok=True)
                    with open(os.path.join(args.out, out_tag + ".json"),
                              "w") as f:
                        json.dump({"arch": arch, "shape": shp,
                                   "mesh": "multi" if multi else "single",
                                   "status": "fail",
                                   "error": f"{type(e).__name__}: {e}"}, f)
    print(f"done; {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
