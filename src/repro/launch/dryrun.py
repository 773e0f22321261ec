import os
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=512")
# The lines above MUST run before any jax import anywhere in the process:
# jax locks the device count at first backend initialization.  An explicit
# externally-set device count (tests use 8) is respected.

_DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces (and caches as JSON under ``--out``):
  * ``memory_analysis`` — per-device argument/output/temp bytes (fits?)
  * ``cost_analysis``   — HLO FLOPs and bytes-accessed for §Roofline
  * per-collective byte totals parsed from the compiled HLO
    (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute), the collective-roofline numerator.

Usage:
  python -m repro.launch.dryrun --arch deepseek-7b --shape train_4k
  python -m repro.launch.dryrun --all --mesh both --skip-existing
"""

import argparse
import dataclasses
import json
import re
import sys
import time
import traceback

import jax
from jax.sharding import NamedSharding

from repro.configs import ARCH_IDS, applicable_shapes, get_config
from repro.configs.base import RuntimeConfig
from repro.distributed import sharding as shd
from repro.launch import mesh as mesh_mod
from repro.launch import steps as steps_mod

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def parse_collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum result-buffer bytes per collective kind.  Result size is the
    per-device traffic proxy: all-reduce result == operand; all-gather
    result == bytes received; all-to-all/collective-permute result == bytes
    moved; reduce-scatter uses operand ~= result * group (approximated by
    result here, noted in EXPERIMENTS)."""
    totals = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"%?[\w.\-]+ = (.*)$", stripped)
        if not m:
            continue
        rhs = m.group(1)
        kind = None
        for k in _COLLECTIVES:
            if re.search(rf"\b{k}(-start|-done)?\(", rhs):
                kind = k
                break
        if kind is None or f"{kind}-done(" in rhs:
            continue                      # count start, not done
        head = rhs.split("(", 1)[0]
        nbytes = 0
        for dt, dims in shape_re.findall(head):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        totals[kind] += nbytes
        counts[kind] += 1
    return {"bytes": totals, "counts": counts}


def _to_shardings(tree, mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s) if s is not None else None, tree,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
        or x is None)


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             rt: RuntimeConfig | None = None,
             rules: shd.ShardingRules | None = None) -> dict:
    cfg = get_config(arch)
    shapes = applicable_shapes(cfg)
    if shape_name not in shapes:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped",
                "reason": "shape not applicable to this arch family"}
    shape = shapes[shape_name]
    mesh = mesh_mod.make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rt = rt or RuntimeConfig(
        mode="xla", remat="dots",
        fused_loss_chunk=512 if shape.kind == "train" else 0,
        loss_unroll=True)
    rules = rules or shd.ShardingRules()

    t0 = time.time()
    cell = steps_mod.plan_cell(cfg, shape, mesh, rt, rules)
    with mesh:
        jitted = jax.jit(
            cell.step,
            in_shardings=_to_shardings(cell.in_shardings, mesh),
            out_shardings=_to_shardings(cell.out_shardings, mesh),
            donate_argnums=cell.donate_argnums)
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = parse_collective_bytes(compiled.as_text())

    # ---- trip-count correction: XLA counts scan bodies once; add
    # (trip_count - 1) x the straight-line cost of one scanned super-block.
    corrected = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": {k: float(v) for k, v in coll["bytes"].items()},
    }
    parts_out = {}
    for pname, plow, mult in steps_mod.plan_part_cells(
            cfg, shape, mesh, rt, rules):
        with mesh:
            pc = jax.jit(
                plow.step,
                in_shardings=_to_shardings(plow.in_shardings, mesh),
                out_shardings=plow.out_shardings,
                donate_argnums=plow.donate_argnums,
            ).lower(*plow.args).compile()
        pcost = pc.cost_analysis()
        pcoll = parse_collective_bytes(pc.as_text())
        parts_out[pname] = {
            "flops": float(pcost.get("flops", 0.0)),
            "bytes_accessed": float(pcost.get("bytes accessed", 0.0)),
            "collectives": pcoll,
            "multiplier": mult,
        }
        corrected["flops"] += mult * parts_out[pname]["flops"]
        corrected["bytes_accessed"] += \
            mult * parts_out[pname]["bytes_accessed"]
        for k, v in pcoll["bytes"].items():
            corrected["collective_bytes"][k] += mult * float(v)

    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "status": "ok",
        "kind": shape.kind,
        "n_devices": mesh.devices.size,
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", 0),
        },
        "collectives": coll,
        "parts": parts_out,
        "corrected": corrected,
        "n_params": cfg.n_params(),
        "n_active_params": cfg.n_active_params(),
        "timings": {"lower_s": round(t_lower, 2),
                    "compile_s": round(t_compile, 2)},
    }
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", default=None)
    ap.add_argument("--shape", action="append", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    # ---- perf-iteration knobs (EXPERIMENTS.md §Perf) -----------------------
    ap.add_argument("--remat", default="dots",
                    choices=["none", "dots", "full"])
    ap.add_argument("--moe-dispatch", default="global",
                    choices=["global", "grouped"],
                    help="global = paper-faithful baseline dispatch")
    ap.add_argument("--no-fsdp", action="store_true",
                    help="replicate params over the data axis (serving "
                         "layout: kills per-step weight all-gathers)")
    ap.add_argument("--loss-chunk", type=int, default=512)
    args = ap.parse_args(argv)

    rt = RuntimeConfig(mode="xla", remat=args.remat,
                       moe_dispatch=args.moe_dispatch,
                       moe_constraint=("auto" if args.moe_dispatch
                                       == "grouped" else "none"),
                       loss_unroll=True)
    rules = shd.ShardingRules(fsdp=not args.no_fsdp)
    _loss_chunk = args.loss_chunk

    archs = args.arch or (list(ARCH_IDS) if args.all else ["deepseek-7b"])
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    os.makedirs(args.out, exist_ok=True)

    failures = 0
    for arch in archs:
        cfg = get_config(arch)
        shape_names = args.shape or list(applicable_shapes(cfg))
        for shape_name in shape_names:
            for mesh_kind in meshes:
                tag = f"{arch}__{shape_name}__{mesh_kind}"
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[skip] {tag}")
                    continue
                print(f"[cell] {tag} ...", flush=True)
                is_train = shape_name.startswith("train")
                cell_rt = dataclasses.replace(
                    rt, fused_loss_chunk=_loss_chunk if is_train else 0)
                try:
                    res = run_cell(arch, shape_name, mesh_kind,
                                   rt=cell_rt, rules=rules)
                except Exception as e:  # noqa: BLE001 — record and continue
                    failures += 1
                    res = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_kind, "status": "error",
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-4000:]}
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
                if res["status"] == "ok":
                    print(f"  flops={res['flops']:.3e} "
                          f"bytes={res['bytes_accessed']:.3e} "
                          f"coll={sum(res['collectives']['bytes'].values()):.3e} "
                          f"temp={res['memory']['temp_bytes']/2**30:.2f}GiB "
                          f"compile={res['timings']['compile_s']}s",
                          flush=True)
                else:
                    print(f"  {res['status']}: {res.get('reason', res.get('error', ''))[:300]}",
                          flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
