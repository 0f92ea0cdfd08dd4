"""Multi-pod dry-run: prove every (arch × shape × mesh) cell lowers,
SPMD-partitions, and compiles on the production meshes, and extract the
roofline terms from the compiled artifact.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b \
      --shape train_4k --mesh single --out results/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --sweep --out results/dryrun

Each cell writes one JSON with memory_analysis, cost_analysis, per-type
collective bytes (parsed from the compiled per-device HLO), and timing.
The sweep is resumable: existing JSONs are skipped.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import/init: jax locks the device count on first
#   use.  These two lines are the first executable statements of the module
#   (the docstring above compiles to a constant; no __future__ import here
#   precisely so these lines can run before anything else).

import argparse
import json
import re
import sys
import time
import traceback
from typing import Dict, Optional

import jax

from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import (HBM_BYTES, HBM_BW, ICI_BW_PER_LINK,
                               PEAK_FLOPS_BF16, make_production_mesh)
from repro.launch.shapes import SHAPES, build_cell, cell_supported

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "all-to-all", "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(pred|[suf]\d+|bf16|c64|c128)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s+((?:\([^)]*\)|\S+))\s+(" + "|".join(COLLECTIVE_OPS)
    + r")(?:-(?:start|done))?\(")


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def collective_bytes(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per-collective-type result bytes in the per-device HLO module.

    '-start' ops are counted, their '-done' twins skipped (same tensor)."""
    out = {op: {"bytes": 0, "count": 0} for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        if f"{m.group(2)}-done(" in line:
            continue
        out[m.group(2)]["bytes"] += _type_bytes(m.group(1))
        out[m.group(2)]["count"] += 1
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             overrides: Optional[dict] = None) -> dict:
    """Lower + compile one cell; return the roofline record."""
    # per-arch baseline distribution defaults (documented in DESIGN.md §6):
    # dbrx-132b's 264 GB of bf16 params exceed TP-16 HBM → FSDP.
    arch_defaults = {"dbrx-132b": {"fsdp": True}}
    # normalize: ARCH_IDS use underscores, defaults use canonical dashes
    norm = arch.replace("_", "-")
    merged = dict(arch_defaults.get(arch, arch_defaults.get(norm, {})))
    merged.update(overrides or {})
    grad_accum = merged.pop("grad_accum", None)
    opt_kw = {k: merged.pop(k) for k in
              ("grad_compression", "zero1", "shard_grads") if k in merged}
    opt_cfg = None
    if opt_kw:
        from repro.train.optim import OptimConfig
        opt_cfg = OptimConfig(**opt_kw)
    cfg = get_config(arch)
    if merged:
        cfg = cfg.replace(**merged)
    shape = SHAPES[shape_name]
    ok, why = cell_supported(cfg, shape)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "family": cfg.family, "status": "skipped", "skip_reason": why,
    }
    if not ok:
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.devices.size
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        step, args, shards, out_shards, donate = build_cell(
            cfg, shape, mesh, grad_accum=grad_accum, opt_cfg=opt_cfg)
        jitted = jax.jit(step, in_shardings=shards,
                         out_shardings=out_shards,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    mem_rec = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        mem_rec[k] = int(getattr(mem, k, 0) or 0)
    # live bytes per device: args + temps (donated outputs alias args)
    live = mem_rec["argument_size_in_bytes"] + mem_rec["temp_size_in_bytes"]

    cost = compiled.cost_analysis() or {}

    # trip-count-aware analysis (XLA's cost_analysis counts while-loop
    # bodies ONCE — useless under scan-over-layers; launch/hlo_cost.py)
    from repro.launch.hlo_cost import analyze as hlo_analyze
    hlo = hlo_analyze(compiled.as_text())
    flops = float(hlo["flops"])
    bytes_accessed = float(hlo["bytes"])
    coll = hlo["collectives"]
    coll_total = sum(v["bytes"] for v in coll.values())

    rec.update({
        "status": "ok",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": mem_rec,
        "live_bytes_per_device": live,
        "fits_hbm": bool(live <= HBM_BYTES),
        "flops_per_device": flops,
        "bytes_per_device": bytes_accessed,
        "xla_cost_analysis_flops": float(cost.get("flops", 0.0)),
        "collectives": coll,
        "collective_bytes_per_device": coll_total,
        # roofline terms (seconds, per the assignment formulas)
        "t_compute": flops / PEAK_FLOPS_BF16,
        "t_memory": bytes_accessed / HBM_BW,
        "t_collective": coll_total / ICI_BW_PER_LINK,
    })
    terms = {"compute": rec["t_compute"], "memory": rec["t_memory"],
             "collective": rec["t_collective"]}
    rec["bottleneck"] = max(terms, key=terms.get)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=("single", "multi"))
    ap.add_argument("--sweep", action="store_true",
                    help="run every remaining (arch × shape) for --mesh")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override key=value (e.g. remat=dots)")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v

    os.makedirs(args.out, exist_ok=True)

    def one(arch, shape_name):
        tag = f"{arch.replace('.', '_')}__{shape_name}__{args.mesh}"
        path = os.path.join(args.out, tag + ".json")
        if os.path.exists(path) and args.sweep:
            print(f"[skip existing] {tag}")
            return
        print(f"[dryrun] {tag} ...", flush=True)
        try:
            rec = run_cell(arch, shape_name, args.mesh, overrides or None)
        except Exception as e:
            rec = {"arch": arch, "shape": shape_name, "mesh": args.mesh,
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        jax.clear_caches()   # bound sweep RSS: drop compiled executables
        status = rec["status"]
        extra = ""
        if status == "ok":
            gib = rec["live_bytes_per_device"] / 2**30
            extra = (f" compile={rec['compile_s']}s live={gib:.2f}GiB "
                     f"fits={rec['fits_hbm']} bottleneck={rec['bottleneck']}")
        print(f"[done] {tag}: {status}{extra}", flush=True)

    if args.sweep:
        for arch in ARCH_IDS:
            for shape_name in SHAPES:
                one(arch, shape_name)
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required without --sweep")
        one(args.arch, args.shape)


if __name__ == "__main__":
    main()
