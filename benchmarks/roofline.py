"""Roofline analysis from the dry-run JSONs (see launch/dryrun.py).

Per (arch × shape × mesh):
  compute    = HLO_FLOPs_per_device / peak_FLOP/s          [s]
  memory     = HLO_bytes_per_device / HBM_bw               [s]
  collective = collective_bytes_per_device / link_bw       [s]
  MODEL_FLOPS (analytic) = 6·N·D_tokens (train) / 2·N·D (prefill)
                         / 2·N·B (decode), N = active params
  usefulness = MODEL_FLOPS / (HLO_FLOPs_per_device × chips)

Emits the EXPERIMENTS.md §Roofline markdown table + per-cell bottleneck
lever notes.  Run:  PYTHONPATH=src python -m benchmarks.roofline \
    --dir results/dryrun --markdown
"""
import argparse
import glob
import json
import os

from repro.configs import get_config
from repro.launch.mesh import PEAK_FLOPS_BF16
from repro.models.config import param_counts

LEVERS = {
    "compute": "raise MXU utilization: larger microbatch / fused matmuls "
               "/ bf16 everywhere",
    "memory": "cut HBM traffic: tighter remat policy, fused attention "
              "(Pallas), smaller collective staging buffers",
    "collective": "reshard: fewer TP all-reduces (2D sharding), overlap "
                  "via microbatch pipelining, bf16 collectives",
}


def model_flops(arch: str, shape: str, rec: dict) -> float:
    cfg = get_config(arch)
    n_active = param_counts(cfg)["active"]
    if shape == "train_4k":
        tokens = 256 * 4096
        return 6.0 * n_active * tokens
    if shape == "prefill_32k":
        return 2.0 * n_active * 32 * 32768
    if shape == "decode_32k":
        return 2.0 * n_active * 128
    if shape == "long_500k":
        return 2.0 * n_active * 1
    raise KeyError(shape)


def load(dirname: str):
    rows = []
    for path in sorted(glob.glob(os.path.join(dirname, "*.json"))):
        with open(path) as f:
            rows.append(json.load(f))
    return rows


def analyze(rec: dict) -> dict:
    out = dict(rec)
    if rec.get("status") != "ok":
        return out
    mf = model_flops(rec["arch"].replace("_", "-", 1)
                     if False else rec["arch"], rec["shape"], rec)
    total_hlo = rec["flops_per_device"] * rec["n_chips"]
    out["model_flops"] = mf
    out["usefulness"] = mf / total_hlo if total_hlo else 0.0
    # roofline fraction: useful-FLOPs time vs the bounding term
    t_bound = max(rec["t_compute"], rec["t_memory"], rec["t_collective"])
    t_useful = (mf / rec["n_chips"]) / PEAK_FLOPS_BF16
    out["roofline_fraction"] = t_useful / t_bound if t_bound else 0.0
    out["lever"] = LEVERS[rec["bottleneck"]]
    return out


def markdown_table(rows) -> str:
    hdr = ("| arch | shape | mesh | compute s | memory s | collective s | "
           "bottleneck | useful/HLO | roofline frac | fits |\n"
           "|---|---|---|---|---|---|---|---|---|---|")
    lines = [hdr]
    for r in rows:
        if r.get("status") == "skipped":
            lines.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | — "
                         f"| — | — | skipped: {r['skip_reason'][:42]} | — "
                         f"| — | — |")
            continue
        if r.get("status") != "ok":
            lines.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | — "
                         f"| — | — | ERROR | — | — | — |")
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['t_compute']:.3e} | {r['t_memory']:.3e} "
            f"| {r['t_collective']:.3e} | **{r['bottleneck']}** "
            f"| {r['usefulness']:.2f} | {r['roofline_fraction']:.2f} "
            f"| {'y' if r['fits_hbm'] else 'NO'} |")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="results/dryrun")
    ap.add_argument("--markdown", action="store_true")
    args = ap.parse_args()
    rows = [analyze(r) for r in load(args.dir)]
    if args.markdown:
        print(markdown_table(rows))
    else:
        for r in rows:
            if r.get("status") == "ok":
                print(f"roofline,{r['arch']},{r['shape']},{r['mesh']},"
                      f"bottleneck={r['bottleneck']},"
                      f"frac={r['roofline_fraction']:.3f},"
                      f"useful={r['usefulness']:.2f},"
                      f"fits={r['fits_hbm']}")
            else:
                print(f"roofline,{r['arch']},{r['shape']},{r['mesh']},"
                      f"{r['status']}")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
