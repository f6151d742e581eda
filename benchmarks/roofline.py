"""Roofline analysis (deliverable g).

Reads the dry-run artifacts (``artifacts/dryrun/*.json`` + partitioned HLO)
and derives, per (arch × shape × mesh):

  compute term    = dot_FLOPs_per_device / peak_FLOPs      (197 TFLOP/s bf16)
  memory term     = HBM_bytes_per_device / HBM_bw          (819 GB/s)
  collective term = collective_bytes_per_device / link_bw  (50 GB/s ICI;
                    pod-axis collectives would ride DCN — single-pod table)

dot_FLOPs / collective bytes / HBM bytes are **loop-corrected** via the HLO
analyzer (benchmarks/hlo_analysis.py): XLA cost_analysis counts while bodies
once, so scanned layers/microbatches/chunks would otherwise be undercounted
by 10-1000x.  The raw cost_analysis numbers are retained in the JSON
artifacts for reference.

MODEL_FLOPS (the useful-work numerator) is analytic:
  train   3 x (2·N_active·T + A)      (fwd + 2x bwd; remat NOT counted)
  prefill     2·N_active·T + A
  decode      2·N_active·B + A_dec
  A (causal attention, useful half) = Σ_attn_layers 2·B·S²·H·hd
  A_dec = Σ_attn_layers 4·B·S_cache·H·hd

Usage: python -m benchmarks.roofline [--mesh singlepod|multipod] [--md]
"""
from __future__ import annotations

import argparse
import glob
import json
import os

from repro import enable_compile_cache
from repro.configs import ARCHS, SHAPES, get_config, shape_cells

from .hlo_analysis import analyze_file

PEAK_FLOPS = 197e12      # bf16 per chip
HBM_BW = 819e9           # bytes/s per chip
LINK_BW = 50e9           # bytes/s per ICI link

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")

__all__ = ["model_flops", "cell_rows", "main"]


def model_flops(cfg, shape) -> float:
    """Analytic useful FLOPs per step (global)."""
    n_act = cfg.n_active_params()
    b, s = shape.global_batch, shape.seq_len
    attn_layers = sum(
        kind.split("+")[0] in ("attn", "swa") for kind in cfg.pattern
    ) * cfg.n_repeat
    hhd = cfg.n_heads * cfg.head_dim
    if shape.step == "train":
        tokens = b * s
        window = cfg.sliding_window or s
        a = attn_layers * 2.0 * b * s * min(s, window) * hhd
        return 3.0 * (2.0 * n_act * tokens + a)
    if shape.step == "prefill":
        tokens = b * s
        window = cfg.sliding_window or s
        a = attn_layers * 2.0 * b * s * min(s, window) * hhd
        return 2.0 * n_act * tokens + a
    # decode: one token against an S-length cache
    window = cfg.sliding_window or s
    a = attn_layers * 4.0 * b * min(s, window) * hhd
    return 2.0 * n_act * b + a


def cell_rows(mesh_tag: str = "singlepod") -> list[dict]:
    rows = []
    for arch in ARCHS:
        cfg = get_config(arch)
        for shape in shape_cells(arch):
            base = f"{arch}__{shape.name}__{mesh_tag}"
            jpath = os.path.join(ART, base + ".json")
            hpath = os.path.join(ART, base + ".hlo.gz")
            if not (os.path.exists(jpath) and os.path.exists(hpath)):
                continue
            rec = json.load(open(jpath))
            cost = analyze_file(hpath)
            n_dev = rec["n_devices"]
            t_c = cost.dot_flops / PEAK_FLOPS
            t_m = cost.hbm_bytes / HBM_BW
            t_x = cost.collective_bytes / LINK_BW
            dom = max(("compute", t_c), ("memory", t_m),
                      ("collective", t_x), key=lambda kv: kv[1])[0]
            mf = model_flops(cfg, shape) / n_dev
            ratio = mf / cost.dot_flops if cost.dot_flops else 0.0
            bound = max(t_c, t_m, t_x)
            rows.append({
                "arch": arch,
                "shape": shape.name,
                "step": shape.step,
                "compute_s": t_c,
                "memory_s": t_m,
                "collective_s": t_x,
                "dominant": dom,
                "hlo_tflops_dev": cost.dot_flops / 1e12,
                "model_tflops_dev": mf / 1e12,
                "useful_ratio": ratio,
                "roofline_frac": (mf / PEAK_FLOPS) / bound if bound else 0.0,
                "mem_gib_dev": (rec["memory"]["argument_bytes"]
                                + rec["memory"]["temp_bytes"]) / 2**30,
                "coll_gb_dev": cost.collective_bytes / 1e9,
            })
    return rows


def _fmt(rows, md=False):
    hdr = ["arch", "shape", "compute_s", "memory_s", "collective_s",
           "dominant", "model_tflops_dev", "useful_ratio", "roofline_frac",
           "mem_gib_dev"]
    out = []
    if md:
        out.append("| " + " | ".join(hdr) + " |")
        out.append("|" + "---|" * len(hdr))
    else:
        out.append(",".join(hdr))
    for r in rows:
        vals = [r["arch"], r["shape"], f"{r['compute_s']:.4f}",
                f"{r['memory_s']:.4f}", f"{r['collective_s']:.4f}",
                r["dominant"], f"{r['model_tflops_dev']:.1f}",
                f"{r['useful_ratio']:.3f}", f"{r['roofline_frac']:.3f}",
                f"{r['mem_gib_dev']:.1f}"]
        out.append(("| " + " | ".join(vals) + " |") if md
                   else ",".join(vals))
    return "\n".join(out)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="singlepod",
                    choices=["singlepod", "multipod"])
    ap.add_argument("--md", action="store_true")
    args = ap.parse_args()
    rows = cell_rows(args.mesh)
    print(f"### Roofline — {args.mesh} "
          f"(197 TF/s bf16, 819 GB/s HBM, 50 GB/s ICI)")
    print(_fmt(rows, md=args.md))


if __name__ == "__main__":
    main()
