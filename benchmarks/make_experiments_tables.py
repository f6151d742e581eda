"""Generate the EXPERIMENTS.md §Dry-run and §Roofline tables from the
dry-run artifacts.  Usage: python -m benchmarks.make_experiments_tables"""
import glob
import json
import os

from repro import enable_compile_cache

from .roofline import ART, cell_rows


def dryrun_table() -> str:
    out = ["| arch | shape | mesh | GiB/dev | fits 16GiB | compile s | "
           "top collectives |", "|---|---|---|---|---|---|---|"]
    for path in sorted(glob.glob(os.path.join(ART, "*.json"))):
        r = json.load(open(path))
        gib = (r["memory"]["argument_bytes"]
               + r["memory"]["temp_bytes"]) / 2**30
        mesh = "x".join(map(str, r["mesh"]))
        coll = sorted(r["collectives"].items(), key=lambda kv: -kv[1])[:2]
        coll_s = "; ".join(f"{k} {v/2**30:.2f}GiB" for k, v in coll) or "-"
        out.append(
            f"| {r['arch']} | {r['shape']} | {mesh} | {gib:.2f} | "
            f"{'yes' if gib <= 16.0 else 'NO'} | {r['t_compile_s']} | "
            f"{coll_s} |")
    return "\n".join(out)


def roofline_table(mesh="singlepod") -> str:
    rows = cell_rows(mesh)
    hdr = ("| arch | shape | compute s | memory s | collective s | dominant "
           "| model TF/dev | useful ratio | roofline frac |")
    out = [hdr, "|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.4f} | "
            f"{r['memory_s']:.4f} | {r['collective_s']:.4f} | "
            f"{r['dominant']} | {r['model_tflops_dev']:.1f} | "
            f"{r['useful_ratio']:.3f} | {r['roofline_frac']:.3f} |")
    return "\n".join(out)


def main():
    enable_compile_cache()
    print("## Dry-run table\n")
    print(dryrun_table())
    print("\n## Roofline (single-pod)\n")
    print(roofline_table("singlepod"))


if __name__ == "__main__":
    main()
