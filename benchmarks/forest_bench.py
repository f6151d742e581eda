"""Multi-bank forest scaling benchmark: compile one bagged forest, then run
its first 1/2/4/8 banks through ``repro.ForestExecutor`` and record how both
the *modelled* pipelined throughput (sum of per-bank f_max / II, from the
analog ReCAM model) and the *measured* host throughput scale with bank
count.  Dumps ``artifacts/forest_bench.json``; the modelled aggregate dec/s
series must be strictly increasing in bank count (asserted — it is the
paper's multi-array pipelining story).

    PYTHONPATH=src python -m benchmarks.forest_bench [--banks 1 2 4 8]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro import (ForestExecutor, compile_forest, enable_compile_cache,
                   forest_infer_ref, train_forest)
from repro.dt import load_split

from .common import ART, emit


def run(
    dataset: str = "cancer",
    *,
    banks: tuple[int, ...] = (1, 2, 4, 8),
    s: int = 128,
    batch: int = 256,
    repeats: int = 5,
    engine: str = "banked",
    seed: int = 0,
) -> dict:
    Xtr, ytr, Xte, yte = load_split(dataset)
    trees = train_forest(Xtr, ytr, n_trees=max(banks), max_depth=8, seed=seed)
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, len(Xte), size=batch)
    Xq, yq = Xte[idx], yte[idx]

    rows = []
    for n in banks:
        forest = compile_forest(trees[:n], s=s)
        ex = ForestExecutor(forest, engine=engine)
        compiles = ex.warmup(batch)
        # measured: median wall time over repeats (post-warmup, steady state)
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            res = ex.infer(Xq)
            times.append(time.perf_counter() - t0)
        wall = float(np.median(times))
        ref = forest_infer_ref(forest, Xq)
        agg = res.figures["aggregate"]
        rows.append({
            "n_banks": n,
            "n_groups": ex.plan.n_groups,
            "rows_total": sum(int(l.cells.shape[0]) for l in forest.layouts),
            "engine": engine,
            "jit_compiles": compiles,
            "wall_s": wall,
            "measured_decs_per_s": n * batch / wall,
            "modelled_decs_pipe": agg["decs_pipe"],
            "modelled_ensemble_decs_pipe": agg["ensemble_decs_pipe"],
            "modelled_latency_s": agg["latency_s"],
            "area_mm2": agg["area_m2"] * 1e6,
            "energy_nj_per_dec": agg.get("energy_per_dec_j", 0.0) * 1e9,
            "accuracy": float((res.predictions == yq).mean()),
            "parity_with_ref": bool(
                (res.predictions == ref.predictions).all()
            ),
        })

    series = [r["modelled_decs_pipe"] for r in rows]
    monotone = all(b > a for a, b in zip(series, series[1:]))
    assert monotone, f"modelled dec/s not increasing with banks: {series}"
    return {
        "dataset": dataset,
        "s": s,
        "batch": batch,
        "seed": seed,
        "banks": rows,
        "modelled_decs_pipe_monotone": monotone,
    }


def main(argv=None) -> dict:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="cancer")
    ap.add_argument("--banks", nargs="+", type=int, default=[1, 2, 4, 8])
    ap.add_argument("--s", type=int, default=128)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--engine", default="banked")
    ap.add_argument("--seed", type=int, default=0,
                    help="forest training + query sampling seed (the "
                         "artifact JSON is reproducible run-to-run)")
    ap.add_argument("--out", default=os.path.join(ART, "forest_bench.json"))
    args = ap.parse_args(argv)

    report = run(args.dataset, banks=tuple(args.banks), s=args.s,
                 batch=args.batch, repeats=args.repeats, engine=args.engine,
                 seed=args.seed)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    emit(report["banks"], f"forest_bench[{args.dataset}]")
    for r in report["banks"]:
        print(f"banks={r['n_banks']:2d}: modelled "
              f"{r['modelled_decs_pipe'] / 1e6:9.1f} Mdec/s  measured "
              f"{r['measured_decs_per_s']:10.0f} dec/s  "
              f"acc {r['accuracy']:.4f}  parity {r['parity_with_ref']}")
    print(f"# wrote {args.out}")
    return report


if __name__ == "__main__":
    main()
