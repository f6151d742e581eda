"""Serving-engine load benchmark: push a randomized request stream through
``repro.serve.TCAMServer``, print wall-clock throughput/latency to stdout,
and dump the seed-deterministic portion of the report (accuracy, request
counters, modelled ReCAM energy/throughput, layout geometry) as JSON to
``artifacts/serve_bench.json`` — same flags + same ``--seed`` produce a
byte-identical artifact.

    PYTHONPATH=src python -m benchmarks.serve_bench [--requests 2048] [--seed 0]
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np

from repro import enable_compile_cache
from repro.dt import load_split
from repro.serve import ServeConfig, TCAMServer

from .common import ART, add_seed_arg, compiled, write_artifact

# Keys of the metrics snapshot that are a pure function of (flags, seed):
# request stream, accuracy, modelled energy per decision, and layout-derived
# hardware figures.  Batching/latency/jit counters depend on wall-clock batch
# formation and stay out of the artifact.
DETERMINISTIC_KEYS = (
    "dataset", "s", "engine", "buckets",
    "requests_enqueued", "requests_served", "accuracy",
    "modelled_nj_per_dec", "active_evals",
    "modelled_mdecs_seq", "modelled_mdecs_pipe", "layout",
)


def run(
    datasets: tuple[str, ...] = ("iris", "cancer", "covid"),
    *,
    requests: int = 2048,
    s: int = 64,
    max_batch: int = 128,
    max_delay_ms: float = 2.0,
    engine: str = "auto",
    seed: int = 0,
) -> list[dict]:
    reports = []
    rng = np.random.default_rng(seed)
    for name in datasets:
        c, (Xtr, ytr, Xte, yte) = compiled(name, s)
        cfg = ServeConfig(max_batch=max_batch, max_delay_s=max_delay_ms / 1e3,
                          engine=engine)
        # randomized arrival order + duplicate queries, like real traffic
        idx = rng.integers(0, len(Xte), size=requests)
        t0 = time.perf_counter()
        with TCAMServer(c, config=cfg) as server:
            server.warmup()
            results = server.serve(Xte[idx])
            stats = server.metrics()
        wall = time.perf_counter() - t0
        preds = np.array([r.prediction for r in results])
        stats.update(
            dataset=name,
            s=s,
            wall_s=wall,
            throughput_rps=len(results) / wall,
            accuracy=float((preds == yte[idx]).mean()),
        )
        reports.append(stats)
    return reports


def main(argv=None) -> list[dict]:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--datasets", nargs="+", default=["iris", "cancer", "covid"])
    ap.add_argument("--requests", type=int, default=2048)
    ap.add_argument("--s", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=128)
    ap.add_argument("--max-delay-ms", type=float, default=2.0)
    ap.add_argument("--engine", default="auto")
    add_seed_arg(ap)
    ap.add_argument("--out", default=os.path.join(ART, "serve_bench.json"))
    args = ap.parse_args(argv)

    reports = run(tuple(args.datasets), requests=args.requests, s=args.s,
                  max_batch=args.max_batch, max_delay_ms=args.max_delay_ms,
                  engine=args.engine, seed=args.seed)
    artifact = {
        "meta": {
            "datasets": list(args.datasets), "requests": args.requests,
            "s": args.s, "max_batch": args.max_batch,
            "max_delay_ms": args.max_delay_ms, "engine": args.engine,
            "seed": args.seed,
        },
        "results": [
            {k: r[k] for k in DETERMINISTIC_KEYS if k in r} for r in reports
        ],
    }
    for r in reports:
        print(f"{r['dataset']:>8}: {r['throughput_rps']:8.0f} req/s  "
              f"total p50/p99 {r['total_latency']['p50_ms']:6.2f}/"
              f"{r['total_latency']['p99_ms']:6.2f} ms  "
              f"fill {r['mean_batch_fill']:.2f}  "
              f"compiles {r['jit_cache']['misses']}  "
              f"{r['modelled_nj_per_dec']:.4f} nJ/dec  "
              f"acc {r['accuracy']:.4f}")
    write_artifact(args.out, artifact)
    return reports


if __name__ == "__main__":
    main()
