"""Batched DT-inference serving on the TCAM kernels (the paper's kind of
deployment: a stream of classification requests answered by one massively
parallel ternary match).

    PYTHONPATH=src python examples/serve_tcam.py [--dataset covid] [--s 64]

Requests are pushed one at a time into a ``repro.serve.TCAMServer`` — the
production engine: adaptive batch formation (flush on max-batch or deadline),
padding-bucket batching with a warm jit compile cache, automatic engine
selection (bit-packed kernel when legal, MXU bitplane kernel otherwise) and a
metrics layer.  The printout reports accuracy, serving latency percentiles,
and the modelled ReCAM energy/throughput — consistent bit-for-bit with
``core.simulate`` / ``DT2CAM.infer``.
"""
import argparse
import time

import numpy as np

from repro import enable_compile_cache
from repro.core import compile_tree, train_tree
from repro.dt import DATASETS, load_split
from repro.serve import ServeConfig, TCAMServer


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="covid")
    ap.add_argument("--s", type=int, default=64)
    ap.add_argument("--requests", type=int, default=1024)
    ap.add_argument("--max-batch", type=int, default=128)
    ap.add_argument("--max-delay-ms", type=float, default=2.0)
    ap.add_argument("--engine", default="auto",
                    choices=["auto", "mxu", "packed", "ref"])
    args = ap.parse_args()

    spec = DATASETS[args.dataset]
    Xtr, ytr, Xte, yte = load_split(args.dataset)
    tree = train_tree(Xtr, ytr, max_depth=spec.max_depth,
                      max_leaves=spec.max_leaves)
    c = compile_tree(tree, args.s)
    lay = c.layout
    print(f"{args.dataset}: LUT {c.lut.n_rows}x{c.lut.width}, "
          f"{lay.n_rwd}x{lay.n_cwd} tiles of {args.s}x{args.s}")

    cfg = ServeConfig(max_batch=args.max_batch,
                      max_delay_s=args.max_delay_ms / 1e3,
                      engine=args.engine)
    idx = np.arange(args.requests) % len(Xte)
    t0 = time.perf_counter()
    with TCAMServer(c, config=cfg) as server:
        print(f"engine: {server.engine}, buckets: {server.policy.buckets}, "
              f"warmed {server.warmup()} compiles")
        results = server.serve(Xte[idx])
        stats = server.metrics()
    dt = time.perf_counter() - t0

    preds = np.array([r.prediction for r in results])
    acc = float((preds == yte[idx]).mean())
    dev = stats["device"]   # jax.devices()[0], as JAX reports it
    mode = "Pallas interpret mode" if stats["interpret"] else "compiled"
    print(f"served {len(results)} requests in {dt:.2f}s "
          f"({len(results) / dt:.0f} req/s on {dev['platform']} "
          f"{dev['kind']}, {mode}) "
          f"in {stats['batches']} batches "
          f"(fill {stats['mean_batch_fill']:.2f}, "
          f"jit compiles {stats['jit_cache']['misses']})")
    print(f"accuracy: {acc:.4f}")
    print(f"queue   p50/p99: {stats['queue_latency']['p50_ms']:.2f}/"
          f"{stats['queue_latency']['p99_ms']:.2f} ms")
    print(f"compute p50/p99: {stats['compute_latency']['p50_ms']:.2f}/"
          f"{stats['compute_latency']['p99_ms']:.2f} ms")
    print(f"modelled ReCAM: {stats['modelled_nj_per_dec']:.4f} nJ/dec, "
          f"{stats['modelled_mdecs_seq']:.1f} M dec/s sequential, "
          f"{stats['modelled_mdecs_pipe']:.0f} M dec/s pipelined")


if __name__ == "__main__":
    main()
