"""Model lifecycle benchmark: delta reprogramming savings + zero-downtime
hot swap under load.

Scenario (one JSON report, CI artifact):

1. **Retrain** — v1 is trained on the dataset; v2 on a noise-perturbed copy
   (the production "model drifted, retrain and redeploy" event).  Both are
   published to a ``ModelRegistry`` with lineage v1 -> v2.
2. **Delta vs full reprogramming** — ``plan_delta`` must write strictly
   fewer cells than the naive erase-then-program pass (asserted), with
   modelled write energy / program time / endurance consumption from
   ``reprogram_figures`` for both, plus the wear-leveled variant
   (``wear_level_rows``) and the chip's cumulative ``WearTracker`` ledger.
3. **Hot swap under load** — a background ``TCAMServer`` takes ``--requests``
   requests; mid-stream v2 is staged (mirroring live traffic) and promoted.
   Asserted: *every* submitted future resolves with a result (zero dropped,
   zero errors), and the promoted server's predictions are bit-exact against
   v2's functional-sim reference path.

    PYTHONPATH=src python -m benchmarks.lifecycle_bench [--seed 0]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import time

import numpy as np

from repro import (
    DT2CAM,
    LifecycleManager,
    ModelRegistry,
    ServeConfig,
    TCAMServer,
    WearTracker,
    enable_compile_cache,
    encode_inputs,
    plan_delta,
    plan_full,
    simulate,
    wear_level_rows,
)
from repro.dt import load_split

from .common import ART, emit


def _retrained_pair(dataset: str, s: int, seed: int):
    """v1 on the clean split, v2 on feature-noise-perturbed training data
    (same labels) — a realistic drift-retrain delta, not a toy bitflip."""
    Xtr, ytr, Xte, yte = load_split(dataset)
    rng = np.random.default_rng(seed)
    scale = 0.1 * Xtr.std(axis=0, keepdims=True)
    Xtr2 = Xtr + rng.normal(0.0, 1.0, size=Xtr.shape) * scale
    v1 = DT2CAM(s=s, max_depth=8).fit(Xtr, ytr)
    v2 = DT2CAM(s=s, max_depth=8).fit(Xtr2, ytr)
    return v1, v2, (Xtr, ytr, Xte, yte)


def reprogram_study(v1, v2, registry: ModelRegistry, dataset: str) -> dict:
    """Publish lineage, plan delta/full/wear-leveled passes, model energy."""
    r1 = registry.publish(v1.compiled, dataset, metadata={"gen": 1})
    r2 = registry.publish(v2.compiled, dataset,
                          parents=[r1.version_id], metadata={"gen": 2})
    old_lay, new_lay = v1.compiled.layout, v2.compiled.layout

    delta = plan_delta(old_lay.cells, new_lay.cells,
                       old_class_bits=old_lay.class_bits,
                       new_class_bits=new_lay.class_bits)
    full = plan_full(old_lay.cells, new_lay.cells,
                     old_class_bits=old_lay.class_bits,
                     new_class_bits=new_lay.class_bits)
    assert delta.n_cells_written < full.n_cells_written, (
        f"delta ({delta.n_cells_written} cells) must write strictly fewer "
        f"cells than full reprogramming ({full.n_cells_written})"
    )

    # wear-leveled placement: same candidate, rows re-placed to minimise
    # pulses against the live grid (and spread endurance consumption)
    wear = WearTracker()
    wear.record(plan_full(np.zeros((0, 0), np.int8), old_lay.cells,
                          new_class_bits=old_lay.class_bits))
    remap = wear_level_rows(new_lay, old_lay.cells, wear)
    leveled = plan_delta(old_lay.cells, remap.layout.cells,
                         old_class_bits=old_lay.class_bits,
                         new_class_bits=remap.layout.class_bits)
    wear.record(leveled)

    return {
        "versions": {
            "v1": r1.version_id, "v2": r2.version_id,
            "lineage": [v.version_id
                        for v in registry.lineage(r2.version_id)],
        },
        "delta": {**delta.summary(), "figures": delta.figures()},
        "full": {**full.summary(), "figures": full.figures()},
        "wear_leveled_delta": {**leveled.summary(),
                               "figures": leveled.figures(),
                               "remap": remap.summary()},
        "cells_saved": full.n_cells_written - delta.n_cells_written,
        "energy_saving_x": (full.figures()["energy_j"]
                            / max(delta.figures()["energy_j"], 1e-30)),
        "wear": wear.snapshot(),
    }


def hot_swap_under_load(v1, v2, registry: ModelRegistry, dataset: str,
                        data, *, n_requests: int, seed: int) -> dict:
    """Stage + promote v2 while a background server is taking traffic."""
    Xtr, ytr, Xte, yte = data
    rng = np.random.default_rng(seed)
    Xq = Xte[rng.integers(0, len(Xte), size=n_requests)]

    r1 = registry.publish(v1.compiled, dataset)
    r2 = registry.publish(v2.compiled, dataset, parents=[r1.version_id])

    cfg = ServeConfig(engine="ref", max_batch=64, max_delay_s=0.001,
                      background=True)
    srv = TCAMServer(v1.compiled, config=cfg,
                     rng=np.random.default_rng(seed))
    mgr = LifecycleManager(registry, srv, live_version=r1.version_id)

    stage_at, promote_at = n_requests // 4, n_requests // 2
    futs = []
    promotion = None
    t0 = time.perf_counter()
    for i, x in enumerate(Xq):
        futs.append(srv.submit(x))
        if i == stage_at:
            mgr.stage(r2.version_id, mirror_fraction=0.5)
        elif i >= promote_at and promotion is None:
            # a retrained model legitimately disagrees with v1 on live
            # traffic — the operator tolerance is wide open here; the
            # correctness gate is the candidate's own canary
            rep = mgr.promote(min_shadow_batches=1, max_disagreement=1.0)
            if not rep.staged:      # gate actually evaluated
                promotion = rep
                assert rep.promoted, f"promotion failed: {rep.reason}"
    srv.drain(timeout=120.0)
    wall = time.perf_counter() - t0
    if promotion is None:          # not enough mirrored batches mid-stream
        promotion = mgr.promote(min_shadow_batches=0, max_disagreement=1.0)
        assert promotion.promoted, f"promotion failed: {promotion.reason}"

    dropped = sum(1 for f in futs if not f.done())
    errors = sum(1 for f in futs if f.done() and f.exception() is not None)
    assert dropped == 0, f"{dropped} requests never resolved across the swap"
    assert errors == 0, f"{errors} requests errored across the swap"

    # promoted model must be bit-exact against v2's functional-sim reference
    n_check = min(256, len(Xte))
    served = np.array([r.prediction for r in srv.serve(Xte[:n_check])])
    ref = simulate(v2.compiled.layout,
                   encode_inputs(v2.compiled.lut, Xte[:n_check])).predictions
    assert np.array_equal(served, ref), \
        "promoted model is not bit-exact vs its simulate() reference"

    metrics = srv.metrics()
    srv.close()
    return {
        "n_requests": n_requests,
        "wall_s": wall,
        "dropped": dropped,
        "errors": errors,
        "promotion": promotion.summary(),
        "post_promotion_bit_exact": True,
        "lifecycle_metrics": metrics["lifecycle"],
        "live_version": mgr.live_version,
        "acc_v1": float((np.asarray([
            int(p) for p in simulate(
                v1.compiled.layout,
                encode_inputs(v1.compiled.lut, Xte)).predictions
        ]) == yte).mean()),
        "acc_v2": float((served == yte[:n_check]).mean()),
    }


def run(dataset: str = "cancer", *, s: int = 128, n_requests: int = 1000,
        seed: int = 0, registry_root: str | None = None) -> dict:
    root = registry_root or os.path.join(ART, "lifecycle_registry")
    shutil.rmtree(root, ignore_errors=True)
    registry = ModelRegistry(root)
    v1, v2, data = _retrained_pair(dataset, s, seed)
    report = {
        "dataset": dataset,
        "s": s,
        "seed": seed,
        "reprogramming": reprogram_study(v1, v2, registry, dataset),
        "hot_swap": hot_swap_under_load(
            v1, v2, registry, dataset, data,
            n_requests=n_requests, seed=seed,
        ),
    }
    return report


def main(argv=None) -> dict:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="cancer")
    ap.add_argument("--s", type=int, default=128)
    ap.add_argument("--requests", type=int, default=1000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(ART, "lifecycle_bench.json"))
    args = ap.parse_args(argv)

    report = run(args.dataset, s=args.s, n_requests=args.requests,
                 seed=args.seed)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)

    rp = report["reprogramming"]
    emit([{"delta_cells": rp["delta"]["cells_written"],
           "full_cells": rp["full"]["cells_written"]}],
         f"lifecycle_bench[{args.dataset}]")
    print(f"delta writes {rp['delta']['cells_written']} cells "
          f"({rp['delta']['figures']['energy_j'] * 1e9:.2f} nJ) vs full "
          f"{rp['full']['cells_written']} "
          f"({rp['full']['figures']['energy_j'] * 1e9:.2f} nJ) — "
          f"{rp['energy_saving_x']:.1f}x energy saving")
    hs = report["hot_swap"]
    print(f"hot swap: {hs['n_requests']} requests, dropped={hs['dropped']} "
          f"errors={hs['errors']} promoted={hs['promotion']['promoted']} "
          f"bit_exact={hs['post_promotion_bit_exact']}")
    print(f"# wrote {args.out}")
    return report


if __name__ == "__main__":
    main()
