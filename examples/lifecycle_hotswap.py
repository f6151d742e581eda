"""Zero-downtime model update: registry -> delta reprogramming -> shadow ->
promote, on a live serving stream.

    PYTHONPATH=src python examples/lifecycle_hotswap.py [--dataset cancer]

The production event this walks through: a model drifts, gets retrained, and
the new version must reach the chip without dropping a request.

1. v1 and v2 (retrained on perturbed data) are published to a
   ``ModelRegistry`` — content-hashed, lineage-tracked, round-trip exact.
2. The ``LifecycleManager`` plans the reprogramming pass at write-pulse
   resolution: the delta touches only the cells whose state changed, and the
   modelled write energy / program time / endurance consumption are printed
   against the naive full erase-then-program pass.
3. ``stage()`` loads v2 into the server's shadow slot; a fraction of live
   traffic is mirrored through it and compared prediction-for-prediction.
4. ``promote()`` gates on shadow disagreement and the candidate's own golden
   canary, then atomically swaps v2 live — in-flight batches finish on v1,
   every future resolves.
"""
import argparse

import numpy as np

import repro
from repro.dt import DATASETS, load_split
from repro.serve import ServeConfig, TCAMServer


def main():
    repro.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="cancer")
    ap.add_argument("--s", type=int, default=128)
    ap.add_argument("--requests", type=int, default=512)
    ap.add_argument("--registry", default="artifacts/example_registry")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    spec = DATASETS[args.dataset]
    Xtr, ytr, Xte, yte = load_split(args.dataset)
    rng = np.random.default_rng(args.seed)

    # v1 on the clean split, v2 retrained after simulated drift
    v1 = repro.DT2CAM(s=args.s, max_depth=spec.max_depth).fit(Xtr, ytr)
    noise = rng.normal(0, 1, Xtr.shape) * 0.1 * Xtr.std(0, keepdims=True)
    v2 = repro.DT2CAM(s=args.s, max_depth=spec.max_depth).fit(
        Xtr + noise, ytr
    )

    reg = repro.ModelRegistry(args.registry)
    r1 = reg.publish(v1.compiled, args.dataset, metadata={"gen": 1})
    r2 = reg.publish(v2.compiled, args.dataset,
                     parents=[r1.version_id], metadata={"gen": 2})
    print(f"registry: {r1.version_id} -> {r2.version_id} "
          f"({len(reg)} versions)")

    cfg = ServeConfig(engine="ref", max_batch=64, max_delay_s=0.001)
    with TCAMServer(v1.compiled, config=cfg) as srv:
        mgr = repro.LifecycleManager(reg, srv, live_version=r1.version_id)

        # serve the first half of the stream on v1
        idx = rng.integers(0, len(Xte), size=args.requests)
        half = args.requests // 2
        futs = srv.submit_many(Xte[idx[:half]])

        # stage v2: delta-plan the reprogramming, mirror half of the traffic
        plan = mgr.stage(r2.version_id, mirror_fraction=0.5)
        figs = plan.figures()
        full = repro.plan_full(v1.compiled.layout.cells,
                               v2.compiled.layout.cells).figures()
        print(f"delta reprogram: {plan.n_cells_written} cells, "
              f"{figs['pulses']} pulses, {figs['energy_j'] * 1e9:.2f} nJ "
              f"(full pass: {full['pulses']} pulses, "
              f"{full['energy_j'] * 1e9:.2f} nJ)")

        # second half of the stream runs with the shadow mirror active
        futs += srv.submit_many(Xte[idx[half:]])
        srv.drain(timeout=120.0)

        report = mgr.promote(min_shadow_batches=1, max_disagreement=1.0)
        print(f"promotion: {report.reason} "
              f"(mirrored {report.shadow_requests} requests, "
              f"disagreement {report.disagreement_rate:.3f}, "
              f"canary {report.canary_accuracy:.3f})")

        dropped = sum(1 for f in futs if not f.done() or f.exception())
        served = np.array([r.prediction
                           for r in srv.serve(Xte[: min(256, len(Xte))])])
        ref = repro.simulate(
            v2.compiled.layout,
            repro.encode_inputs(v2.compiled.lut, Xte[: len(served)]),
        ).predictions
        print(f"dropped/errored across the swap: {dropped}")
        print(f"promoted model bit-exact vs v2 sim ref: "
              f"{bool(np.array_equal(served, ref))}")
        print(f"wear ledger: {mgr.wear.snapshot()}")
        print(f"live version: {mgr.live_version}")


if __name__ == "__main__":
    main()
