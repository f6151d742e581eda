"""Fault-injection chaos harness for the reliability layer.

Two experiment families, emitted as one JSON report (CI artifact):

1. **Fault sweep** — for each dataset and stuck-at probability p (=p_sa0
   =p_sa1), sample faulty chips and measure:
     * BIST coverage against the analytic behavior-change ground truth;
     * test accuracy of the ideal chip, the faulty chip, and the chip after
       spare-row repair (the headline claim: repair recovers to within ~1%
       of ideal at p = 2%);
     * k-chip majority voting (``ReplicatedServer``) accuracy and the
       observed disagreement rate.
2. **Serving chaos** — a live ``TCAMServer`` under injected *compute*
   faults (via ``fault_injection_hook``), a bounded queue, and per-request
   deadlines.  The invariant under test: the server never hangs — every
   submitted Future resolves with a result or a typed serving error, and
   the shed / deadline / retry / compute-failure counters surface in
   ``metrics()``.

Run:  PYTHONPATH=src python -m benchmarks.chaos_harness \
          --datasets iris,cancer,car --p-grid 0.005,0.02 --trials 3
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import numpy as np

from benchmarks.common import ART, fitted_tree
from repro import enable_compile_cache
from repro.core import compile_tree
from repro.core import (NonIdealSpec, apply_saf_mask, encode_inputs,
                        sample_saf, simulate)
from repro.reliability import (
    ReplicatedServer,
    behavior_changed_rows,
    repair_layout,
    row_utilization,
    run_bist,
)
from repro.serve import (
    ComputeFailed,
    DeadlineExceeded,
    Rejected,
    ServeConfig,
    TCAMServer,
)


def _acc(layout, lut, X, y) -> float:
    return float((simulate(layout, encode_inputs(lut, X)).predictions == y).mean())


# -- experiment 1: stuck-at fault sweep (BIST coverage + repair recovery) ----
def fault_sweep(datasets, p_grid, trials, k, seed) -> list[dict]:
    rows = []
    for name in datasets:
        tree, (Xtr, ytr, Xte, yte) = fitted_tree(name)
        n = compile_tree(tree).layout.n_rows
        c = compile_tree(tree, spare_rows=2 * n)
        lay, lut = c.layout, c.lut
        used = 1 + lay.width
        acc_ideal = _acc(lay, lut, Xte, yte)
        prio = row_utilization(lay, encode_inputs(lut, Xtr))
        for p in p_grid:
            spec = NonIdealSpec(p_sa0=p, p_sa1=p)
            for trial in range(trials):
                rng = np.random.default_rng(seed + 1000 * trial)
                mask = sample_saf(lay.cells.shape, p, p, rng)
                faulty = apply_saf_mask(lay.cells, mask)
                flay = dataclasses.replace(lay, cells=faulty)

                bist = run_bist(faulty, lay.cells, used=used,
                                n_rows=lay.cells.shape[0])
                changed = behavior_changed_rows(lay.cells, faulty, used)
                rlay, _, rr = repair_layout(
                    flay, lay.cells, mask, bist.defective_rows, priority=prio
                )

                # k-chip majority voting on an eval slice (ref engine keeps
                # the harness fast; the voting logic is engine-agnostic)
                n_eval = min(64, len(yte))
                with ReplicatedServer(
                    c, k=k, nonideal=spec,
                    rng=np.random.default_rng(seed + 1000 * trial),
                    config=ServeConfig(engine="ref", background=False,
                                       max_batch=n_eval),
                ) as rs:
                    voted = rs.serve(Xte[:n_eval])
                    acc_voted = float(np.mean(
                        [v.prediction for v in voted] == yte[:n_eval]
                    ))
                    vote_m = rs.metrics()

                rows.append({
                    "dataset": name, "p": p, "trial": trial,
                    "defective_rows": bist.n_defective,
                    "changed_rows": int(changed.sum()),
                    "bist_coverage": bist.coverage(changed),
                    "probes_run": bist.probes_run,
                    "acc_ideal": acc_ideal,
                    "acc_faulty": _acc(flay, lut, Xte, yte),
                    "acc_repaired": _acc(rlay, lut, Xte, yte),
                    "repair": rr.summary(),
                    "k": k,
                    "acc_voted": acc_voted,
                    "disagreement_rate": vote_m["disagreement_rate"],
                })
                r = rows[-1]
                print(f"{name} p={p} t{trial}: cov={r['bist_coverage']:.3f} "
                      f"acc i/f/r/v={acc_ideal:.3f}/{r['acc_faulty']:.3f}/"
                      f"{r['acc_repaired']:.3f}/{acc_voted:.3f} "
                      f"repaired={rr.rows_repaired} "
                      f"unrep={len(rr.unrepaired)}")
    return rows


# -- experiment 2: serving chaos (compute faults, shedding, deadlines) -------
def serving_chaos(dataset, seed) -> dict:
    import threading

    tree, (Xtr, ytr, Xte, yte) = fitted_tree(dataset)
    c = compile_tree(tree)
    X = np.tile(np.asarray(Xte), (max(1, 64 // len(Xte)) + 1, 1))

    # 2a: transient compute faults absorbed by the retry budget
    fail_next = [2]

    def flaky(_X):
        if fail_next[0] > 0:
            fail_next[0] -= 1
            raise RuntimeError("injected transient device fault")

    cfg = ServeConfig(engine="ref", max_batch=16, max_delay_s=0.001,
                      max_retries=3, retry_backoff_s=0.001)
    with TCAMServer(c, config=cfg, rng=np.random.default_rng(seed)) as s:
        s.fault_injection_hook = flaky
        res = s.serve(X[:32])
        retried = s.metrics()["reliability"]
        ok_after_retry = len(res) == 32 and retried["retries"] >= 2

    # 2b: a stalled-then-faulty device, a tiny bounded queue, and short
    # per-request deadlines: every future must still resolve (result or
    # typed error) and drain must not hang.  The first batch stalls the
    # worker (gate) so the queue genuinely fills and queued requests expire.
    gate = threading.Event()
    calls = [0]

    def stall_then_fault(_X):
        calls[0] += 1
        if calls[0] <= 2:          # first batch + its one retry
            gate.wait(30.0)
            raise RuntimeError("injected persistent device fault")

    cfg = ServeConfig(engine="ref", max_batch=4, min_bucket=4,
                      max_delay_s=0.001,
                      max_queue=8, request_timeout_s=0.05,
                      max_retries=1, retry_backoff_s=0.001)
    counts = {"ok": 0, "rejected": 0, "deadline": 0, "compute_failed": 0}
    with TCAMServer(c, config=cfg, rng=np.random.default_rng(seed)) as s:
        s.fault_injection_hook = stall_then_fault
        futs = [s.submit(x) for x in X[:40]]   # floods the bounded queue
        time.sleep(0.2)                        # queued requests expire
        gate.set()                             # stalled batch fails + retries
        s.drain(timeout=60.0)
        futs += [s.submit(x) for x in X[:8]]   # device recovered
        s.drain(timeout=60.0)
        for f in futs:
            assert f.done(), "unresolved future: the server hung"
            e = f.exception()
            if e is None:
                counts["ok"] += 1
            elif isinstance(e, Rejected):
                counts["rejected"] += 1
            elif isinstance(e, DeadlineExceeded):
                counts["deadline"] += 1
            elif isinstance(e, ComputeFailed):
                counts["compute_failed"] += 1
        chaos_metrics = s.metrics()["reliability"]

    report = {
        "dataset": dataset,
        "transient": {"served": ok_after_retry, "metrics": retried},
        "persistent": {"outcomes": counts, "metrics": chaos_metrics,
                       "all_futures_resolved": True,
                       "n_futures": len(futs)},
    }
    print(f"chaos[{dataset}]: transient served={ok_after_retry} "
          f"retries={retried['retries']} | persistent outcomes={counts}")
    return report


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--datasets", default="iris,cancer,car")
    ap.add_argument("--p-grid", default="0.005,0.02")
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--k", type=int, default=3)
    ap.add_argument("--seed", type=int, default=100)
    ap.add_argument("--out", default=os.path.join(ART, "chaos_harness.json"))
    args = ap.parse_args()

    datasets = [d for d in args.datasets.split(",") if d]
    p_grid = [float(p) for p in args.p_grid.split(",") if p]

    t0 = time.time()
    # meta carries only seed-determined fields: same flags + same seed ->
    # byte-identical artifact JSON (wall time goes to stdout, not the file)
    report = {
        "meta": {"datasets": datasets, "p_grid": p_grid,
                 "trials": args.trials, "k": args.k, "seed": args.seed},
        "fault_sweep": fault_sweep(datasets, p_grid, args.trials,
                                   args.k, args.seed),
        "serving_chaos": serving_chaos(datasets[0], args.seed),
    }

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out} ({time.time() - t0:.1f}s)")


if __name__ == "__main__":
    main()
