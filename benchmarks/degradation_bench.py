"""Temporal-degradation campaign: accuracy vs. drift horizon, with and
without online scrubbing.

Two seed-matched chips per dataset (identical drift sample) age along the
same virtual-time checkpoints.  The *no-scrub* arm just keeps serving as
conductances drift and retention flips cells — accuracy collapses once
drifted resistances cross the read midpoint.  The *scrub* arm runs the
margin-policy maintenance pass (``TCAMServer.scrub_now``) at every
checkpoint, which refreshes weak rows through the SET/RESET write planner,
so its accuracy stays within the guardrail (<= 1% below fresh) while the
refresh energy and program pulses land in the wear ledger and the metrics
snapshot.  A final chaos section scrubs concurrently with a live request
stream and asserts every in-flight future resolves exactly once.

The artifact is fully seed-deterministic (virtual clock, no wall time):

    PYTHONPATH=src python -m benchmarks.degradation_bench [--seed 0]
"""
from __future__ import annotations

import argparse
import os
import threading

import numpy as np

from repro import enable_compile_cache
from repro.core import DriftSpec, NonIdealSpec
from repro.serve import ServeConfig, TCAMServer

from .common import ART, add_seed_arg, compiled, emit, write_artifact

# Drift law parameters for the campaign: mild power-law conductance drift
# plus a finite retention time constant, so the no-scrub arm collapses
# inside the checkpoint horizon (flip threshold sqrt(r_hrs/r_lrs) ~ 22x).
DRIFT = DriftSpec(nu=0.05, nu_sigma=0.02, t0=1.0, retention_tau_s=2e6)
CHECKPOINTS = (1e5, 1e6, 3e6, 1e7, 3e7)   # cumulative virtual seconds
GUARDRAIL = 0.01                          # scrubbed accuracy vs fresh
COLLAPSE = 0.02                           # no-scrub must degrade at least this


def _server(c, seed: int, **cfg_kw) -> TCAMServer:
    kw = dict(engine="ref", background=False, max_batch=64)
    kw.update(cfg_kw)
    return TCAMServer(c, nonideal=NonIdealSpec(drift=DRIFT),
                      config=ServeConfig(**kw),
                      rng=np.random.default_rng(seed))


def _accuracy(server: TCAMServer, X, y) -> float:
    preds = np.array([r.prediction for r in server.serve(X)])
    return float((preds == y).mean())


def _margin_min(server: TCAMServer) -> float:
    return float(server.margins().margin.min())


def run_dataset(name: str, *, s: int, seed: int) -> tuple[dict, list[dict]]:
    c, (Xtr, ytr, Xte, yte) = compiled(name, s)
    # identical construction order => identical rng draws => both arms age
    # the exact same sampled chip
    plain = _server(c, seed)
    scrubbed = _server(c, seed)
    fresh = _accuracy(plain, Xte, yte)
    assert _accuracy(scrubbed, Xte, yte) == fresh, "arms diverged at t=0"

    timeline = []
    prev_t = 0.0
    for t in CHECKPOINTS:
        dt = t - prev_t
        prev_t = t
        plain.advance_time(dt)
        scrubbed.advance_time(dt)
        report = scrubbed.scrub_now()
        timeline.append({
            "t_s": t,
            "no_scrub_acc": _accuracy(plain, Xte, yte),
            "no_scrub_margin_min_v": _margin_min(plain),
            "scrub_acc": _accuracy(scrubbed, Xte, yte),
            "scrub_margin_min_v": _margin_min(scrubbed),
            "rows_refreshed": report.n_refreshed,
        })

    deg = scrubbed.metrics()["degradation"]
    wear = scrubbed.health()["degradation"]["wear"]
    summary = {
        "dataset": name,
        "fresh_accuracy": fresh,
        "no_scrub_final": timeline[-1]["no_scrub_acc"],
        "scrub_final": timeline[-1]["scrub_acc"],
        "scrub": deg,
        "wear_total_pulses": wear["total_pulses"],
        "timeline": timeline,
    }
    plain.close()
    scrubbed.close()

    # guardrail campaign acceptance: scrubbing holds accuracy flat while
    # the unscrubbed chip measurably degrades, and every refresh is
    # accounted for in both the energy report and the endurance ledger
    assert summary["scrub_final"] >= fresh - GUARDRAIL, summary
    assert summary["no_scrub_final"] <= fresh - COLLAPSE, summary
    assert deg["scrub_passes"] == len(CHECKPOINTS)
    assert deg["scrub_energy_j"] > 0.0 and deg["scrub_pulses"] > 0
    assert wear["total_pulses"] == deg["scrub_pulses"], (wear, deg)

    rows = [{"dataset": name, "t_s": f"{p['t_s']:.0e}",
             "no_scrub": f"{p['no_scrub_acc']:.4f}",
             "scrubbed": f"{p['scrub_acc']:.4f}",
             "refreshed": p["rows_refreshed"]} for p in timeline]
    return summary, rows


def run_chaos(name: str, *, s: int, seed: int, requests: int = 256) -> dict:
    """Scrub passes must never drop or double-resolve in-flight requests:
    hammer a background server with a request stream while a second thread
    forces scrub/advance cycles, then check every future resolved once."""
    c, (Xtr, ytr, Xte, yte) = compiled(name, s)
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, len(Xte), size=requests)
    server = _server(c, seed, background=True)
    stop = threading.Event()

    def _scrubber() -> None:
        while not stop.is_set():
            server.advance_time(2e5)
            server.scrub_now(force=True)

    th = threading.Thread(target=_scrubber, daemon=True)
    th.start()
    try:
        futs = [server.submit(Xte[i]) for i in idx]
        server.drain(timeout=120)
    finally:
        stop.set()
        th.join(timeout=30)
    resolved = [f for f in futs if f.done() and f.exception() is None]
    served = server.metrics()["requests_served"]
    scrub_passes = server.metrics()["degradation"]["scrub_passes"]
    server.close()
    assert len(resolved) == requests, (len(resolved), requests)
    assert served == requests, (served, requests)
    assert scrub_passes > 0, "chaos arm never scrubbed"
    return {"dataset": name, "requests": requests,
            "resolved_ok": len(resolved), "errors": 0,
            "scrubbed_during_serve": True}


def main(argv=None) -> dict:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--datasets", nargs="+", default=["iris", "cancer"])
    ap.add_argument("--s", type=int, default=32)
    add_seed_arg(ap)
    ap.add_argument("--out", default=os.path.join(ART,
                                                  "degradation_bench.json"))
    args = ap.parse_args(argv)

    summaries, table = [], []
    for name in args.datasets:
        summary, rows = run_dataset(name, s=args.s, seed=args.seed)
        summaries.append(summary)
        table.extend(rows)
    chaos = run_chaos(args.datasets[0], s=args.s, seed=args.seed)

    emit(table, "degradation: accuracy vs drift horizon")
    for sm in summaries:
        print(f"{sm['dataset']:>8}: fresh {sm['fresh_accuracy']:.4f}  "
              f"no-scrub {sm['no_scrub_final']:.4f}  "
              f"scrubbed {sm['scrub_final']:.4f}  "
              f"refresh {sm['scrub']['scrub_energy_j'] * 1e9:.2f} nJ / "
              f"{sm['scrub']['scrub_pulses']} pulses")

    report = {
        "meta": {
            "datasets": list(args.datasets), "s": args.s, "seed": args.seed,
            "checkpoints_s": list(CHECKPOINTS),
            "guardrail": GUARDRAIL,
            "drift": {"nu": DRIFT.nu, "nu_sigma": DRIFT.nu_sigma,
                      "t0": DRIFT.t0,
                      "retention_tau_s": DRIFT.retention_tau_s},
        },
        "datasets": summaries,
        "chaos": chaos,
    }
    write_artifact(args.out, report)
    return report


if __name__ == "__main__":
    main()
