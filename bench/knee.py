"""Find the knee of an open-loop cell: the highest Poisson rate the server
sustains without a growing backlog.  Run once when a cell is defined (and
again by a later benchmark PR); its rate then goes into the traffic file.

    python3 bench/knee.py --workload credit-tree.online --seconds 40 \\
        --seeds 11 12 13 --rates 8000 10000 12000 14000

Each rate is one window of the cell per seed, with its traffic's rate
replaced, on a fresh server, in this one process.  A rate holds when every
seed's window is correct and shows no backlog: the median latency of its
last quarter is under 1.25 times that of its first quarter, and its 99th
percentile under five times its median.
"""
import argparse
import json
import sys
import time

import run


def quarters_p50_ms(w) -> list:
    from loadgen import percentile
    lat = w.latency_s()
    q = (w.rec["due"] - w.t0) * 4 // w.seconds
    return [percentile(lat[q == k], 50) * 1e3 for k in range(4)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=40)
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload, trace=False)
    devices = run.require_chips(cell.chips)
    import repro
    from loadgen import percentile
    repro.enable_compile_cache()
    knee = None
    for rate in sorted(args.rates):
        cell.traffic = {**cell.traffic, "rate_per_s": rate}
        holds = True
        for seed in args.seeds:
            result, _, w = run.run_cell(cell, seed, args.seconds, False,
                                        t_start=time.perf_counter(),
                                        devices=devices)
            lat = w.latency_s()
            p50, p99 = (percentile(lat, q) * 1e3 for q in (50, 99))
            qs = quarters_p50_ms(w)
            ok = (result["correct"] and qs[3] < 1.25 * qs[0]
                  and p99 < 5 * p50)
            holds = holds and ok
            print(json.dumps({"rate_per_s": rate, "seed": seed, "ok": ok,
                              "p50_ms": p50, "p99_ms": p99,
                              "quarter_p50_ms": qs,
                              "correct": result["correct"]}), flush=True)
        knee = rate if holds else knee
        if not holds:
            break
    print(json.dumps({"knee_per_s": knee,
                      "rate_at_0.8": None if knee is None
                      else round(0.8 * knee)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
