"""Record the small chip trace that ``test_trace.py`` reduces.

    python3 bench/tests/record_trace.py credit-tree.batch 0.2

Runs the cell's window for 3 s with a slice of the given seconds profiled,
keeps the events of the lines the reduction reads that overlap the slice,
writes them to ``bench/tests/data/<cell>.trace.json.gz`` and prints what
the reduction makes of them.
"""
import gzip
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import deploy  # noqa: E402
import loadgen  # noqa: E402
import run  # noqa: E402
import tracing  # noqa: E402


def main(workload: str, length: float) -> None:
    cell = run.load_cell(workload, trace=True)
    run.require_chips(1)
    import repro
    repro.enable_compile_cache()
    dep = deploy.build(cell.config)
    server = repro.TCAMServer(
        dep.compiled, config=repro.ServeConfig(**cell.config["serve"]))
    server.warmup()
    prof = tracing.SliceProfiler(offset=1.0, length=length, python=True)
    loadgen.drive(server.submit, cell.traffic, dep.X_test, 3.0, 1,
                  on_start=prof.arm)
    server.close()
    tr = prof.load()
    lo, hi = tracing._slice_bounds(tr)
    # the lines the reduction reads: device ops, the serving thread and the
    # slice's marker
    serving = tracing._serving_line(tr)
    kept = {p: {ln: [e for e in evs if e[1] + e[2] >= lo and e[1] <= hi]
                for ln, evs in lines.items()
                if ln == tracing.OPS_LINE or evs is serving
                or any(e[0] == tracing.SLICE for e in evs)}
            for p, lines in tr.planes.items()}
    summary = tracing.reduce(tracing.Trace(kept), 1)
    out = BENCH / "tests" / "data" / f"{workload}.trace.json.gz"
    out.parent.mkdir(exist_ok=True)
    with gzip.open(out, "wt") as fh:
        json.dump(kept, fh)
    print(json.dumps({"wrote": str(out), "bytes": out.stat().st_size,
                      "busy_s": summary.busy_s, "window_s": summary.window_s,
                      "idle_gaps": summary.idle_gaps[:3],
                      "device_ops": summary.device_ops[:3]}))


if __name__ == "__main__":
    main(sys.argv[1], float(sys.argv[2]))
