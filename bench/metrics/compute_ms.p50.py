"""Median ``RequestResult.compute_s`` (batch formed to results ready: encode,
copies, device program, finalize) over the answered requests due before the
profiler started.  Reads ``compute_ms.p50.<suffix>`` for every suffix."""
from loadgen import percentile


def read(run):
    rec = run.unprofiled
    if not rec["ok"].any():
        return None
    return percentile(rec["compute_s"][rec["ok"]], 50) * 1e3
