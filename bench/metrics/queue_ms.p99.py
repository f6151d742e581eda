"""99th percentile of ``RequestResult.queue_s`` (submit to batch formed,
the server's clock) over the answered requests due before the profiler
started."""
from loadgen import percentile


def read(run):
    rec = run.unprofiled
    if not rec["ok"].any():
        return None
    return percentile(rec["queue_s"][rec["ok"]], 99) * 1e3
