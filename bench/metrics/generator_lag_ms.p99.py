"""How late the load generator submitted: 99th percentile of submit time
minus due time, on the benchmark's own clock, over the requests due before
the profiler started (open loop only)."""
from loadgen import percentile


def read(run):
    rec = run.unprofiled
    if run.window.loop != "open" or rec.size == 0:
        return None
    return percentile(rec["submit"] - rec["due"], 99) * 1e3
