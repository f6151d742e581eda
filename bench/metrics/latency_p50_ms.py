"""Median latency, due time to Future resolved, of every request due in an
open-loop window (a failed request counts as infinitely late)."""
from loadgen import percentile


def read(run):
    if run.window.loop != "open":
        return None
    return percentile(run.window.latency_s(), 50) * 1e3
