"""Bytes of outputs the program copied back from the device per decision:
Σ ``d2h_bytes`` over Σ requests of the batches formed before the profiler
started (padded rows count)."""
import spans


def read(run):
    return spans.per_decision(run, "d2h_bytes")
