"""Bytes the program copied to the device per decision: Σ ``h2d_bytes``
over Σ requests of the batches formed before the profiler started (padded
rows count)."""
import spans


def read(run):
    return spans.per_decision(run, "h2d_bytes")
