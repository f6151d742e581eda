"""Median seconds per batch, in ms, of the program's ``serve.finalize`` span:
slicing and energy, and in a forest the per-bank reduction and the vote,
over the batches formed before the profiler started (``spans.unprofiled``).
Reads ``finalize_ms.p50.<suffix>``."""
import spans


def read(run):
    return spans.median_ms(run, "finalize")
