"""Median seconds per batch, in ms, of the program's ``serve.encode`` span:
stacking, input noise, thermometer encoding (every bank's, in a forest) and
padding to the bucket, over the batches formed before the profiler started
(``spans.unprofiled``).  Reads ``encode_ms.p50.<suffix>``."""
import spans


def read(run):
    return spans.median_ms(run, "encode")
