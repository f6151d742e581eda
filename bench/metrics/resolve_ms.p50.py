"""Median seconds per batch, in ms, of the program's ``serve.resolve`` span:
the batch's metrics, one ``RequestResult`` per request and its
``set_result`` (which runs the callers' done-callbacks), over the batches
formed before the profiler started (``spans.unprofiled``).  Reads
``resolve_ms.p50.<suffix>``."""
import spans


def read(run):
    return spans.median_ms(run, "resolve")
