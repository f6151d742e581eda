"""Median seconds per batch, in ms, of the program's ``serve.d2h`` span: the
copy of the batch's outputs to the host (``np.asarray``), over the batches
formed before the profiler started (``spans.unprofiled``).  Reads
``d2h_ms.p50.<suffix>``."""
import spans


def read(run):
    return spans.median_ms(run, "d2h")
