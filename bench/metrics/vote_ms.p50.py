"""Median seconds per batch, in ms, of the program's ``serve.vote`` span: the
forest's aggregation (soft vote, or a boosted model's margin and link),
nested inside ``serve.finalize``, over the batches formed before the
profiler started (``spans.unprofiled``).  None where the batch records have
no ``vote_s``.  Reads ``vote_ms.p50.<suffix>``."""
import spans


def read(run):
    rec = spans.unprofiled(run)
    if rec is None or "vote_s" not in rec.dtype.names:
        return None
    return spans.median_ms(run, "vote")
