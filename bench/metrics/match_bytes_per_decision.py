"""Bytes of placed cell operands the forest's match read per decision:
Σ ``match_bytes`` over Σ requests of the batches formed before the profiler
started.  A batch reads every plan group's bitplanes and ``kmax`` once,
padding included, whatever its size.  None where the batch records have no
such field."""
import spans


def read(run):
    rec = spans.unprofiled(run)
    if rec is None or "match_bytes" not in rec.dtype.names:
        return None
    return spans.per_decision(run, "match_bytes")
