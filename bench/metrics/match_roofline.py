"""The match's share of its roofline: the least time the chip could take for
the batches served in the traced slice (``roofline.least_seconds``, from the
deployment's real rows and columns and each batch's real request count),
over the device's busy time in the slice.  A batch counts in proportion to
the part of its compute interval inside the slice."""
from loadgen import batches, overlap
from roofline import least_seconds, peak


def read(run):
    tr = run.trace
    if tr is None or tr.busy_s <= 0:
        return None
    n, start, done = batches(run.window.rec)
    share = overlap(start, done, *run.trace_host_bounds)
    pk = peak(run.device_kind)
    least = sum(s * least_seconds(run.banks, int(k), pk)
                for s, k in zip(share, n) if s > 0)
    if least == 0:
        return None
    return 100.0 * least / tr.busy_s
