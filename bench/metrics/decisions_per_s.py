"""Decisions completed per second of the window.  Each batch's requests
count in proportion to the part of its compute interval (results' time less
``compute_s``) inside the window, so a 256-request batch that straddles an
edge does not make the rate jump by its whole size."""
from loadgen import batches, overlap


def read(run):
    w = run.window
    n, start, done = batches(w.rec)
    if n.size == 0:
        return None
    return float((n * overlap(start, done, w.t0, w.t_end)).sum()) / w.seconds
