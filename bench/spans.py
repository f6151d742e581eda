"""The program's batch records, for the readers of per-layer metrics.

``TCAMServer.metrics_store.batch_records()`` gives one record per batch:
its id, the time it was formed on the server's clock (the benchmark's
clock too: both are ``time.perf_counter``), its size and bucket, the
seconds of each of its ``serve.*`` spans (``<phase>_s``) and the bytes it
copied to and from the device.

``Run`` (``run.py``) carries no such records: ``records(run)`` takes
``run.batches`` where a run has that field, and otherwise asks the
``TCAMServer`` that served the window, found among the locals of the
callers up the stack (``run_cell`` holds it, closed, while the readers
run).  A program without batch records gives None, as does every reader
of them.
"""
from __future__ import annotations

import sys

import numpy as np

from loadgen import percentile


def records(run):
    """Every batch record the server kept, oldest first; None without."""
    if hasattr(run, "batches"):
        return run.batches
    frame = sys._getframe(1)
    while frame is not None:
        for v in list(frame.f_locals.values()):
            store = getattr(v, "metrics_store", None)
            if store is not None:
                get = getattr(store, "batch_records", None)
                return None if get is None else get()
        frame = frame.f_back
    return None


def unprofiled(run):
    """The records of the batches formed inside the window and before any
    profiling started: the cut ``Run.unprofiled`` makes of the requests."""
    rec = records(run)
    if rec is None:
        return None
    w = run.window
    end = w.t_end if run.trace is None else run.trace_host_bounds[0]
    return rec[(rec["t_form"] >= w.t0) & (rec["t_form"] < end)]


def median_ms(run, phase: str):
    """Median seconds of span ``serve.<phase>`` per batch, in ms."""
    rec = unprofiled(run)
    if rec is None or rec.size == 0:
        return None
    return percentile(rec[f"{phase}_s"], 50) * 1e3


def per_decision(run, field: str):
    """Σ ``field`` over Σ requests of the batches."""
    rec = unprofiled(run)
    if rec is None or rec["n"].sum() == 0:
        return None
    return float(rec[field].sum()) / float(rec["n"].sum())


def waiting_share(run):
    """Share of the traced device slice in which the server held no batch:
    the slice less the union of the ``serve.batch`` spans in it (batches
    run one at a time, so their spans do not overlap).  The device runs
    only inside a batch, so all of this time is device idle time."""
    rec = records(run)
    if rec is None or run.trace is None:
        return None
    a, b = run.trace_host_bounds
    start = rec["t_form"]
    end = start + rec["batch_s"]
    held = np.clip(np.minimum(end, b) - np.maximum(start, a), 0, None).sum()
    return 1.0 - float(held) / (b - a)
