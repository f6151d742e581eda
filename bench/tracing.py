"""Profile slices of the window, and reduce their traces to numbers.

A run with ``--trace 1`` profiles two slices.  The first runs the profiler
without its Python tracer, which slows the host several times over at ten
thousand requests a second: busy time, the device ops and the roofline come
from it.  The second turns the Python tracer on, so that the host's side of
every idle gap has a name: the idle gaps come from it, and are as long as
they are under that tracer.  Each slice is marked by a ``TraceAnnotation``
(``SLICE``), which gives its bounds on the trace's own clock.

* busy: the union of the intervals in which an operation ran on a device
  (its ``XLA Ops`` line), inside the slice, averaged over the chips used;
* device_ops: the operations that took most device time in the slice;
* idle_gaps: the longest stretches with no device operation, each named by
  what the serving thread (the Python line that holds the server's
  ``_process`` frames) was doing at the gap's midpoint: its innermost frame
  in ``engine.py`` and its innermost frame.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import shutil
import tempfile
import threading
import time

import numpy as np

SLICE = "bench_slice"
OPS_LINE = "XLA Ops"
TOP = 10


@dataclasses.dataclass
class Trace:
    """planes -> lines -> events (name, start_ns, duration_ns)."""

    planes: dict

    @classmethod
    def from_xplane(cls, path: str) -> "Trace":
        import jax
        pd = jax.profiler.ProfileData.from_file(path)
        planes = {}
        for pl in pd.planes:
            lines = {}
            for i, ln in enumerate(pl.lines):
                key = ln.name if ln.name not in lines else f"{ln.name}#{i}"
                lines[key] = [(e.name, e.start_ns, e.duration_ns)
                              for e in ln.events]
            planes[pl.name] = lines
        return cls(planes)

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as fh:
            return cls(json.load(fh))


@dataclasses.dataclass
class Summary:
    busy_s: float
    window_s: float
    device_ops: list
    idle_gaps: list

    @property
    def idle_share(self):
        """None where the trace holds no device plane (a run off the
        chip)."""
        if self.busy_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (start, end) intervals; returns them sorted and disjoint."""
    if iv.size == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def _slice_bounds(trace: Trace) -> tuple[float, float]:
    for lines in trace.planes.values():
        for events in lines.values():
            for name, start, dur in events:
                if name == SLICE:
                    return float(start), float(start + dur)
    raise ValueError(f"no {SLICE!r} event in the trace")


def _op_name(hlo: str) -> str:
    return hlo.split(" = ", 1)[0].lstrip("%")


def _serving_line(trace: Trace):
    best, best_t = None, 0.0
    for lines in trace.planes.values():
        for events in lines.values():
            t = sum(d for n, _, d in events if n.endswith(" _process"))
            if t > best_t:
                best, best_t = events, t
    return best


def _host_name(events, t: float) -> str:
    """What the serving thread was doing at time ``t``: its innermost frame
    of the server (``engine.py``) and its innermost frame overall."""
    cover = sorted((d, n.lstrip("$")) for n, s, d in events
                   if s <= t <= s + d)
    if not cover:
        return "no host frame"
    inner = cover[0][1]
    server = next((n for _, n in cover if n.startswith("engine.py")), None)
    return inner if server in (None, inner) else f"{server} > {inner}"


def reduce(trace: Trace, chips: int) -> Summary:
    lo, hi = _slice_bounds(trace)
    busy, ops = [], {}
    gaps_chip0 = None
    for c in range(chips):
        events = trace.planes.get(f"/device:TPU:{c}", {}).get(OPS_LINE, [])
        iv = []
        for name, start, dur in events:
            s, e = max(start, lo), min(start + dur, hi)
            if e > s:
                iv.append((s, e))
                ops[_op_name(name)] = ops.get(_op_name(name), 0.0) + (e - s)
        u = _union(np.asarray(iv, np.float64).reshape(-1, 2))
        busy.append(float((u[:, 1] - u[:, 0]).sum()) if u.size else 0.0)
        if c == 0:
            edges = np.concatenate([[lo], u.ravel(), [hi]]).reshape(-1, 2)
            gaps_chip0 = edges[edges[:, 1] > edges[:, 0]]
    longest = gaps_chip0[np.argsort(gaps_chip0[:, 0] - gaps_chip0[:, 1])]
    serving = _serving_line(trace) or []
    idle = [[_host_name(serving, (s + e) / 2), float(e - s) / 1e9]
            for s, e in longest[:TOP]]
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    return Summary(busy_s=float(np.mean(busy)) / 1e9,
                   window_s=(hi - lo) / 1e9,
                   device_ops=[[n, t / 1e9] for n, t in top_ops],
                   idle_gaps=idle)


class SliceProfiler:
    """Profiles ``length`` seconds starting ``offset`` seconds into the
    window, from a thread of its own, into a temporary directory; with the
    Python tracer where ``python``."""

    def __init__(self, offset: float, length: float, python: bool) -> None:
        self.offset, self.length, self.python = offset, length, python
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.host_bounds = (np.nan, np.nan)
        self._thread = None
        self.error = None

    def arm(self, t0: float) -> None:
        self._thread = threading.Thread(target=self._run, args=(t0,),
                                        name="bench-profiler", daemon=True)
        self._thread.start()

    def _run(self, t0: float) -> None:
        import jax
        try:
            time.sleep(max(0.0, t0 + self.offset - time.perf_counter()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = int(self.python)
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(SLICE):
                    a = time.perf_counter()
                    time.sleep(self.length)
                    self.host_bounds = (a, time.perf_counter())
            finally:
                jax.profiler.stop_trace()
        except Exception as e:      # reported by summary(), never swallowed
            self.error = e

    def load(self) -> Trace:
        """Wait for the slice to end; read its trace and delete the file."""
        self._thread.join()
        try:
            if self.error is not None:
                raise RuntimeError("profiling the slice failed") \
                    from self.error
            path, = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            return Trace.from_xplane(path)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
