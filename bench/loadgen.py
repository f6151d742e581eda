"""The one load generator: reads a traffic mix's parameters and drives
``submit`` with them from the calling thread.

A mix is ``bench/traffic/<mix>.json``:

* ``{"loop": "open", "arrivals": "poisson", "rate_per_s": r}``: independent
  users.  Every seed gets the same set of exponential gaps (drawn once from
  ``gap_seed``, scaled to fill the window exactly), in its own order, so the
  count and spread of arrivals do not move with the seed.  Latency runs from
  the time a request was due, so a late generator counts against it.
* ``{"loop": "closed", "in_flight": k}``: callers that wait; a resolved
  request is replaced at once by the next row of the seeded stream.

Rows are drawn uniformly from the deployment's test split by the seed.

A Future's done-callback runs on the server's thread, so it only queues the
Future; the generator's thread reads the answers into flat arrays between
submits.  No Future outlives its reading.  A waiting generator blocks on an
event, so it never spins on the interpreter lock the server needs.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time

import numpy as np

GRACE_S = 60.0        # how long past the window's close an answer is awaited
_CHUNK = 1 << 16

RECORD = np.dtype([
    ("due", "f8"), ("submit", "f8"), ("done", "f8"), ("row", "i8"),
    ("ok", "?"), ("prediction", "i8"), ("survivor", "i8"),
    ("n_survivors", "i8"), ("active_evals", "i8"), ("energy_j", "f8"),
    ("queue_s", "f8"), ("compute_s", "f8"), ("bucket", "i8"),
])
_ANSWER = ("prediction", "survivor", "n_survivors", "active_evals",
           "energy_j", "queue_s", "compute_s", "bucket")


@dataclasses.dataclass
class Window:
    """What one measured window sent and got back (host clock, seconds):
    one ``RECORD`` per request sent, ``done`` nan where none came back."""

    loop: str
    seconds: float
    t0: float
    rec: np.ndarray

    @property
    def t_end(self) -> float:
        return self.t0 + self.seconds

    def latency_s(self) -> np.ndarray:
        """Due to resolved; a request that failed or never came is +inf."""
        return np.where(self.rec["ok"], self.rec["done"] - self.rec["due"],
                        np.inf)


def batches(rec: np.ndarray) -> tuple[np.ndarray, ...]:
    """The answered requests grouped into the batches that served them (one
    ``compute_s`` and bucket per batch): (requests, start, done) per batch,
    a batch running from its results' time less its compute seconds."""
    rec = rec[rec["ok"]]
    key = np.stack([rec["compute_s"], rec["bucket"].astype(np.float64)], 1)
    _, inv, counts = np.unique(key, axis=0, return_inverse=True,
                               return_counts=True)
    inv = inv.ravel()
    done = np.full(counts.size, -np.inf)
    np.maximum.at(done, inv, rec["done"])
    compute = np.zeros(counts.size)
    compute[inv] = rec["compute_s"]
    return counts, done - compute, done


def overlap(start: np.ndarray, done: np.ndarray, a: float, b: float
            ) -> np.ndarray:
    """Share of each [start, done] interval that lies inside [a, b]."""
    inside = np.clip(np.minimum(done, b) - np.maximum(start, a), 0, None)
    return inside / np.maximum(done - start, 1e-12)


def percentile(x, q: float) -> float:
    """Nearest-rank percentile (q in 0..100); +inf entries sort last."""
    x = np.sort(np.asarray(x, np.float64))
    if x.size == 0:
        return float("nan")
    return float(x[max(0, int(np.ceil(q / 100.0 * x.size)) - 1)])


def open_schedule(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds from the window's start) of an open-loop mix."""
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    n = max(1, round(traffic["rate_per_s"] * seconds))
    gaps = np.random.default_rng(traffic.get("gap_seed", 0)).exponential(
        1.0, n)
    gaps *= seconds / gaps.sum()
    gaps = np.random.default_rng(seed).permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


class _Recorder:
    """Per-request records in fixed chunks (a chunk never moves, so a
    record's slot is fixed when it is sent)."""

    def __init__(self, clock) -> None:
        self.clock = clock
        self.chunks: list[np.ndarray] = []
        self.n = 0
        self.n_done = 0
        self.resolved = collections.deque()   # (index, time, Future)
        self.wake = threading.Event()

    def sent(self, due: float, submit: float, row: int) -> int:
        i = self.n
        if i % _CHUNK == 0:
            c = np.zeros(_CHUNK, RECORD)
            c["done"] = np.nan
            self.chunks.append(c)
        r = self.chunks[-1][i % _CHUNK]
        r["due"], r["submit"], r["row"] = due, submit, row
        self.n += 1
        return i

    def on_done(self, i: int):
        def cb(fut, i=i):
            self.resolved.append((i, self.clock(), fut))
            self.wake.set()
        return cb

    def wait(self, timeout: float) -> bool:
        """Block until an answer may have come (True) or ``timeout``."""
        woke = self.wake.wait(timeout)
        self.wake.clear()
        return woke

    def read(self) -> int:
        """Read every queued answer into its record; returns how many."""
        k = 0
        while self.resolved:
            i, t, fut = self.resolved.popleft()
            r = self.chunks[i // _CHUNK][i % _CHUNK]
            r["done"] = t
            if fut.exception() is None:
                res = fut.result()
                r["ok"] = True
                for f in _ANSWER:
                    r[f] = getattr(res, f)
            k += 1
        self.n_done += k
        return k

    def records(self) -> np.ndarray:
        if not self.chunks:
            return np.zeros(0, RECORD)
        return np.concatenate(self.chunks)[: self.n]


def drive(submit, traffic: dict, X: np.ndarray, seconds: float, seed: int,
          on_start=None, clock=time.perf_counter) -> Window:
    """Run one window of ``traffic`` against ``submit`` (row -> Future).
    ``on_start(t0)`` is called as the window opens."""
    rows_rng = np.random.default_rng([seed, 1])
    rec = _Recorder(clock)

    def send(due: float, row: int) -> None:
        now = clock()
        i = rec.sent(due, now, row)
        submit(X[row]).add_done_callback(rec.on_done(i))

    if traffic["loop"] == "open":
        offs = open_schedule(traffic, seconds, seed)
        rows = rows_rng.integers(0, len(X), offs.size)
        t0 = clock()
        if on_start:
            on_start(t0)
        due = t0 + offs
        i, n = 0, offs.size
        while i < n:
            now = clock()
            while i < n and due[i] <= now:
                send(due[i], int(rows[i]))
                i += 1
            rec.read()
            wait = due[i] - clock() if i < n else 0.0
            if wait > 0:
                time.sleep(wait)
    elif traffic["loop"] == "closed":
        t0 = clock()
        if on_start:
            on_start(t0)
        t_end = t0 + seconds
        stream = iter(())

        def next_row() -> int:
            nonlocal stream
            r = next(stream, None)
            if r is None:
                stream = iter(rows_rng.integers(0, len(X), _CHUNK).tolist())
                r = next(stream)
            return r

        for _ in range(traffic["in_flight"]):
            send(clock(), next_row())
        while True:
            left = t_end - clock()
            if left <= 0:
                break
            if rec.wait(left):
                for _ in range(rec.read()):
                    if clock() < t_end:
                        send(clock(), next_row())
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")

    give_up = t0 + seconds + GRACE_S
    while rec.n_done < rec.n:
        left = give_up - clock()
        if left <= 0:
            break
        rec.wait(left)
        rec.read()
    rec.read()
    return Window(loop=traffic["loop"], seconds=float(seconds), t0=t0,
                  rec=rec.records())
