"""Decides ``correct``: the answers of the timed path against the reference.

Every request sent in the window must be answered (``unanswered``).  A
sample of them, drawn from the seed once the window has closed, is
recomputed by the plain reference and compared field by field, exactly: each
``wrong_<field>`` counts sampled requests whose field differs.  An exact
comparison has the limit 0.
"""
from __future__ import annotations

import numpy as np

from reference import FIELDS

LIMITS = {"unanswered": 0, **{f"wrong_{f}": 0 for f in FIELDS}}


def sample(n: int, size: int, seed: int) -> np.ndarray:
    """``size`` request indices of ``n``, drawn from the seed; all where
    there are fewer."""
    if n <= size:
        return np.arange(n)
    rng = np.random.default_rng([seed, 2])
    return np.sort(rng.choice(n, size=size, replace=False))


def run_check(rec: np.ndarray, reference, X: np.ndarray, size: int,
              seed: int, answers=None) -> dict:
    """Check numbers for one window's records.  ``answers`` (rows -> fields)
    stands in for what the program served: the control does that."""
    idx = sample(rec.size, size, seed)
    idx = idx[rec["ok"][idx]]
    Xs = X[rec["row"][idx]]
    want = reference.answers(Xs)
    got = ({f: rec[f][idx] for f in FIELDS} if answers is None
           else answers(Xs))
    wrong = {f"wrong_{f}": int(np.sum(np.asarray(got[f]) != want[f]))
             for f in FIELDS}
    return {"checked": int(idx.size), "unanswered": int((~rec["ok"]).sum()),
            **wrong}


def verdict(numbers: dict) -> bool:
    return numbers["checked"] > 0 and all(
        numbers[k] <= lim for k, lim in LIMITS.items())
