"""The benchmark's own data: the seeded synthetic generators of the two
Table II datasets its deployments run on, and the fixed 90/10 split.

A copy, so that no change to the program can change the rows a cell serves:
``bench/tests/test_data.py`` holds it equal to the program's generator as of
the benchmark's first version.  Each generator plants an axis-aligned rule
tree in uniform (quantized) features and flips a share of the labels.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

# Table II shapes (#instances, #features, #classes) and generator settings
SPECS = {
    "credit": dict(n=120269, f=10, c=2, planted_depth=12, label_noise=0.12,
                   seed=15, quantize=400),
    "covid": dict(n=33599, f=4, c=2, planted_depth=9, label_noise=0.015,
                  seed=17, quantize=40),
}


def _planted_tree_labels(X: np.ndarray, n_classes: int, depth: int,
                         rng: np.random.Generator) -> np.ndarray:
    """Label points by a random planted tree: split on a uniform feature at a
    random quantile of the points that reach the node; random leaf class."""
    y = np.zeros(X.shape[0], dtype=np.int64)

    def rec(idx: np.ndarray, d: int) -> None:
        if d == 0 or idx.size < 8:
            y[idx] = rng.integers(0, n_classes)
            return
        f = int(rng.integers(0, X.shape[1]))
        q = float(rng.uniform(0.25, 0.75))
        thr = np.quantile(X[idx, f], q)
        mask = X[idx, f] <= thr
        if mask.all() or not mask.any():
            y[idx] = rng.integers(0, n_classes)
            return
        rec(idx[mask], d - 1)
        rec(idx[~mask], d - 1)

    rec(np.arange(X.shape[0]), depth)
    return y


def _synthetic(n: int, f: int, c: int, *, planted_depth: int,
               label_noise: float, seed: int,
               quantize: Optional[int] = None
               ) -> tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(seed)
    X = rng.uniform(0.0, 1.0, size=(n, f))
    if quantize:
        X = np.floor(X * quantize)
    y = _planted_tree_labels(X, c, planted_depth, rng)
    flip = rng.random(n) < label_noise
    y[flip] = rng.integers(0, c, size=int(flip.sum()))
    return X, y


def load_split(name: str) -> tuple[np.ndarray, ...]:
    """(X_train, y_train, X_test, y_test): min-max normalized over the whole
    set, then a fixed shuffle (seed 0) and a 90/10 split."""
    X, y = _synthetic(**SPECS[name])
    lo, hi = X.min(axis=0), X.max(axis=0)
    X = (X - lo) / np.maximum(hi - lo, 1e-12)
    perm = np.random.default_rng(0).permutation(X.shape[0])
    n_tr = int(round(0.9 * X.shape[0]))
    tr, te = perm[:n_tr], perm[n_tr:]
    return X[tr], y[tr], X[te], y[te]
