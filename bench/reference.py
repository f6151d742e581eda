"""Plain reference for the answers a served decision tree or forest gives.

Written from the semantics of DT2CAM (arXiv 2204.06114, section II) and from
the tree arrays alone; it imports nothing of the program and reads none of
the tables the program builds (rule table, LUT, cell grid, placed arrays).

One tree is one TCAM: a row per leaf, in left-to-right order.  Each feature
with T unique thresholds (over all leaves' intervals) takes T + 1 unary
columns; a value falls in range k = 1 + #{thresholds < v} and searches as k
trailing ones.  A leaf's interval (lo, hi] spans ranges [lb, ub]: its code is
0 left of column n - ub, 1 from column n - lb, don't-care between.  Column 0
is a decoder bit; the code follows in feature order, and the columns split
into divisions of S.  Rows past the leaves, up to a multiple of S, never
match, and are dropped in the first division.  Division by division a row
stays active until it mismatches (selective precharge); a request's active
evaluations are the (row, division) pairs evaluated, and its energy is
``active * E_ROW + banks * E_MEM`` in float64.

``answers`` computes what a correct server returns.  ``control`` computes the
same in float32 (energy, and the forest's soft vote): the nearest precision
below the float64 the deployment states, which the check must refuse.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

# Energy per active row evaluation (Eqn 7: eta * C_in * V_dd^2 + E_sa) and
# per class read, from the paper's 16 nm calibration.
E_ROW = 0.90 * 50e-15 * 1.0 ** 2 + 2.4e-15
E_MEM = 5.0e-15

FIELDS = ("prediction", "survivor", "n_survivors", "active_evals", "energy_j")
_BLOCK = 256         # requests per vectorized block: bounds (B, R) arrays


@dataclasses.dataclass
class TreeTCAM:
    """One tree laid out as a TCAM, from its node arrays."""

    lb: np.ndarray           # (R, F) first range each leaf accepts (1-based)
    ub: np.ndarray           # (R, F) last range it accepts
    thresholds: list         # per feature, sorted unique thresholds
    offsets: np.ndarray      # (F + 1,) first column of each feature's code
    s: int

    @classmethod
    def from_arrays(cls, feature, threshold, left, right, n_features: int,
                    s: int) -> "TreeTCAM":
        """``feature < 0`` marks a leaf; ``x[f] <= threshold`` goes left."""
        lo_rows, hi_rows = [], []
        stack = [(0, np.full(n_features, -np.inf),
                  np.full(n_features, np.inf))]
        while stack:                     # depth first, left child first
            node, lo, hi = stack.pop()
            f = int(feature[node])
            if f < 0:
                lo_rows.append(lo)
                hi_rows.append(hi)
                continue
            t = float(threshold[node])
            lo_r, hi_l = lo.copy(), hi.copy()
            hi_l[f] = min(hi_l[f], t)
            lo_r[f] = max(lo_r[f], t)
            stack.append((int(right[node]), lo_r, hi))
            stack.append((int(left[node]), lo, hi_l))
        lo, hi = np.array(lo_rows), np.array(hi_rows)
        ths = []
        lb = np.ones(lo.shape, np.int64)
        ub = np.ones(lo.shape, np.int64)
        for j in range(n_features):
            v = np.concatenate([lo[:, j], hi[:, j]])
            th = np.unique(v[np.isfinite(v)])
            ths.append(th)
            fin_lo, fin_hi = np.isfinite(lo[:, j]), np.isfinite(hi[:, j])
            lb[:, j] = np.where(fin_lo, 2 + np.searchsorted(th, lo[:, j]), 1)
            ub[:, j] = np.where(fin_hi, 1 + np.searchsorted(th, hi[:, j]),
                                th.size + 1)
        widths = np.array([t.size + 1 for t in ths], np.int64)
        return cls(lb=lb, ub=ub, thresholds=ths,
                   offsets=np.concatenate([[0], np.cumsum(widths)]), s=s)

    @property
    def rows(self) -> int:
        return int(self.lb.shape[0])

    @property
    def cols(self) -> int:
        """Real columns: the decoder bit and every feature's code."""
        return 1 + int(self.offsets[-1])

    @property
    def divisions(self) -> int:
        return math.ceil(self.cols / self.s)

    @property
    def spare_rows(self) -> int:
        return math.ceil(self.rows / self.s) * self.s - self.rows

    def search(self, X: np.ndarray) -> tuple[np.ndarray, ...]:
        """(survivor, n_survivors, active_evals) per row of X."""
        k = np.stack([1 + np.searchsorted(th, X[:, j], side="left")
                      for j, th in enumerate(self.thresholds)], axis=1)
        # one past each feature's last column: a range k above a row's ub
        # first mismatches at column end - k, one below its lb at end - lb
        end = 1 + self.offsets[1:]
        d = self.divisions
        lb, ub = self.lb.T.astype(np.int16), self.ub.T.astype(np.int16)
        div_lo = ((end - self.lb) // self.s).T.astype(np.int16)  # (F, R)
        surv, nsurv, active = [], [], []
        for lo in range(0, X.shape[0], _BLOCK):
            kb = k[lo:lo + _BLOCK].astype(np.int16)
            div_hi = ((end - kb) // self.s).astype(np.int16)      # (B, F)
            first = np.full((kb.shape[0], self.rows), d, np.int16)
            for j in range(kb.shape[1]):     # first mismatching division
                kj = kb[:, j:j + 1]
                np.minimum(first, np.where(kj > ub[j], div_hi[:, j:j + 1],
                                           np.where(kj < lb[j], div_lo[j],
                                                    d)),
                           out=first)
            match = first == d
            ns = match.sum(axis=1)
            surv.append(np.where(ns > 0, np.argmax(match, axis=1), -1))
            nsurv.append(ns)
            active.append(np.minimum(first + 1, d).sum(axis=1, dtype=np.int64)
                          + self.spare_rows)
        return (np.concatenate(surv), np.concatenate(nsurv),
                np.concatenate(active))


def walk(feature, threshold, left, right, X: np.ndarray) -> np.ndarray:
    """Leaf node reached by each row of X."""
    node = np.zeros(X.shape[0], np.int64)
    while True:
        f = feature[node]
        inner = f >= 0
        if not inner.any():
            return node
        go_left = X[np.arange(X.shape[0]), np.maximum(f, 0)] \
            <= threshold[node]
        node = np.where(inner, np.where(go_left, left[node], right[node]),
                        node)


def energy(active: np.ndarray, banks: int, dtype=np.float64) -> np.ndarray:
    e = active.astype(dtype) * dtype(E_ROW) + dtype(banks * E_MEM)
    return e.astype(np.float64)


class TreeReference:
    """A single tree served as one TCAM."""

    def __init__(self, tree: dict, s: int) -> None:
        self.tree = tree
        self.tcam = TreeTCAM.from_arrays(
            tree["feature"], tree["threshold"], tree["left"], tree["right"],
            int(tree["n_features"]), s)

    @property
    def banks(self) -> list[TreeTCAM]:
        return [self.tcam]

    def answers(self, X: np.ndarray, dtype=np.float64) -> dict:
        t = self.tree
        leaf = walk(t["feature"], t["threshold"], t["left"], t["right"], X)
        surv, nsurv, active = self.tcam.search(X)
        return {"prediction": t["value"][leaf].astype(np.int64),
                "survivor": surv, "n_survivors": nsurv,
                "active_evals": active,
                "energy_j": energy(active, 1, dtype)}

    def control(self, X: np.ndarray) -> dict:
        return self.answers(X, np.float32)


class ForestReference:
    """A fitted scikit-learn random forest, one TCAM bank per estimator,
    soft vote as ``RandomForestClassifier.predict`` takes it."""

    def __init__(self, model, s: int) -> None:
        self.model = model
        self.tcams = []
        for est in model.estimators_:
            t = est.tree_
            self.tcams.append(TreeTCAM.from_arrays(
                t.feature, t.threshold, t.children_left, t.children_right,
                int(t.n_features), s))

    @property
    def banks(self) -> list[TreeTCAM]:
        return self.tcams

    def _vote(self, X: np.ndarray, dtype) -> np.ndarray:
        """Leaf class probabilities summed in estimator order, divided by
        the number of trees; ties go to the lower class."""
        acc = np.zeros((X.shape[0], self.model.n_classes_), dtype)
        for est in self.model.estimators_:
            t = est.tree_
            value = t.value[:, 0, :].astype(np.float64)
            norm = value.sum(axis=1, keepdims=True)
            norm[norm == 0.0] = 1.0
            proba = (value / norm).astype(dtype)
            acc += proba[walk(t.feature, t.threshold, t.children_left,
                              t.children_right, X)]
        acc /= dtype(len(self.model.estimators_))
        return self.model.classes_[np.argmax(acc, axis=1)]

    def answers(self, X: np.ndarray, dtype=np.float64) -> dict:
        # the forest reads its inputs as float32, as scikit-learn does
        X = X.astype(np.float32).astype(np.float64)
        nsurv = np.zeros(X.shape[0], np.int64)
        active = np.zeros(X.shape[0], np.int64)
        for tcam in self.tcams:
            _, ns, ac = tcam.search(X)
            nsurv += ns > 0
            active += ac
        pred = (self.model.predict(X) if dtype is np.float64
                else self._vote(X, dtype))
        return {"prediction": pred.astype(np.int64),
                "survivor": np.full(X.shape[0], -1, np.int64),
                "n_survivors": nsurv, "active_evals": active,
                "energy_j": energy(active, len(self.tcams), dtype)}

    def control(self, X: np.ndarray) -> dict:
        return self.answers(X, np.float32)
