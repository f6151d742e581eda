"""Plain reference for a served scikit-learn ``HistGradientBoostingClassifier``.

Read from the fitted predictors' node arrays alone (``_predictors[i][k]
.nodes``: ``feature_idx``, ``num_threshold``, ``left``, ``right``,
``is_leaf``, ``value``), the model's baseline and ``classes_``; it imports
nothing of the program.  Each (iteration, class) predictor is one TCAM
bank, laid out by ``reference.TreeTCAM`` as a single tree is.  A request's
margin is the baseline plus each bank's leaf value, added to the bank's
class column in iteration order in float64; binary takes class 1 where the
margin is strictly positive, multiclass the argmax.  ``answers`` takes the
prediction from ``predict``, the survivor counts and active evaluations
from the banks, and the energy as ``reference.energy`` gives it.
``control`` sums the leaves and the energy in float32, the nearest
precision below the float64 the deployment states, which the check must
refuse.
"""
from __future__ import annotations

import numpy as np

from reference import FIELDS, TreeTCAM, energy, walk

__all__ = ["BoostedReference", "FIELDS"]


def _arrays(nodes) -> tuple[np.ndarray, ...]:
    """(feature, threshold, left, right) with ``feature = -1`` at leaves."""
    leaf = nodes["is_leaf"].astype(bool)
    return (np.where(leaf, -1, nodes["feature_idx"]).astype(np.int64),
            nodes["num_threshold"].astype(np.float64),
            nodes["left"].astype(np.int64), nodes["right"].astype(np.int64))


class BoostedReference:
    """A fitted histogram gradient-boosting classifier, one TCAM bank per
    (iteration, class) predictor, decided on its summed margin."""

    def __init__(self, model, s: int) -> None:
        self.model = model
        n_features = int(model.n_features_in_)
        self.predictors = [(k, p.nodes) for it in model._predictors
                           for k, p in enumerate(it)]
        self.tcams = [TreeTCAM.from_arrays(*_arrays(nodes), n_features, s)
                      for _, nodes in self.predictors]

    @property
    def banks(self) -> list[TreeTCAM]:
        return self.tcams

    def margin(self, X: np.ndarray, dtype=np.float64) -> np.ndarray:
        """(n, K) baseline plus leaf values, summed in iteration order."""
        base = np.asarray(self.model._baseline_prediction).reshape(-1)
        acc = np.zeros((X.shape[0], base.size), dtype) + base.astype(dtype)
        for k, nodes in self.predictors:
            leaf = walk(*_arrays(nodes), X)
            acc[:, k] += nodes["value"][leaf].astype(dtype)
        return acc

    def decide(self, margin: np.ndarray) -> np.ndarray:
        idx = ((margin[:, 0] > 0).astype(np.int64) if margin.shape[1] == 1
               else np.argmax(margin, axis=1))
        return self.model.classes_[idx]

    def answers(self, X: np.ndarray, dtype=np.float64) -> dict:
        nsurv = np.zeros(X.shape[0], np.int64)
        active = np.zeros(X.shape[0], np.int64)
        for tcam in self.tcams:
            _, ns, ac = tcam.search(X)
            nsurv += ns > 0
            active += ac
        pred = (self.model.predict(X) if dtype is np.float64
                else self.decide(self.margin(X, dtype)))
        return {"prediction": pred.astype(np.int64),
                "survivor": np.full(X.shape[0], -1, np.int64),
                "n_survivors": nsurv, "active_evals": active,
                "energy_j": energy(active, len(self.tcams), dtype)}

    def control(self, X: np.ndarray) -> dict:
        return self.answers(X, np.float32)
