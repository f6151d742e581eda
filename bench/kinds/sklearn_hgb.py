"""A scikit-learn ``HistGradientBoostingClassifier``, one TCAM bank per
(iteration, class) predictor, served in the program's forest mode."""
from __future__ import annotations

import hashlib

import numpy as np

from reference_boosted import BoostedReference

_NODE_FIELDS = ("feature_idx", "num_threshold", "left", "right", "is_leaf",
                "is_categorical", "value")


def trainer_digest() -> str:
    import sklearn
    return "sklearn " + sklearn.__version__


def compiler_digest() -> str:
    import repro.core
    import repro.forest
    from deploy import source_digest
    return source_digest(repro.core, repro.forest)


def fit(config: dict, X: np.ndarray, y: np.ndarray):
    """Fitted on one OpenMP thread, so that no host's core count can move
    a sum of gradients and with it the digest."""
    from sklearn.ensemble import HistGradientBoostingClassifier
    from threadpoolctl import threadpool_limits
    with threadpool_limits(limits=1, user_api="openmp"):
        return HistGradientBoostingClassifier(**config["fit"]).fit(X, y)


def digest(model) -> str:
    h = hashlib.sha256()
    for it in model._predictors:
        for p in it:
            for f in _NODE_FIELDS:
                h.update(np.ascontiguousarray(p.nodes[f]).tobytes())
    h.update(np.asarray(model._baseline_prediction, np.float64).tobytes())
    h.update(np.asarray(model.classes_, np.int64).tobytes())
    return h.hexdigest()[:16]


def compile(model, config: dict):
    import repro
    return repro.compile_forest(model, s=config["s"])


def reference(model, config: dict) -> BoostedReference:
    return BoostedReference(model, config["s"])
