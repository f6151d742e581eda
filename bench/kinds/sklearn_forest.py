"""A scikit-learn ``RandomForestClassifier``, one TCAM bank per estimator,
served in the program's forest mode."""
from __future__ import annotations

import hashlib

import numpy as np

from reference import ForestReference


def trainer_digest() -> str:
    import sklearn
    return "sklearn " + sklearn.__version__


def compiler_digest() -> str:
    import repro.core
    import repro.forest
    from deploy import source_digest
    return source_digest(repro.core, repro.forest)


def fit(config: dict, X: np.ndarray, y: np.ndarray):
    from sklearn.ensemble import RandomForestClassifier
    return RandomForestClassifier(**config["fit"]).fit(X, y)


def digest(model) -> str:
    h = hashlib.sha256()
    for est in model.estimators_:
        t = est.tree_
        for a, dt in ((t.feature, np.int64), (t.threshold, np.float64),
                      (t.children_left, np.int64),
                      (t.children_right, np.int64), (t.value, np.float64)):
            h.update(np.ascontiguousarray(a, dt).tobytes())
    h.update(np.asarray(model.classes_, np.int64).tobytes())
    return h.hexdigest()[:16]


def compile(model, config: dict):
    import repro
    return repro.compile_forest(model, s=config["s"])


def reference(model, config: dict) -> ForestReference:
    return ForestReference(model, config["s"])
