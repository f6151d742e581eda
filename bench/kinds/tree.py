"""One CART tree fitted by the program's trainer, compiled to one TCAM."""
from __future__ import annotations

import hashlib

import numpy as np

from reference import TreeReference

_KEYS = ("feature", "threshold", "left", "right", "value")
_DTYPES = (np.int32, np.float64, np.int32, np.int32, np.int32)


def trainer_digest() -> str:
    import repro.core.cart
    from deploy import source_digest
    return source_digest(repro.core.cart)


def compiler_digest() -> str:
    import repro.core
    from deploy import source_digest
    return source_digest(repro.core)


def fit(config: dict, X: np.ndarray, y: np.ndarray) -> dict:
    """The tree as plain arrays (node i: ``x[feature] <= threshold`` goes
    to ``left``; ``feature == -1`` is a leaf of class ``value``)."""
    import repro
    t = repro.train_tree(X, y, **config["fit"])
    out = {k: np.asarray(getattr(t, k), dt) for k, dt in zip(_KEYS, _DTYPES)}
    out.update(n_features=int(t.n_features), n_classes=int(t.n_classes))
    return out


def digest(tree: dict) -> str:
    h = hashlib.sha256()
    for k, dt in zip(_KEYS, _DTYPES):
        h.update(np.ascontiguousarray(tree[k], dt).tobytes())
    h.update(repr((tree["n_features"], tree["n_classes"])).encode())
    return h.hexdigest()[:16]


def compile(tree: dict, config: dict):
    import repro
    t = repro.DecisionTree(*(tree[k] for k in _KEYS), tree["n_features"],
                           tree["n_classes"])
    return repro.compile_tree(t, config["s"])


def reference(tree: dict, config: dict) -> TreeReference:
    return TreeReference(tree, config["s"])
