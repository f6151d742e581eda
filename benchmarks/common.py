"""Shared benchmark infrastructure: dataset -> fitted/compiled DT2CAM with
on-disk tree caching (Credit takes ~10s to fit; cache under artifacts/,
keyed on the training data, the fit parameters and the trainer's source),
plus the seeding / artifact-writing conventions every benchmark follows:
a ``--seed`` flag (``add_seed_arg``) and a JSON artifact whose content is
fully seed-determined — wall-clock numbers go to stdout, never into the
file (``write_artifact``), so same flags + same seed => byte-identical
artifact."""
from __future__ import annotations

import hashlib
import inspect
import json
import os
import time

import numpy as np

from repro.core import DT2CAM, DecisionTree, compile_tree, train_tree
from repro.dt import DATASETS, load_split

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts")
TREES = os.path.join(ART, "trees")

__all__ = ["fitted_tree", "compiled", "ART", "emit", "add_seed_arg",
           "write_artifact"]


def add_seed_arg(ap, default: int = 0) -> None:
    """The shared ``--seed`` flag: one integer seeding every RNG the
    benchmark touches, making the artifact JSON reproducible."""
    ap.add_argument(
        "--seed", type=int, default=default,
        help="RNG seed; same flags + same seed -> byte-identical artifact",
    )


def write_artifact(path: str, report) -> None:
    """Write a benchmark report as indented JSON (CI artifact).  Callers
    must keep wall-clock-dependent values out of ``report`` — print those
    to stdout instead — so the artifact stays seed-deterministic."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {path}")


def _fit_key(spec, Xtr: np.ndarray, ytr: np.ndarray) -> str:
    """Digest of everything that produces the tree: the training split, the
    fit parameters and the trainer's source."""
    h = hashlib.sha1(np.ascontiguousarray(Xtr).tobytes())
    h.update(np.ascontiguousarray(ytr).tobytes())
    h.update(repr((spec.max_depth, spec.max_leaves,
                   spec.min_samples_leaf)).encode())
    h.update(inspect.getsource(inspect.getmodule(train_tree)).encode())
    return h.hexdigest()[:12]


def fitted_tree(name: str) -> tuple[DecisionTree, tuple]:
    spec = DATASETS[name]
    os.makedirs(TREES, exist_ok=True)
    Xtr, ytr, Xte, yte = load_split(name)
    path = os.path.join(TREES, f"{name}-{_fit_key(spec, Xtr, ytr)}.npz")
    if os.path.exists(path):
        z = np.load(path)
        tree = DecisionTree(z["feature"], z["threshold"], z["left"],
                            z["right"], z["value"], int(z["n_features"]),
                            int(z["n_classes"]))
    else:
        tree = train_tree(Xtr, ytr, max_depth=spec.max_depth,
                          max_leaves=spec.max_leaves,
                          min_samples_leaf=spec.min_samples_leaf)
        np.savez(path, feature=tree.feature, threshold=tree.threshold,
                 left=tree.left, right=tree.right, value=tree.value,
                 n_features=tree.n_features, n_classes=tree.n_classes)
    return tree, (Xtr, ytr, Xte, yte)


def compiled(name: str, s: int):
    tree, data = fitted_tree(name)
    return compile_tree(tree, s), data


def emit(rows: list[dict], name: str) -> None:
    """Print a benchmark table as CSV (name,key=value CSV convention)."""
    if not rows:
        return
    keys = list(rows[0].keys())
    print(f"### {name}")
    print(",".join(keys))
    for r in rows:
        print(",".join(str(r[k]) for k in keys))
    print()
