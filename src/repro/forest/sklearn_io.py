"""sklearn interop: lossless import of fitted sklearn trees/forests.

Converts ``sklearn.tree._tree.Tree`` flat arrays into the repo's
``DecisionTree`` (same split semantics: ``x[f] <= threshold`` goes left) and
extracts the per-leaf class-probability tables needed to reproduce
``RandomForestClassifier.predict`` *bit-exactly*:

* leaf probabilities replicate ``DecisionTreeClassifier.predict_proba``:
  a tree that stores class fractions (sklearn >= 1.4) gives them as they
  are, as sklearn 1.9's ``predict_proba`` does; one that stores counts is
  normalized with the old quirk (rows summing to zero divide by 1);
* probabilities are indexed by LUT row via ``tree_leaf_ids`` (both the rule
  table and the DFS leaf walk enumerate leaves left-to-right);
* sklearn casts inputs to float32 inside ``predict`` — the importer records
  that so the forest front door applies the same cast before encoding.

A fitted ``HistGradientBoostingClassifier`` imports predictor by predictor
(``model._predictors[i][k]``): each becomes a ``DecisionTree`` (float64
``num_threshold``, ``x <= t`` goes left, root at node 0) plus its leaf
values in LUT-row order.  HGB reads its inputs as float64, so no cast
applies.  Categorical splits and splits that route NaN alone (an infinite
threshold) have no one-row-per-leaf thermometer code and are refused.

Everything here is numpy-only and degrades gracefully: when sklearn is not
installed, ``is_sklearn_forest`` and ``is_sklearn_boosting`` simply return
False.
"""
from __future__ import annotations

import numpy as np

from ..core.cart import DecisionTree, tree_leaf_ids

__all__ = [
    "is_sklearn_forest", "from_sklearn_tree", "leaf_proba_rows",
    "is_sklearn_boosting", "from_hgb_predictor", "leaf_value_rows",
]


def is_sklearn_forest(obj) -> bool:
    """Duck-typed check for a fitted sklearn forest ensemble
    (``RandomForestClassifier``-like: ``estimators_`` + ``classes_``)."""
    return hasattr(obj, "estimators_") and hasattr(obj, "classes_")


def from_sklearn_tree(estimator) -> DecisionTree:
    """Convert a fitted ``DecisionTreeClassifier`` to a ``DecisionTree``.

    sklearn leaves carry ``feature == TREE_UNDEFINED`` (-2) — mapped to the
    repo's -1 sentinel; split rule and child order are identical
    (``x[f] <= threshold`` -> left child).
    """
    t = estimator.tree_
    feature = np.asarray(t.feature, dtype=np.int32)
    feature = np.where(feature < 0, -1, feature).astype(np.int32)
    value = np.asarray(t.value, dtype=np.float64)[:, 0, :]
    return DecisionTree(
        feature=feature,
        threshold=np.asarray(t.threshold, dtype=np.float64),
        left=np.asarray(t.children_left, dtype=np.int32),
        right=np.asarray(t.children_right, dtype=np.int32),
        value=np.argmax(value, axis=1).astype(np.int32),
        n_features=int(t.n_features),
        n_classes=int(value.shape[1]),
    )


def leaf_proba_rows(estimator, tree: DecisionTree) -> np.ndarray:
    """(n_leaves, n_classes) float64 leaf probabilities in LUT-row order.

    Row ``r`` of the compiled LUT corresponds to leaf ``tree_leaf_ids[r]``;
    each row replicates ``DecisionTreeClassifier.predict_proba`` bit-for-bit:
    ``value[leaf]`` where the tree stores fractions (every row sums to 1,
    up to rounding), else ``value[leaf] / sum`` with zero sums divided by 1.
    """
    raw = np.asarray(estimator.tree_.value, dtype=np.float64)[:, 0, :]
    normalizer = raw.sum(axis=1)[:, np.newaxis]
    if np.all(np.abs(normalizer - 1.0) < 1e-9):
        proba = raw
    else:
        normalizer[normalizer == 0.0] = 1.0
        proba = raw / normalizer
    return np.ascontiguousarray(proba[tree_leaf_ids(tree)])


def is_sklearn_boosting(obj) -> bool:
    """Duck-typed check for a fitted sklearn histogram gradient-boosting
    classifier (``_predictors`` + ``_baseline_prediction`` + ``classes_``)."""
    return (hasattr(obj, "_predictors") and hasattr(obj, "classes_")
            and hasattr(obj, "_baseline_prediction"))


def from_hgb_predictor(predictor, n_features: int) -> DecisionTree:
    """Convert one fitted HGB ``TreePredictor`` to a ``DecisionTree``.

    Leaves get ``feature = -1``; an internal node splits on
    ``x[feature_idx] <= num_threshold`` (float64) -> ``left``.  Raises
    ``ValueError`` on a categorical split or an infinite threshold (a split
    that sends only NaN one way).
    """
    nodes = predictor.nodes
    leaf = nodes["is_leaf"].astype(bool)
    if np.any(nodes["is_categorical"][~leaf]):
        raise ValueError(
            "HistGradientBoostingClassifier with categorical splits cannot "
            "be compiled: a category set has no thermometer code"
        )
    threshold = np.asarray(nodes["num_threshold"], np.float64)
    if not np.all(np.isfinite(threshold[~leaf])):
        raise ValueError(
            "HistGradientBoostingClassifier splits on missing values (an "
            "infinite threshold); NaN routing cannot be compiled"
        )
    return DecisionTree(
        feature=np.where(leaf, -1, nodes["feature_idx"]).astype(np.int32),
        threshold=np.where(leaf, np.nan, threshold),
        left=np.where(leaf, -1, nodes["left"].astype(np.int64)).astype(
            np.int32),
        right=np.where(leaf, -1, nodes["right"].astype(np.int64)).astype(
            np.int32),
        value=np.zeros(len(nodes), np.int32),
        n_features=int(n_features),
        n_classes=1,
    )


def leaf_value_rows(predictor, tree: DecisionTree) -> np.ndarray:
    """(n_leaves,) float64 leaf values (the margin each leaf adds) in
    LUT-row order, as ``tree_leaf_ids`` enumerates the leaves."""
    value = np.asarray(predictor.nodes["value"], np.float64)
    return np.ascontiguousarray(value[tree_leaf_ids(tree)])
