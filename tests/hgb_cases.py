"""Small fitted ``HistGradientBoostingClassifier`` models for the boosted
forest tests, with query rows that include inputs lying exactly on split
thresholds."""
import numpy as np

from repro.dt import load_split

# id -> (dataset, iterations, max depth, training rows; None = all)
HGB_CASES = {
    "binary": ("cancer", 20, 3, None),
    "multiclass": ("iris", 10, 4, None),
    # fewer than 2 x min_samples_leaf rows: every tree is a single leaf
    "single-leaf": ("cancer", 12, 4, 30),
}


def on_thresholds(model, X: np.ndarray, n: int = 24) -> np.ndarray:
    """``n`` rows of ``X``, each with one feature set exactly to the
    threshold of an internal node, taken from the model's predictors."""
    splits = [(int(f), float(t)) for it in model._predictors for p in it
              for f, t, leaf in zip(p.nodes["feature_idx"],
                                    p.nodes["num_threshold"],
                                    p.nodes["is_leaf"]) if not leaf]
    rows = []
    for i, (f, t) in enumerate(splits[:n]):
        row = X[i % len(X)].copy()
        row[f] = t
        rows.append(row)
    return np.array(rows).reshape(-1, X.shape[1])


def hgb_case(name: str):
    """(model, query rows) for one of ``HGB_CASES``."""
    from sklearn.ensemble import HistGradientBoostingClassifier

    ds, n_iter, depth, n_train = HGB_CASES[name]
    Xtr, ytr, Xte, _ = load_split(ds)
    if n_train is not None:
        Xtr, ytr = Xtr[:n_train], ytr[:n_train]
    model = HistGradientBoostingClassifier(
        max_iter=n_iter, max_depth=depth, early_stopping=False,
        random_state=0).fit(Xtr, ytr)
    return model, np.concatenate([Xte, on_thresholds(model, Xte)])


def categorical_model():
    """A boosted model whose splits are on a categorical feature."""
    from sklearn.ensemble import HistGradientBoostingClassifier

    rng = np.random.default_rng(0)
    X = np.stack([rng.integers(0, 4, 400), rng.random(400)], 1).astype(
        np.float64)
    y = np.isin(X[:, 0], (1, 3)).astype(np.int64)
    return HistGradientBoostingClassifier(
        max_iter=3, max_depth=2, categorical_features=[0],
        random_state=0).fit(X, y), X
