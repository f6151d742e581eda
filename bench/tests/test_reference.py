"""The plain reference agrees with the program's numpy oracles, and its
float32 control does not."""
import numpy as np
import pytest

import data
import deploy


@pytest.fixture(scope="module")
def credit_small():
    Xtr, ytr, Xte, _ = data.load_split("credit")
    cfg = {"fit": {"max_depth": 12, "max_leaves": 300}, "s": 32}
    kind = deploy.kind_module("tree")
    tree = kind.fit(cfg, Xtr, ytr)
    return kind.compile(tree, cfg), kind.reference(tree, cfg), Xte[:400]


def test_tree_matches_simulate(credit_small):
    import repro
    compiled, ref, X = credit_small
    sim = repro.simulate(compiled.layout, repro.encode_inputs(compiled.lut, X))
    got = ref.answers(X)
    want = {"prediction": sim.predictions, "survivor": sim.survivors,
            "n_survivors": sim.n_survivors, "active_evals": sim.active_evals,
            "energy_j": sim.energy_per_dec}
    for k, v in want.items():
        np.testing.assert_array_equal(got[k], v, err_msg=k)
    bank, = ref.banks
    assert (bank.rows, bank.cols) == (compiled.layout.n_rows,
                                      compiled.layout.width + 1)


def test_forest_matches_forest_infer_ref():
    import repro
    from repro.forest import forest_infer_ref
    Xtr, ytr, Xte, _ = data.load_split("covid")
    cfg = {"fit": {"n_estimators": 4, "max_depth": 9, "random_state": 0},
           "s": 128}
    kind = deploy.kind_module("sklearn_forest")
    model = kind.fit(cfg, Xtr, ytr)
    forest = kind.compile(model, cfg)
    X = Xte[:300]
    got = kind.reference(model, cfg).answers(X)
    r = forest_infer_ref(forest, X)
    active = r.active_evals.sum(axis=0)
    hw = repro.DEFAULT_HW
    np.testing.assert_array_equal(got["prediction"], r.predictions)
    np.testing.assert_array_equal(got["n_survivors"],
                                  (r.n_survivors > 0).sum(axis=0))
    np.testing.assert_array_equal(got["active_evals"], active)
    np.testing.assert_array_equal(
        got["energy_j"], active * hw.e_row + forest.n_banks * hw.e_mem)


def test_control_differs(credit_small):
    _, ref, X = credit_small
    got, ctl = ref.answers(X), ref.control(X)
    assert np.sum(got["energy_j"] != ctl["energy_j"]) > len(X) // 2
