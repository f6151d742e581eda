"""Forest compiler + sharded multi-bank execution.

Acceptance (ISSUE): a 25-tree sklearn RandomForest compiled with
``compile_forest`` must reproduce ``RandomForestClassifier.predict``
bit-exactly on the numpy ref path, and the jax engines must match per
engine; a single-tree forest must agree with the single-tree path; the
modelled aggregate dec/s must grow monotonically with bank count; and
forest-mode serving must survive per-bank BIST/repair with spare-row
survivors resolving to the right vote entries.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st

import repro
import repro.forest.compiler
from repro.core import DT2CAM, NonIdealSpec
from repro.core.lut import CELL_X
from repro.dt import load_split
from repro.forest import (
    CompiledForest,
    aggregate_votes,
    compile_forest,
    encode_group,
    forest_infer_ref,
    plan_forest,
    train_forest,
)
from repro.kernels import (match_cells, place_cells, serve_group,
                           tcam_match_banked)

sklearn = pytest.importorskip("sklearn")
from sklearn.ensemble import RandomForestClassifier  # noqa: E402

PAPER_DATASETS = ["cancer", "car"]


@pytest.fixture(scope="module", params=PAPER_DATASETS)
def rf_case(request):
    Xtr, ytr, Xte, yte = load_split(request.param)
    rf = RandomForestClassifier(
        n_estimators=25, max_depth=8, random_state=0
    ).fit(Xtr, ytr)
    forest = compile_forest(rf, s=128)
    return request.param, rf, forest, Xte, yte


# --------------------------------------------------------------------------
# sklearn parity: ref path
# --------------------------------------------------------------------------
def test_sklearn_forest_parity_ref(rf_case):
    name, rf, forest, Xte, yte = rf_case
    assert isinstance(forest, CompiledForest)
    assert forest.n_banks == 25
    res = forest_infer_ref(forest, Xte)
    np.testing.assert_array_equal(res.predictions, rf.predict(Xte))
    # soft-vote scores match predict_proba up to fp aggregation order
    np.testing.assert_allclose(res.score, rf.predict_proba(Xte),
                               rtol=0, atol=1e-12)


def test_sklearn_forest_parity_banked_engine(rf_case):
    name, rf, forest, Xte, yte = rf_case
    ref = forest_infer_ref(forest, Xte)
    ex = repro.ForestExecutor(forest, engine="banked")
    res = ex.infer(Xte)
    np.testing.assert_array_equal(res.predictions, rf.predict(Xte))
    np.testing.assert_array_equal(res.survivors, ref.survivors)
    np.testing.assert_array_equal(res.active_evals, ref.active_evals)


def test_sklearn_forest_parity_mxu_engine():
    # one dataset, small batch: the vmapped Pallas kernel runs in interpret
    # mode on CPU and is slow
    Xtr, ytr, Xte, yte = load_split("cancer")
    rf = RandomForestClassifier(
        n_estimators=5, max_depth=6, random_state=1
    ).fit(Xtr, ytr)
    forest = compile_forest(rf, s=128)
    Xq = Xte[:32]
    ref = forest_infer_ref(forest, Xq)
    res = repro.ForestExecutor(forest, engine="mxu").infer(Xq)
    np.testing.assert_array_equal(res.predictions, rf.predict(Xq))
    np.testing.assert_array_equal(res.survivors, ref.survivors)
    np.testing.assert_array_equal(res.active_evals, ref.active_evals)


# --------------------------------------------------------------------------
# single-tree forest == single-tree path
# --------------------------------------------------------------------------
def _single_tree_agrees(seed: int, n: int) -> None:
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 5))
    y = (X[:, 0] + 0.5 * X[:, 2] > 0).astype(np.int64)
    model = DT2CAM(s=32, max_depth=6).fit(X, y)
    forest = compile_forest([model.compiled.tree], s=32)
    assert forest.n_banks == 1
    single = model.infer(X)
    res = forest_infer_ref(forest, X)
    np.testing.assert_array_equal(res.predictions, single.predictions)
    np.testing.assert_array_equal(res.survivors[0], single.survivors)
    np.testing.assert_array_equal(res.active_evals[0], single.active_evals)


def test_single_tree_forest_equals_single_tree_deterministic():
    for seed in (0, 1, 2):
        _single_tree_agrees(seed, 80)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 9999), n=st.integers(30, 120))
def test_single_tree_forest_equals_single_tree_property(seed, n):
    _single_tree_agrees(seed, n)


# --------------------------------------------------------------------------
# plan + figures
# --------------------------------------------------------------------------
def test_plan_shapes_and_figures_monotone():
    Xtr, ytr, _, _ = load_split("cancer")
    trees = train_forest(Xtr, ytr, n_trees=4, max_depth=8, seed=0)
    rates = []
    for n in (1, 2, 4):
        forest = compile_forest(trees[:n], s=128)
        plan = plan_forest(forest)
        assert sorted(
            int(i) for g in plan.groups for i in g.bank_ids
        ) == list(range(n))
        for g in plan.groups:
            assert g.r_pad % g.s == 0 and (g.r_pad & (g.r_pad - 1)) == 0
            assert g.cells.shape == (g.n_banks, g.r_pad, g.d_pad * g.s)
        figs = repro.forest_figures(forest.layouts)
        assert figs["aggregate"]["n_banks"] == n
        rates.append(figs["aggregate"]["decs_pipe"])
    assert rates[0] < rates[1] < rates[2]


def test_compile_forest_validation():
    Xtr, ytr, Xte, _ = load_split("cancer")
    trees = train_forest(Xtr, ytr, n_trees=2, max_depth=4, seed=0)
    with pytest.raises(ValueError, match="vote"):
        compile_forest(trees, s=64, vote="plurality")
    forest = compile_forest(trees, s=64)
    with pytest.raises(repro.FeatureMismatch, match="expects"):
        forest_infer_ref(forest, Xte[:, :-1])


# --------------------------------------------------------------------------
# a plan group's reduction on the device
# --------------------------------------------------------------------------
def _host_reduce(survive, evals, rows, d_real):
    """The per-bank numpy loop ``serve_group`` replaced: (G, B, R) survive
    and evals -> (3, G, B) first survivor, survivor count, clamped evals
    over each bank's real rows."""
    g, b, _ = survive.shape
    out = np.empty((3, g, b), np.int64)
    for slot in range(g):
        sv = survive[slot, :, :rows[slot]]
        out[0, slot] = np.argmax(sv, axis=1)
        out[1, slot] = sv.sum(axis=1)
        out[2, slot] = np.minimum(evals[slot, :, :rows[slot]],
                                  d_real[slot]).sum(axis=1)
    return out


def _group_case(rng, s=32, d_pad=4, r_pad=256):
    """One plan group of three banks with unequal real rows and divisions:
    a sparse bank (several survivors per request), a dense one (none), and
    a 7-row bank (some of each); padding rows carry kmax = -1 and padding
    divisions are all-CELL_X, as ``plan_forest`` lays them out."""
    rows, d_real = np.array([256, 130, 7]), np.array([4, 2, 1])
    cells = np.full((3, r_pad, d_pad * s), CELL_X, np.int8)
    kmax = np.zeros((3, r_pad, d_pad), np.int32)
    for g, p_care in enumerate((0.02, 0.3, 0.05)):
        r, w = rows[g], d_real[g] * s
        care = rng.random((r, w)) < p_care
        cells[g, :r, :w] = np.where(care, rng.integers(0, 2, (r, w)), CELL_X)
        kmax[g, r:] = -1
    return cells, kmax, rows, d_real


@pytest.mark.parametrize("n", [5, 16])
@pytest.mark.parametrize("engine", ["banked", "mxu", "ref"])
def test_serve_group_equals_host_reduction(engine, n):
    rng = np.random.default_rng(7)
    cells, kmax, rows, d_real = _group_case(rng)
    bucket = 8 if n <= 8 else 16
    x = np.zeros((3, bucket, cells.shape[-1]), np.uint8)
    x[:, :n] = rng.integers(0, 2, (3, n, cells.shape[-1]))
    ops = place_cells(cells, 32, kmax, engine=engine)
    got = np.asarray(serve_group(
        ops, jnp.asarray(rows, jnp.int32), jnp.asarray(d_real, jnp.int32),
        jnp.asarray(x), interpret=True))
    assert got.shape == (3, 3, bucket) and got.dtype == np.int32
    survive, evals = (np.asarray(o) for o in match_cells(ops, jnp.asarray(x),
                                                          interpret=True))
    want = _host_reduce(survive, evals, rows, d_real)
    np.testing.assert_array_equal(got[:, :, :n], want[:, :, :n])
    ns = got[1, :, :n]
    assert (ns == 0).any() and (ns > 1).any()
    # the first survivor is the lowest surviving row, not just any
    several = np.argwhere(ns > 1)
    g, b = several[0]
    assert got[0, g, b] == np.flatnonzero(survive[g, b])[0]
    # padding rows report one evaluation each and padding divisions add
    # more; neither counts
    assert (evals[:, :n, rows[2]:] > 0).all()
    assert (evals[2, :n, :rows[2]] > d_real[2]).any()


# --------------------------------------------------------------------------
# serving: forest mode, repair, degradation
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served_forest():
    Xtr, ytr, Xte, yte = load_split("cancer")
    trees = train_forest(Xtr, ytr, n_trees=6, max_depth=6, seed=0)
    forest = compile_forest(trees, s=128, spare_rows=4)
    return forest, Xte


@pytest.fixture(scope="module")
def padded_forest():
    """Three plan groups at S=16: five banks padded with rows, two with
    divisions."""
    Xtr, ytr, Xte, yte = load_split("cancer")
    trees = train_forest(Xtr, ytr, n_trees=6, max_depth=8, seed=0)
    forest = compile_forest(trees, s=16, spare_rows=4)
    groups = plan_forest(forest).groups
    assert sum((g.rows < g.r_pad).sum() for g in groups) == 5
    assert sum((g.d_real < g.d_pad).sum() for g in groups) == 2
    return forest, Xte


FIELDS = ("prediction", "survivor", "n_survivors", "active_evals", "energy_j")


def _fields(results) -> dict:
    return {k: np.array([getattr(r, k) for r in results]) for k in FIELDS}


def _forest_fields(forest, predictions, survivors, active, enabled) -> dict:
    """What a forest server answers per request, from per-bank survivors
    (LUT rows, -1 = none) and active evals."""
    hw = repro.DEFAULT_HW
    total = active[enabled].sum(axis=0)
    return {
        "prediction": predictions,
        "survivor": np.full(len(predictions), -1),
        "n_survivors": (survivors[enabled] >= 0).sum(axis=0),
        "active_evals": total,
        "energy_j": (total.astype(np.float64) * hw.e_row
                     + int(enabled.sum()) * hw.e_mem),
    }


def _ref_fields(forest, X, enabled=None) -> dict:
    ref = forest_infer_ref(forest, X, enabled=enabled)
    return _forest_fields(forest, ref.predictions, ref.survivors,
                          ref.active_evals, ref.enabled)


def _assert_fields_equal(got: dict, want: dict) -> None:
    for k in FIELDS:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def test_forest_serving_matches_ref(served_forest):
    forest, Xte = served_forest
    want = _ref_fields(forest, Xte[:45])
    cfg = repro.ServeConfig(engine="banked", max_batch=16, background=False)
    srv = repro.TCAMServer(forest, config=cfg)
    assert srv.warmup() > 0
    futs = [srv.submit(x) for x in Xte[:45]]
    srv.drain()
    _assert_fields_equal(_fields([f.result() for f in futs]), want)
    assert srv.health()["mode"] == "forest"
    m = srv.metrics()
    assert m["modelled_mdecs_pipe"] > m["modelled_mdecs_ensemble"]
    with pytest.raises(repro.FeatureMismatch, match="expects"):
        srv.submit(Xte[0, :-1])


def test_forest_repair_keeps_serving(served_forest):
    """Per-bank BIST + spare-row repair: post-repair survivors land on
    spare rows, which must resolve through the physical->LUT row map to
    the original vote entries (not crash or mis-vote)."""
    forest, Xte = served_forest
    ref = forest_infer_ref(forest, Xte[:48])
    cfg = repro.ServeConfig(engine="banked", max_batch=16, background=False)
    srv = repro.TCAMServer(
        forest, config=cfg,
        nonideal=NonIdealSpec(p_sa0=0.01, p_sa1=0.01),
        rng=np.random.default_rng(11),
    )
    bists = srv.self_test()
    assert len(bists) == forest.n_banks
    assert sum(b.defective_rows.size for b in bists) > 0
    reports = srv.repair(bists)
    assert sum(r.rows_repaired for r in reports) > 0
    futs = [srv.submit(x) for x in Xte[:48]]
    srv.drain()
    preds = np.array([f.result().prediction for f in futs])
    # the repaired chip votes like the ideal forest on (almost) all inputs;
    # unrepairable banks drop out of the vote rather than poisoning it
    assert (preds == ref.predictions).mean() > 0.9
    health = srv.health()
    assert health["n_banks"] == forest.n_banks
    assert 1 <= health["banks_enabled"] <= forest.n_banks


@pytest.mark.parametrize("engine", ["banked", "mxu", "ref"])
def test_forest_serving_every_field_per_engine(padded_forest, engine):
    """Every field of every answer equals the oracle's, through batches
    that leave the last bucket partly filled."""
    forest, Xte = padded_forest
    X = Xte[:37]
    cfg = repro.ServeConfig(engine=engine, max_batch=16, min_bucket=4,
                            background=False)
    srv = repro.TCAMServer(forest, config=cfg)
    results = srv.serve(X)
    rec = srv.metrics_store.batch_records()
    assert (rec["n"] < rec["bucket"]).any()
    _assert_fields_equal(_fields(results), _ref_fields(forest, X))


@pytest.mark.parametrize("engine", ["banked", "mxu", "ref"])
def test_forest_repair_survivors_resolve_through_row_map(
        padded_forest, engine, monkeypatch):
    """After spare-row repair, every per-bank survivor the vote sees is the
    host reduction's physical row of the same faulted layouts, translated
    through ``_f_row_map``; some of them sit on spare rows."""
    forest, Xte = padded_forest
    X = Xte[:48]
    cfg = repro.ServeConfig(engine=engine, max_batch=16, background=False)
    srv = repro.TCAMServer(
        forest, config=cfg,
        nonideal=NonIdealSpec(p_sa0=0.002, p_sa1=0.002),
        rng=np.random.default_rng(11),
    )
    srv.repair(srv.self_test())
    assert 0 < srv._f_enabled.sum() < forest.n_banks
    voted = []

    def spy(forest, survivors, enabled=None):
        voted.append(np.array(survivors, copy=True))
        return aggregate_votes(forest, survivors, enabled)

    monkeypatch.setattr(repro.forest.compiler, "aggregate_votes", spy)
    results = srv.serve(X)

    Xp = forest.prepare_inputs(X)
    physical = np.empty((forest.n_banks, len(X)), np.int64)
    lut_rows = np.empty_like(physical)
    active = np.empty_like(physical)
    for grp, km in zip(srv._f_plan.groups, srv._f_group_kmax):
        survive, evals = (np.asarray(o) for o in tcam_match_banked(
            grp.cells, encode_group(forest, grp, Xp), grp.s, km,
            engine="ref"))
        first, ns, act = _host_reduce(survive, evals, grp.rows, grp.d_real)
        for slot, bank_id in enumerate(grp.bank_ids):
            physical[bank_id] = np.where(ns[slot] > 0, first[slot], -1)
            lut_rows[bank_id] = np.where(
                ns[slot] > 0, srv._f_row_map[bank_id][first[slot]], -1)
            active[bank_id] = act[slot]
    n_rows = np.array([lay.n_rows for lay in srv._f_layouts])
    assert (physical >= n_rows[:, None]).any()      # survivors on spares
    np.testing.assert_array_equal(np.concatenate(voted, axis=1), lut_rows)
    enabled = srv._f_enabled
    predictions, _ = aggregate_votes(forest, lut_rows, enabled)
    _assert_fields_equal(_fields(results), _forest_fields(
        forest, predictions, lut_rows, active, enabled))


def test_disable_bank_degrades_gracefully(served_forest):
    forest, Xte = served_forest
    cfg = repro.ServeConfig(engine="banked", max_batch=16, background=False)
    srv = repro.TCAMServer(forest, config=cfg)
    enabled = np.ones(forest.n_banks, bool)
    enabled[0] = False
    ref = forest_infer_ref(forest, Xte[:32], enabled=enabled)
    srv.disable_bank(0)
    futs = [srv.submit(x) for x in Xte[:32]]
    srv.drain()
    preds = np.array([f.result().prediction for f in futs])
    np.testing.assert_array_equal(preds, ref.predictions)
    for b in range(1, forest.n_banks):
        if b < forest.n_banks - 1:
            srv.disable_bank(b)
    with pytest.raises(RuntimeError, match="last voting bank"):
        srv.disable_bank(forest.n_banks - 1)


# --------------------------------------------------------------------------
# blessed top-level API
# --------------------------------------------------------------------------
def test_top_level_api_resolves():
    missing = [n for n in repro.__all__ if not hasattr(repro, n)]
    assert missing == []
    assert repro.compile_forest is compile_forest
    assert repro.TCAMServer.__module__.startswith("repro.serve")
    with pytest.raises(AttributeError):
        repro.not_a_public_name
