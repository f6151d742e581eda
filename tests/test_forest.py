"""Forest compiler + sharded multi-bank execution.

Acceptance (ISSUE): a 25-tree sklearn RandomForest compiled with
``compile_forest`` must reproduce ``RandomForestClassifier.predict``
bit-exactly on the numpy ref path, and the jax engines must match per
engine; a single-tree forest must agree with the single-tree path; the
modelled aggregate dec/s must grow monotonically with bank count; and
forest-mode serving must survive per-bank BIST/repair with spare-row
survivors resolving to the right vote entries.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hgb_cases import HGB_CASES, categorical_model, hgb_case
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
from repro.forest.executor import (encode_ranges, group_columns,
                                   union_thresholds)
from repro.kernels import (expand_ranges, match_cells, place_cells,
                           serve_group)

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


def _range_case(rng, g, w, bucket, n, f=16):
    """Random column tables and range indices for a (g, w) group, and the
    (g, bucket, w) search words they expand to, built on the host; rows
    past ``n`` are zero padding, as ``encode_ranges`` leaves them."""
    feat = rng.integers(0, f, (g, w)).astype(np.int32)
    q = rng.integers(0, 9, (g, w)).astype(np.int32)
    q[:, 0] = np.iinfo(np.int32).max            # a decoder bit: always 0
    u = np.zeros((bucket, f), np.int32)
    u[:n] = rng.integers(0, 9, (n, f))
    x = (u[:, feat].swapaxes(0, 1) >= q[:, None, :]).astype(np.uint8)
    return feat, q, u, x


@pytest.mark.parametrize("n", [5, 16])
@pytest.mark.parametrize("engine", ["banked", "ref"])
def test_serve_group_equals_host_reduction(engine, n):
    rng = np.random.default_rng(7)
    cells, kmax, rows, d_real = _group_case(rng)
    bucket = 8 if n <= 8 else 16
    feat, q, u, x = _range_case(rng, 3, cells.shape[-1], bucket, n)
    assert (x[:, :, 0] == 0).all() and 0.3 < x[:, :n].mean() < 0.7
    ops = place_cells(cells, 32, kmax, engine=engine)
    got = np.asarray(serve_group(
        ops, jnp.asarray(rows, jnp.int32), jnp.asarray(d_real, jnp.int32),
        jnp.asarray(feat), jnp.asarray(q), jnp.asarray(u), interpret=True))
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
# search words expanded on the device from per-feature range indices
# --------------------------------------------------------------------------
def _rf(dataset, vote, **kw):
    Xtr, ytr, Xte, _ = load_split(dataset)
    rf = RandomForestClassifier(random_state=0, **kw).fit(Xtr, ytr)
    return compile_forest(rf, s=32, vote=vote), Xte


def _edge_rows(forest, X):
    """Rows of ``X`` with one feature each set exactly on a threshold of
    the union, to +inf, -inf or NaN."""
    rows = []
    for j, th in enumerate(union_thresholds(forest)):
        for v in (*th[:: max(1, th.size // 3)], np.inf, -np.inf, np.nan):
            row = X[len(rows) % len(X)].copy()
            row[j] = v
            rows.append(row)
    return np.array(rows)


def _expansion_case(case):
    """(forest, X, bucket) for one case of the expansion test."""
    if case.startswith("hgb-"):
        model, X = hgb_case(case[4:])
        return compile_forest(model, s=32), X, None
    if case == "featureless-bank":
        forest, X = _rf("cancer", "soft", n_estimators=5, max_depth=2)
        union = union_thresholds(forest)
        assert any(b.lut.thresholds[j].size == 0 and union[j].size > 0
                   for b in forest.banks for j in range(forest.n_features))
        return forest, X, None
    forest, X = _rf("cancer", "hard" if case == "rf-hard" else "soft",
                    n_estimators=6, max_depth=6)
    if case == "edges":
        X = _edge_rows(forest, X)
        Xp = forest.prepare_inputs(X)
        assert any(np.isin(Xp[:, j], th).any()
                   for j, th in enumerate(union_thresholds(forest)))
    if case == "padded":
        return forest, X[:13], 16
    return forest, X, None


@pytest.mark.parametrize("case", ["rf-soft", "rf-hard", "hgb-binary",
                                  "hgb-multiclass", "edges", "padded",
                                  "featureless-bank"])
def test_device_expansion_equals_encode_group(case):
    """For every plan group, the search words the device builds from the
    (bucket, F) range indices equal the host's per-bank ``encode_group``
    bit for bit; rows padded up to the bucket are ignored."""
    forest, X, bucket = _expansion_case(case)
    assert forest.cast_f32 == (not case.startswith("hgb-"))
    Xp = forest.prepare_inputs(X)
    n = len(X)
    union = union_thresholds(forest)
    u = encode_ranges(union, Xp, bucket)
    assert u.shape == (bucket or n, forest.n_features) and u.dtype == np.int32
    assert (u[n:] == 0).all()
    for grp in plan_forest(forest).groups:
        feat, q = group_columns(forest, grp, union)
        got = np.asarray(jax.jit(expand_ranges)(feat, q, u))
        assert got.shape == (grp.n_banks, len(u), grp.width)
        np.testing.assert_array_equal(got[:, :n],
                                      encode_group(forest, grp, Xp))
    if bucket is not None:
        res = repro.ForestExecutor(forest, engine="banked").infer(X)
        ref = forest_infer_ref(forest, X)
        for k in ("predictions", "survivors", "n_survivors", "active_evals"):
            np.testing.assert_array_equal(getattr(res, k), getattr(ref, k),
                                          err_msg=k)


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


@pytest.mark.parametrize("engine", ["banked", "ref"])
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


@pytest.mark.parametrize("engine", ["banked", "ref"])
def test_forest_repair_survivors_resolve_through_row_map(
        padded_forest, engine, monkeypatch):
    """After spare-row repair, every per-bank survivor the vote sees is the
    host reduction's physical row of the same faulted layouts, translated
    through the executor's row maps; some of them sit on spare rows."""
    forest, Xte = padded_forest
    X = Xte[:48]
    cfg = repro.ServeConfig(engine=engine, max_batch=16, background=False)
    srv = repro.TCAMServer(
        forest, config=cfg,
        nonideal=NonIdealSpec(p_sa0=0.002, p_sa1=0.002),
        rng=np.random.default_rng(11),
    )
    srv.repair(srv.self_test())
    fx = srv._fx
    assert 0 < fx.enabled.sum() < forest.n_banks
    assert all(k is None for k in fx.kmax)     # no SA offsets: kmax0
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
    for grp in fx.plan.groups:
        survive, evals = (np.asarray(o) for o in match_cells(
            place_cells(grp.cells, grp.s, grp.kmax0, engine="ref"),
            jnp.asarray(encode_group(forest, grp, Xp))))
        first, ns, act = _host_reduce(survive, evals, grp.rows, grp.d_real)
        for slot, bank_id in enumerate(grp.bank_ids):
            physical[bank_id] = np.where(ns[slot] > 0, first[slot], -1)
            lut_rows[bank_id] = np.where(
                ns[slot] > 0, fx.row_maps[bank_id][first[slot]], -1)
            active[bank_id] = act[slot]
    n_rows = np.array([lay.n_rows for lay in fx.layouts])
    assert (physical >= n_rows[:, None]).any()      # survivors on spares
    np.testing.assert_array_equal(np.concatenate(voted, axis=1), lut_rows)
    enabled = fx.enabled
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
# one serving path: the server drives the executor's placed groups
# --------------------------------------------------------------------------
def _executor_fields(forest, res) -> dict:
    """What the server answers per request, from an executor's result."""
    return {**_forest_fields(forest, res.predictions, res.survivors,
                             res.active_evals, res.enabled),
            "score": res.score}


def _served_fields(results) -> dict:
    return {**_fields(results),
            "score": np.stack([r.score for r in results])}


def _assert_same(got: dict, want: dict) -> None:
    assert got.keys() == want.keys()
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def _serve(forest, engine, **kw):
    cfg = repro.ServeConfig(engine=engine, max_batch=16, min_bucket=4,
                            background=False)
    return repro.TCAMServer(forest, config=cfg, **kw)


@pytest.mark.parametrize("engine", ["banked", "ref"])
def test_executor_equals_server_on_repaired_chip(padded_forest, engine):
    """An executor built from a faulted server's layouts and kmax, handed
    the same repairs, answers as the server does in every field: its row
    maps send survivors on spare rows to the rules they carry."""
    forest, Xte = padded_forest
    X = Xte[:37]
    srv = _serve(forest, engine, rng=np.random.default_rng(11),
                 nonideal=NonIdealSpec(p_sa0=0.002, p_sa1=0.002,
                                       sa_sigma=0.05))
    assert any(k is not None for k in srv._fx.kmax)
    ex = repro.ForestExecutor(forest, engine=engine,
                              layouts=list(srv._fx.layouts),
                              kmax=srv._fx.kmax)
    bists = srv.self_test()
    reports = srv.repair(bists)
    banks = [i for i, b in enumerate(bists) if b.defective_rows.size]
    assert len(banks) == len(reports) and sum(
        r.rows_repaired for r in reports) > 0
    ex.repair({i: (srv._fx.layouts[i], r.assignments)
               for i, r in zip(banks, reports)})
    assert any((rm[lay.n_rows:] >= 0).any()
               for rm, lay in zip(ex.row_maps, ex.layouts))
    assert 0 < srv._fx.enabled.sum() < forest.n_banks
    res = ex.infer(X, enabled=srv._fx.enabled)
    assert res.engine == srv.engine == engine
    # every survivor is a LUT row, those on spare rows included
    n_rows = np.array([lay.n_rows for lay in ex.layouts])
    assert (res.survivors < n_rows[:, None]).all()
    _assert_same(_served_fields(srv.serve(X)), _executor_fields(forest, res))


@pytest.mark.parametrize("engine", ["banked", "ref"])
def test_executor_enabled_mask_equals_server_disable_bank(padded_forest,
                                                          engine):
    forest, Xte = padded_forest
    X = Xte[:21]
    srv = _serve(forest, engine)
    ex = repro.ForestExecutor(forest, engine=engine)
    for bank in (0, forest.n_banks - 1):
        srv.disable_bank(bank)
        ex.disable_bank(bank)
    np.testing.assert_array_equal(ex.enabled, srv._fx.enabled)
    assert ex.enabled.sum() == forest.n_banks - 2
    res = ex.infer(X)
    _assert_same(_served_fields(srv.serve(X)), _executor_fields(forest, res))
    _assert_same(_executor_fields(forest, res), _executor_fields(
        forest, forest_infer_ref(forest, X, enabled=ex.enabled)))


@pytest.mark.parametrize("engine", ["banked", "ref"])
def test_two_group_forest_server_executor_ref_agree(engine):
    """Shallow and deep trees plan into separate groups; the server, the
    executor and the numpy oracle agree in every field."""
    Xtr, ytr, Xte, _ = load_split("cancer")
    trees = (train_forest(Xtr, ytr, n_trees=2, max_depth=3, seed=0)
             + train_forest(Xtr, ytr, n_trees=2, max_depth=10, seed=0))
    forest = compile_forest(trees, s=32)
    X = Xte[:45]
    ex = repro.ForestExecutor(forest, engine=engine)
    assert ex.plan.n_groups >= 2
    srv = _serve(forest, engine)
    assert srv._fx.plan.plan_id == ex.plan.plan_id
    want = _executor_fields(forest, forest_infer_ref(forest, X))
    _assert_same(_served_fields(srv.serve(X)), want)
    _assert_same(_executor_fields(forest, ex.infer(X)), want)


@pytest.mark.parametrize("requested", ["auto", "packed", "mxu"])
@pytest.mark.parametrize("entry", ["server", "executor"])
def test_forest_engine_resolution(served_forest, entry, requested):
    """'auto' is 'banked'; 'packed' falls back to 'banked' with a warning
    (and a fallback tick on the server); any other name, 'mxu' included,
    is refused, naming the accepted values."""
    forest, _ = served_forest
    if entry == "server":
        make = lambda: repro.TCAMServer(forest, config=repro.ServeConfig(
            engine=requested, background=False))
    else:
        make = lambda: repro.ForestExecutor(forest, engine=requested)
    if requested == "mxu":
        with pytest.raises(ValueError, match="'banked', 'ref'"):
            make()
        return
    if requested == "packed":
        with pytest.warns(RuntimeWarning, match="falling back to 'banked'"):
            obj = make()
    else:
        obj = make()
    assert obj.engine == "banked"
    if entry == "server":
        assert obj.metrics()["engine_fallbacks"] == (requested == "packed")
        obj.close()


# --------------------------------------------------------------------------
# boosted forests: HistGradientBoostingClassifier
# --------------------------------------------------------------------------
@pytest.fixture(scope="module", params=sorted(HGB_CASES))
def hgb(request):
    model, X = hgb_case(request.param)
    return model, compile_forest(model, s=32), X


def _margin(model, X):
    return model.decision_function(X).reshape(X.shape[0], -1)


def test_hgb_compiles_one_bank_per_predictor(hgb):
    model, forest, _ = hgb
    k = model.n_trees_per_iteration_
    assert forest.boosted and forest.vote == "margin"
    assert forest.n_banks == len(model._predictors) * k
    assert [b.column for b in forest.banks] == list(range(k)) * len(
        model._predictors)
    assert not forest.cast_f32
    np.testing.assert_array_equal(forest.baseline,
                                  model._baseline_prediction.ravel())
    assert forest.link == ("logit" if k == 1 else "softmax")
    for bank, (i, j) in zip(forest.banks, np.ndindex(len(model._predictors),
                                                     k)):
        p = model._predictors[i][j]
        assert bank.margin.shape == (p.get_n_leaf_nodes(),)
        assert bank.lut.n_rows == p.get_n_leaf_nodes()


def test_hgb_parity_ref(hgb):
    model, forest, X = hgb
    res = forest_infer_ref(forest, X)
    np.testing.assert_array_equal(res.predictions, model.predict(X))
    np.testing.assert_array_equal(res.margin, _margin(model, X))
    np.testing.assert_array_equal(res.score, model.predict_proba(X))
    assert (res.n_survivors == 1).all()


@pytest.mark.parametrize("engine", ["banked", "ref"])
def test_hgb_parity_executor(hgb, engine):
    model, forest, X = hgb
    ref = forest_infer_ref(forest, X)
    res = repro.ForestExecutor(forest, engine=engine).infer(X)
    np.testing.assert_array_equal(res.predictions, model.predict(X))
    np.testing.assert_array_equal(res.margin, _margin(model, X))
    np.testing.assert_array_equal(res.score, model.predict_proba(X))
    for k in ("survivors", "n_survivors", "active_evals"):
        np.testing.assert_array_equal(getattr(res, k), getattr(ref, k),
                                      err_msg=k)


def test_hgb_leaf_values_in_lut_row_order(hgb):
    """Each bank's survivor holds the value of the leaf that the
    predictor's own walk reaches."""
    model, forest, X = hgb
    res = forest_infer_ref(forest, X)
    bitsets, f_idx_map = model._bin_mapper.make_known_categories_bitsets()
    preds = [p for it in model._predictors for p in it]
    for b, (bank, p) in enumerate(zip(forest.banks, preds)):
        np.testing.assert_array_equal(
            bank.margin[res.survivors[b]],
            p.predict(X, bitsets, f_idx_map, 1), err_msg=str(b))


def test_hgb_decides_on_the_margin_not_the_probabilities():
    """Binary: class 1 only for a strictly positive margin, so a margin of
    exactly 0 is class 0; multiclass: argmax, ties to the lower class."""
    def zeroed(forest, baseline):
        return dataclasses.replace(
            forest, baseline=np.asarray(baseline, np.float64),
            banks=[dataclasses.replace(b, margin=np.zeros_like(b.margin))
                   for b in forest.banks])

    model, _ = hgb_case("binary")
    forest = zeroed(compile_forest(model, s=32), [0.0])
    pred, score = aggregate_votes(forest,
                                  np.zeros((forest.n_banks, 3), np.int32))
    np.testing.assert_array_equal(pred, [model.classes_[0]] * 3)
    np.testing.assert_array_equal(score, np.full((3, 2), 0.5))
    model, _ = hgb_case("multiclass")
    forest = zeroed(compile_forest(model, s=32), [0.0, 1.0, 1.0])
    pred, _ = aggregate_votes(forest,
                              np.zeros((forest.n_banks, 2), np.int32))
    np.testing.assert_array_equal(pred, [model.classes_[1]] * 2)


def test_hgb_refuses_nan_inputs(hgb):
    model, forest, X = hgb
    Xn = X[:4].copy()
    Xn[1, 0] = np.nan
    with pytest.raises(ValueError, match="NaN"):
        forest_infer_ref(forest, Xn)
    with pytest.raises(ValueError, match="NaN"):
        repro.ForestExecutor(forest, engine="banked").infer(Xn)


def test_hgb_refuses_categorical_and_nan_splits_and_votes():
    from repro.forest import from_hgb_predictor
    model, _ = categorical_model()
    with pytest.raises(ValueError, match="categorical"):
        compile_forest(model, s=32)
    model, _ = hgb_case("binary")
    with pytest.raises(ValueError, match="vote"):
        compile_forest(model, s=32, vote="soft")
    p = model._predictors[0][0]
    nodes = p.nodes.copy()
    nodes["num_threshold"][0] = np.inf
    with pytest.raises(ValueError, match="missing"):
        from_hgb_predictor(type("P", (), {"nodes": nodes})(),
                           model.n_features_in_)


def test_soft_vote_score_equals_predict_proba(rf_case):
    """The soft vote still scores as ``predict_proba`` does, bit for bit,
    now that it shares its accumulation loop with the margin."""
    name, rf, forest, Xte, yte = rf_case
    res = repro.ForestExecutor(forest, engine="banked").infer(Xte)
    np.testing.assert_array_equal(res.score, rf.predict_proba(Xte))
    assert res.margin is None


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


# --------------------------------------------------------------------------
# deep banks: the banked match streamed division by division
# --------------------------------------------------------------------------
def _deep_group_case(seed, g, r_pad, d_pad, s):
    """A plan group of deep, sparse banks: real rows and divisions below
    the padded shape (pad rows kmax = -1, all-X pad divisions), and some
    real rows that tolerate one or two mismatches (kmax > 0)."""
    rng = np.random.default_rng(seed)
    cells = np.full((g, r_pad, d_pad * s), CELL_X, np.int8)
    kmax = np.zeros((g, r_pad, d_pad), np.int32)
    rows = rng.integers(r_pad // 2, r_pad + 1, g)
    d_real = rng.integers(d_pad // 2, d_pad + 1, g)
    for i in range(g):
        r, w = rows[i], d_real[i] * s
        care = rng.random((r, w)) < 2.0 / w
        cells[i, :r, :w] = np.where(care, rng.integers(0, 2, (r, w)), CELL_X)
        kmax[i, :r] = np.where(rng.random((r, 1)) < 0.2,
                               rng.integers(1, 3, (r, d_pad)), 0)
        kmax[i, r:] = -1
    return cells, kmax


@pytest.mark.parametrize("seed,g,b,r_pad,d_pad,s", [
    (0, 3, 8, 64, 16, 8),
    (1, 2, 5, 40, 20, 8),
    (2, 4, 16, 32, 17, 16),
    (3, 1, 3, 96, 33, 4),
])
def test_streamed_banked_match_equals_one_shot(seed, g, b, r_pad, d_pad, s):
    """``tcam_match_banked_stream`` gives the one-shot form's survive and
    evals bit for bit, pad rows and all-X pad divisions included."""
    from repro.core.lut import bitplanes
    from repro.kernels import tcam_match_banked_ref, tcam_match_banked_stream
    cells, kmax = _deep_group_case(seed, g, r_pad, d_pad, s)
    rng = np.random.default_rng(seed + 100)
    x = rng.integers(0, 2, (g, b, d_pad * s)).astype(np.uint8)
    is0, is1 = bitplanes(cells)
    want = [np.asarray(a) for a in tcam_match_banked_ref(x, is0, is1, s,
                                                         kmax)]
    got = [np.asarray(a) for a in tcam_match_banked_stream(x, is0, is1, s,
                                                           kmax)]
    for w, o in zip(want, got):
        assert o.shape == (g, b, r_pad) and o.dtype == np.int32
        np.testing.assert_array_equal(o, w)
    survive, evals = want
    assert survive.any() and (evals == 1).any() and (evals == d_pad).any()
    assert ((evals > 1) & (evals < d_pad)).any()
    tolerant = np.broadcast_to(kmax[:, None, :, 0] > 0, survive.shape)
    assert survive[tolerant].any()            # a tolerant row lives


# the plan groups of the benchmark's forests at S=128, (G, R, D): every
# bucket of the serving ladder
_BUCKETS = (8, 16, 32, 64, 128, 256)


@pytest.mark.parametrize("g,r,d,streams", [
    (100, 4096, 4, False),      # covid-rf100: one group
    (229, 128, 1, False),       # credit-hgb500: three groups
    (43, 128, 2, False),
    (228, 256, 2, False),
    (10, 32768, 128, True),     # credit-rf100-shard10: one group
])
def test_banked_form_chosen_from_group_shapes(g, r, d, streams):
    from repro.kernels import banked_streams
    assert [banked_streams(g, b, r, d, 128) for b in _BUCKETS] == \
        [streams] * len(_BUCKETS)


def _deep_rf(n_estimators=4, seed=0):
    """A small full-depth random forest on noisy data: at S=8 every bank
    spans 16 or more divisions."""
    rng = np.random.default_rng(seed)
    X = np.round(rng.normal(size=(900, 8)), 2)
    y = ((X[:, 0] + X[:, 1] * X[:, 2] > 0) ^ (rng.random(900) < 0.2))
    rf = RandomForestClassifier(n_estimators=n_estimators,
                                random_state=0).fit(X[:700], y[:700])
    return rf, X[700:]


def test_full_depth_forest_served_through_streamed_match(monkeypatch):
    """A full-depth forest whose banks take the streamed banked form is
    served by ``TCAMServer`` equal to the oracle and to scikit-learn in
    every field."""
    import repro.kernels.ops as kops
    rf, X = _deep_rf()
    forest = compile_forest(rf, s=8)
    assert min(lay.n_cwd for lay in forest.layouts) >= 16
    traced = []
    stream = kops.tcam_match_banked_stream

    def spy(*args):
        traced.append(args[1].shape)
        return stream(*args)

    monkeypatch.setattr(kops, "ONE_SHOT_BYTES", 0)
    monkeypatch.setattr(kops, "tcam_match_banked_stream", spy)
    jax.clear_caches()
    srv = repro.TCAMServer(forest, config=repro.ServeConfig(
        max_batch=32, min_bucket=8, background=False))
    results = srv.serve(X)
    jax.clear_caches()
    groups = srv._fx.plan.groups
    assert {(g.n_banks, g.r_pad, g.width) for g in groups} <= set(traced)
    got = _fields(results)
    _assert_fields_equal(got, _ref_fields(forest, X))
    np.testing.assert_array_equal(got["prediction"], rf.predict(X))


def test_ten_tree_share_is_the_first_ten_trees():
    """A 10-tree fit with ``random_state=0`` holds the first ten trees of
    the 20-tree fit, and the two 10-tree shares' per-bank soft-vote sums
    add up to the 20-tree forest's."""
    import copy
    rf20, X = _deep_rf(20)
    rf10, _ = _deep_rf(10)
    for a, b in zip(rf10.estimators_, rf20.estimators_[:10]):
        for k in ("feature", "threshold", "children_left", "children_right",
                  "value"):
            np.testing.assert_array_equal(getattr(a.tree_, k),
                                          getattr(b.tree_, k))
    rest = copy.copy(rf20)
    rest.estimators_ = rf20.estimators_[10:]

    def vote_sum(model):
        forest = compile_forest(model, s=32)
        res = repro.ForestExecutor(forest).infer(X)
        return sum(bank.proba[row]
                   for bank, row in zip(forest.banks, res.survivors))

    first, second = vote_sum(rf10), vote_sum(rest)
    np.testing.assert_allclose(first + second, vote_sum(rf20), rtol=0,
                               atol=1e-12)
    assert not np.array_equal(first, second)


def test_plan_id_hashes_the_grids_in_place():
    """``plan_forest`` hashes each stacked grid through its buffer: the
    same ``plan_id`` as a digest of the grids' bytes copied out."""
    import hashlib
    forest, _ = _rf("cancer", "soft", n_estimators=5, max_depth=6)
    plan = plan_forest(forest)
    digest = hashlib.sha1()
    for grp in plan.groups:
        digest.update(grp.cells.tobytes())
        digest.update(np.asarray(grp.bank_ids, np.int64).tobytes())
    for lay in forest.layouts:
        digest.update(lay.classes.tobytes())
    assert plan.plan_id == digest.hexdigest()[:12]
