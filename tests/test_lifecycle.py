"""Model lifecycle subsystem: registry round-trips, delta reprogramming at
write-pulse resolution, endurance/wear-leveling, and zero-downtime shadow
promotion on the serving engine."""
import threading

import numpy as np
import pytest

import repro
from repro.core import (
    CELL_1,
    CELL_X,
    DEFAULT_HW,
    DT2CAM,
    FeatureMismatch,
    HardwareParams,
    NonIdealSpec,
    encode_inputs,
    simulate,
    write_energy,
)
from repro.dt import load_split
from repro.lifecycle import (
    LifecycleManager,
    ModelRegistry,
    WearTracker,
    content_hash,
    plan_delta,
    plan_forest_delta,
    plan_full,
    wear_level_rows,
)
from repro.serve import ServeConfig, TCAMServer


@pytest.fixture(scope="module")
def retrained_pair():
    """v1 on clean iris, v2 retrained on noise-perturbed features."""
    Xtr, ytr, Xte, yte = load_split("iris")
    rng = np.random.default_rng(7)
    Xtr2 = Xtr + rng.normal(0, 1, Xtr.shape) * 0.1 * Xtr.std(0, keepdims=True)
    v1 = DT2CAM(s=16, max_depth=5).fit(Xtr, ytr)
    v2 = DT2CAM(s=16, max_depth=5).fit(Xtr2, ytr)
    return v1, v2, (Xtr, ytr, Xte, yte)


def _sync_cfg(**kw) -> ServeConfig:
    base = dict(background=False, engine="ref", max_batch=16, min_bucket=8)
    base.update(kw)
    return ServeConfig(**base)


# --------------------------------------------------------------------------
# registry: content addressing, round-trip, lineage
# --------------------------------------------------------------------------
def test_registry_tree_round_trip_and_idempotence(retrained_pair, tmp_path):
    v1, v2, (Xtr, ytr, Xte, _) = retrained_pair
    reg = ModelRegistry(tmp_path / "reg")
    r1 = reg.publish(v1.compiled, "iris", metadata={"gen": 1})
    r2 = reg.publish(v2.compiled, "iris", parents=[r1.version_id])
    assert len(reg) == 2 and r1.version_id in reg

    # idempotent: identical content maps to the same version
    again = reg.publish(v1.compiled, "iris")
    assert again.version_id == r1.version_id and len(reg) == 2

    # round-trip exact: every array, and the content hash, survive
    loaded = reg.load(r1.version_id)
    c = v1.compiled
    np.testing.assert_array_equal(loaded.layout.cells, c.layout.cells)
    np.testing.assert_array_equal(loaded.layout.class_bits,
                                  c.layout.class_bits)
    np.testing.assert_array_equal(loaded.tree.feature, c.tree.feature)
    np.testing.assert_array_equal(loaded.table.th1, c.table.th1)
    assert len(loaded.lut.thresholds) == len(c.lut.thresholds)
    for a, b in zip(loaded.lut.thresholds, c.lut.thresholds):
        np.testing.assert_array_equal(a, b)
    assert content_hash(loaded) == r1.content_hash
    # the reloaded model predicts identically
    xb = encode_inputs(loaded.lut, Xte)
    np.testing.assert_array_equal(
        simulate(loaded.layout, xb).predictions,
        simulate(c.layout, encode_inputs(c.lut, Xte)).predictions,
    )

    # index survives a fresh registry instance (JSON persistence)
    reg2 = ModelRegistry(tmp_path / "reg")
    assert len(reg2) == 2
    assert reg2.latest("iris").version_id == r2.version_id
    lineage = reg2.lineage(r2.version_id)
    assert [v.version_id for v in lineage] == [r2.version_id, r1.version_id]


def test_registry_forest_round_trip(tmp_path):
    Xtr, ytr, Xte, _ = load_split("iris")
    trees = repro.train_forest(Xtr, ytr, n_trees=3, max_depth=4, seed=0)
    forest = repro.compile_forest(trees, s=16)
    reg = ModelRegistry(tmp_path / "reg")
    rv = reg.publish(forest, "grove")
    assert rv.kind == "forest" and rv.n_banks == 3

    loaded = reg.load(rv.version_id)
    assert loaded.n_banks == 3 and loaded.vote == forest.vote
    for lb, fb in zip(loaded.banks, forest.banks):
        np.testing.assert_array_equal(lb.layout.cells, fb.layout.cells)
        assert (lb.proba is None) == (fb.proba is None)
    np.testing.assert_array_equal(
        repro.forest_infer_ref(loaded, Xte).predictions,
        repro.forest_infer_ref(forest, Xte).predictions,
    )
    assert content_hash(loaded) == rv.content_hash


@pytest.mark.parametrize("case", ["binary", "multiclass"])
def test_registry_boosted_forest_round_trip(tmp_path, case):
    """A boosted forest keeps its leaf values, columns, baseline and link
    through the registry, and answers as before, margin and score too."""
    from hgb_cases import hgb_case
    model, X = hgb_case(case)
    forest = repro.compile_forest(model, s=32)
    reg = ModelRegistry(tmp_path / "reg")
    rv = reg.publish(forest, "boosted")
    loaded = reg.load(rv.version_id)
    assert loaded.vote == "margin" and loaded.link == forest.link
    np.testing.assert_array_equal(loaded.baseline, forest.baseline)
    for lb, fb in zip(loaded.banks, forest.banks):
        np.testing.assert_array_equal(lb.margin, fb.margin)
        assert lb.column == fb.column
    got, want = (repro.forest_infer_ref(f, X) for f in (loaded, forest))
    for k in ("predictions", "score", "margin"):
        np.testing.assert_array_equal(getattr(got, k), getattr(want, k))
    assert content_hash(loaded) == rv.content_hash


def test_registry_rejects_bad_names_and_unknown_refs(retrained_pair,
                                                     tmp_path):
    v1, _, _ = retrained_pair
    reg = ModelRegistry(tmp_path / "reg")
    with pytest.raises(ValueError, match="may not contain"):
        reg.publish(v1.compiled, "bad:name")
    with pytest.raises(KeyError, match="parent"):
        reg.publish(v1.compiled, "m", parents=["m:doesnotexist"])
    with pytest.raises(KeyError, match="unknown version"):
        reg.load("m:doesnotexist")
    with pytest.raises(KeyError, match="no versions"):
        reg.latest("m")


# --------------------------------------------------------------------------
# delta planner: pulse maps, apply-verification, delta < full
# --------------------------------------------------------------------------
def test_plan_delta_reproduces_target_and_beats_full(retrained_pair):
    v1, v2, _ = retrained_pair
    o, n = v1.compiled.layout, v2.compiled.layout
    d = plan_delta(o.cells, n.cells, old_class_bits=o.class_bits,
                   new_class_bits=n.class_bits)
    f = plan_full(o.cells, n.cells, old_class_bits=o.class_bits,
                  new_class_bits=n.class_bits)
    # the acceptance criterion: strictly fewer cells written on a retrain
    assert 0 < d.n_cells_written < f.n_cells_written
    assert f.n_cells_written == f.shape[0] * f.shape[1]
    # applying the delta to the live grid lands exactly on the target
    from repro.lifecycle.delta import _pad_grid
    np.testing.assert_array_equal(d.apply(o.cells),
                                  _pad_grid(n.cells, d.shape))
    np.testing.assert_array_equal(f.apply(o.cells),
                                  _pad_grid(n.cells, f.shape))
    # pulse accounting: every changed cell needs 1..2 element pulses
    pulses = d.set_map + d.reset_map
    assert (pulses[d.rows, d.cols] >= 1).all()
    assert int((pulses > 0).sum()) == d.n_cells_changed
    assert d.n_pulses < f.n_pulses


def test_plan_delta_identical_grids_is_empty():
    cells = np.full((4, 8), CELL_X, np.int8)
    cells[:, 0] = CELL_1
    d = plan_delta(cells, cells)
    assert d.n_cells_written == 0 and d.n_pulses == 0
    assert d.rows_touched == 0
    np.testing.assert_array_equal(d.apply(cells), cells)


def test_plan_delta_aligns_mismatched_shapes():
    small = np.full((2, 4), CELL_1, np.int8)
    big = np.full((4, 6), CELL_X, np.int8)
    d = plan_delta(small, big)
    assert d.shape == (4, 6)
    # the 8 previously-programmed cells are released (RESET of element R1)
    assert d.n_cells_written == 8 and d.n_set == 0 and d.n_reset == 8


def test_write_energy_and_figures_model():
    hw = HardwareParams(e_set=2e-12, e_reset=3e-12, t_prog=5e-9,
                        endurance_writes=100.0)
    assert write_energy(10, 4, hw) == pytest.approx(10 * 2e-12 + 4 * 3e-12)
    cells = np.full((2, 4), CELL_X, np.int8)
    target = cells.copy()
    target[0, 1] = CELL_1            # 1 SET
    target[1, 2] = CELL_1            # 1 SET
    d = plan_delta(cells, target)
    figs = d.figures(hw)
    assert figs["set_pulses"] == 2 and figs["reset_pulses"] == 0
    assert figs["energy_j"] == pytest.approx(2 * 2e-12)
    assert figs["time_s"] == pytest.approx(2 * 5e-9)
    assert figs["endurance_cycles_consumed"] == 2


def test_plan_forest_delta_handles_added_and_retired_banks():
    Xtr, ytr, _, _ = load_split("iris")
    trees = repro.train_forest(Xtr, ytr, n_trees=3, max_depth=4, seed=1)
    f2 = repro.compile_forest(trees[:2], s=16)
    f3 = repro.compile_forest(trees, s=16)

    plans = plan_forest_delta(f2, f3)
    assert len(plans) == 3
    # bank 2 is new: programmed from an erased array -> SET-only cell pulses
    assert plans[2].n_reset == 0 and plans[2].n_set > 0
    # shrinking retires bank 2: erased back to CELL_X -> RESET-only
    back = plan_forest_delta(f3, f2)
    assert back[2].n_set == 0 and back[2].n_reset > 0
    full_plans = plan_forest_delta(f2, f3, full=True)
    assert all(p.kind == "full" for p in full_plans)


# --------------------------------------------------------------------------
# wear: endurance ledger + wear-leveling row placement
# --------------------------------------------------------------------------
def test_wear_tracker_accumulates_and_flags_worn_cells():
    hw = HardwareParams(endurance_writes=3.0)
    w = WearTracker(hw=hw)
    a = np.full((2, 4), CELL_X, np.int8)
    b = a.copy()
    b[0, 1] = CELL_1
    there, back = plan_delta(a, b), plan_delta(b, a)
    for _ in range(2):               # two full program/erase cycles
        w.record(there)
        w.record(back)
    assert w.plans_recorded == 4
    assert w.total_pulses == 4 and w.max_cell_pulses == 4
    assert w.headroom() < 0          # past rated endurance
    assert w.worn_out()[0, 1] and w.worn_out().sum() == 1
    np.testing.assert_array_equal(w.worn_rows(), [0])
    snap = w.snapshot()
    assert snap["worn_cells"] == 1 and snap["endurance_writes"] == 3.0
    # grids grow automatically to the largest plan seen
    w.record(plan_delta(np.full((5, 9), CELL_X, np.int8),
                        np.full((5, 9), CELL_1, np.int8)))
    assert w.counts.shape == (5, 9)


def test_wear_level_rows_functional_equivalence(retrained_pair):
    v1, v2, (Xtr, _, Xte, _) = retrained_pair
    w = WearTracker()
    w.record(plan_full(np.zeros((0, 0), np.int8), v1.compiled.layout.cells))
    rm = wear_level_rows(v2.compiled.layout, v1.compiled.layout.cells, w)
    # same predictions, physically re-placed rows
    xb = encode_inputs(v2.compiled.lut, Xte)
    np.testing.assert_array_equal(
        simulate(rm.layout, xb).predictions,
        simulate(v2.compiled.layout, xb).predictions,
    )
    assert rm.row_map.shape[0] == v2.compiled.layout.n_rows
    assert len(np.unique(rm.row_map)) == rm.row_map.shape[0]  # injective


def test_wear_level_rows_respects_forbidden_rows(retrained_pair):
    v1, v2, (Xtr, _, Xte, _) = retrained_pair
    forbidden = [0, 3]
    rm = wear_level_rows(v2.compiled.layout, v1.compiled.layout.cells,
                         forbidden=forbidden)
    assert not set(forbidden) & set(rm.row_map.tolist())
    # forbidden rows carry a dead intent: decoder cell '1' mismatches all
    assert (rm.layout.cells[forbidden, 0] == CELL_1).all()
    xb = encode_inputs(v2.compiled.lut, Xte)
    np.testing.assert_array_equal(
        simulate(rm.layout, xb).predictions,
        simulate(v2.compiled.layout, xb).predictions,
    )
    with pytest.raises(ValueError, match="out of range"):
        wear_level_rows(v2.compiled.layout, v1.compiled.layout.cells,
                        forbidden=[10_000])
    n_phys = v2.compiled.layout.cells.shape[0]
    with pytest.raises(ValueError, match="cannot place"):
        wear_level_rows(v2.compiled.layout, v1.compiled.layout.cells,
                        forbidden=np.arange(n_phys))


def test_wear_level_composes_with_spare_row_repair():
    """The repair report's blocked_rows feed straight into the remapper."""
    from repro.core import apply_saf_mask, sample_saf
    from repro.reliability import repair_layout, run_bist
    import dataclasses as dc

    Xtr, ytr, Xte, _ = load_split("iris")
    c = repro.compile_tree(repro.train_tree(Xtr, ytr, max_depth=5),
                           16, spare_rows=12)
    lay = c.layout
    rng = np.random.default_rng(3)
    mask = sample_saf(lay.cells.shape, 0.03, 0.03, rng)
    faulty = dc.replace(lay, cells=apply_saf_mask(lay.cells, mask))
    bist = run_bist(faulty.cells, lay.cells, used=1 + lay.width,
                    n_rows=lay.cells.shape[0])
    _, _, report = repair_layout(faulty, lay.cells, mask,
                                 bist.defective_rows)
    blocked = report.blocked_rows
    assert blocked.size > 0
    rm = wear_level_rows(lay, lay.cells, forbidden=blocked)
    assert not set(blocked.tolist()) & set(rm.row_map.tolist())


# --------------------------------------------------------------------------
# serving: shadow slot, promotion gates, atomic swap, rollback
# --------------------------------------------------------------------------
def test_stage_mirror_promote_and_bit_exactness(retrained_pair):
    v1, v2, (Xtr, _, Xte, _) = retrained_pair
    srv = TCAMServer(v1.compiled, config=_sync_cfg())
    srv.stage(v2.compiled, mirror_fraction=1.0)
    assert srv.staged and srv.health()["candidate_staged"]

    n = len(Xte[:16])
    srv.submit_many(Xte[:n])
    srv.pump(force=True)
    lc = srv.metrics()["lifecycle"]
    assert lc["stages"] == 1
    assert lc["shadow_batches"] == 1 and lc["shadow_requests"] == n

    rep = srv.promote(min_shadow_batches=1, max_disagreement=1.0)
    assert rep.promoted and rep.reason == "promoted" and not srv.staged
    assert rep.canary_accuracy >= srv._config.canary_threshold
    assert srv.metrics()["lifecycle"]["promotions"] == 1

    # the promoted model is bit-exact against v2's reference sim path
    res = srv.serve(Xte)
    ref = simulate(v2.compiled.layout,
                   encode_inputs(v2.compiled.lut, Xte)).predictions
    np.testing.assert_array_equal([r.prediction for r in res], ref)
    srv.close()


def test_mirror_fraction_is_deterministic(retrained_pair):
    v1, v2, (_, _, Xte, _) = retrained_pair
    srv = TCAMServer(v1.compiled, config=_sync_cfg(max_batch=8))
    srv.stage(v2.compiled, mirror_fraction=0.25)
    for _ in range(8):               # 8 live batches -> exactly 2 mirrored
        srv.submit_many(Xte[:8])
        srv.pump(force=True)
    lc = srv.metrics()["lifecycle"]
    assert lc["shadow_batches"] == 2
    assert lc["shadow_requests"] == 16
    srv.close()


def test_promote_gate_insufficient_shadow_keeps_candidate(retrained_pair):
    v1, v2, (_, _, Xte, _) = retrained_pair
    srv = TCAMServer(v1.compiled, config=_sync_cfg())
    srv.stage(v2.compiled, mirror_fraction=1.0)
    rep = srv.promote(min_shadow_batches=3)
    assert not rep.promoted and rep.reason == "insufficient_shadow"
    assert rep.staged and srv.staged          # still in the shadow slot
    assert srv.metrics()["lifecycle"]["promotion_failures"] == 0
    srv.close()


def test_promote_gate_disagreement_unstages(retrained_pair):
    v1, v2, (Xtr, _, Xte, _) = retrained_pair
    # v1 vs v2 genuinely disagree on some iris test rows; find them so the
    # gate deterministically sees drift
    p1 = simulate(v1.compiled.layout,
                  encode_inputs(v1.compiled.lut, Xte)).predictions
    p2 = simulate(v2.compiled.layout,
                  encode_inputs(v2.compiled.lut, Xte)).predictions
    drift = np.flatnonzero(p1 != p2)
    assert drift.size > 0, "fixture models must disagree somewhere"

    srv = TCAMServer(v1.compiled, config=_sync_cfg())
    srv.stage(v2.compiled, mirror_fraction=1.0)
    srv.submit_many(np.tile(Xte[drift], (2, 1))[:8])
    srv.pump(force=True)
    rep = srv.promote(max_disagreement=0.0)
    assert not rep.promoted and rep.reason == "disagreement"
    assert rep.disagreement_rate > 0.0
    assert not rep.staged and not srv.staged  # kicked out of the slot
    lc = srv.metrics()["lifecycle"]
    assert lc["promotion_failures"] == 1 and lc["promotions"] == 0
    # live model unchanged
    res = srv.serve(Xte[:8])
    np.testing.assert_array_equal([r.prediction for r in res], p1[:8])
    srv.close()


def test_promote_gate_candidate_canary_failure(retrained_pair):
    """A candidate staged onto badly faulty silicon fails its own canary and
    is rejected — the live model keeps serving."""
    v1, v2, (_, _, Xte, _) = retrained_pair
    srv = TCAMServer(
        v1.compiled,
        config=_sync_cfg(canary_threshold=0.99),
        nonideal=NonIdealSpec(p_sa0=0.10, p_sa1=0.10),
        rng=np.random.default_rng(11),
    )
    srv.stage(v2.compiled, mirror_fraction=1.0)
    srv.submit_many(Xte[:16])
    srv.pump(force=True)
    rep = srv.promote(min_shadow_batches=1, max_disagreement=1.0)
    assert not rep.promoted and rep.reason == "canary"
    assert rep.canary_accuracy < 0.99
    assert not srv.staged
    assert srv.metrics()["lifecycle"]["promotion_failures"] == 1
    srv.close()


def test_rollback_unstages_then_reverts(retrained_pair):
    v1, v2, (_, _, Xte, _) = retrained_pair
    srv = TCAMServer(v1.compiled, config=_sync_cfg())
    with pytest.raises(RuntimeError, match="nothing to roll back"):
        srv.rollback()

    srv.stage(v2.compiled, mirror_fraction=1.0)
    assert srv.rollback() == "unstaged" and not srv.staged

    srv.stage(v2.compiled, mirror_fraction=1.0)
    srv.submit_many(Xte[:16])
    srv.pump(force=True)
    assert srv.promote(max_disagreement=1.0).promoted
    assert srv.rollback() == "reverted"       # back on v1
    res = srv.serve(Xte)
    ref = simulate(v1.compiled.layout,
                   encode_inputs(v1.compiled.lut, Xte)).predictions
    np.testing.assert_array_equal([r.prediction for r in res], ref)
    assert srv.metrics()["lifecycle"]["rollbacks"] == 2
    srv.close()


def test_stage_validation_errors(retrained_pair):
    v1, v2, (Xtr, ytr, Xte, _) = retrained_pair
    srv = TCAMServer(v1.compiled, config=_sync_cfg())
    with pytest.raises(ValueError, match="mirror_fraction"):
        srv.stage(v2.compiled, mirror_fraction=0.0)
    wrong = DT2CAM(s=16, max_depth=3).fit(Xtr[:, :2], ytr)
    with pytest.raises(FeatureMismatch, match="candidate expects"):
        srv.stage(wrong.compiled)
    srv.stage(v2.compiled)
    with pytest.raises(RuntimeError, match="already staged"):
        srv.stage(v2.compiled)
    srv.close()

    trees = repro.train_forest(Xtr, ytr, n_trees=2, max_depth=3, seed=0)
    forest = repro.compile_forest(trees, s=16)
    fsrv = TCAMServer(forest, config=_sync_cfg())
    with pytest.raises(NotImplementedError, match="single-model only"):
        fsrv.stage(v2.compiled)
    with pytest.raises(RuntimeError, match="single-model only"):
        _ = fsrv.live_intent
    fsrv.close()


def test_stage_reuses_persistent_saf_mask(retrained_pair):
    """Same-shape candidate grids land on the same silicon: the persistent
    stuck-element mask carries over to the staged chip state."""
    v1, v2, _ = retrained_pair
    srv = TCAMServer(
        v1.compiled, config=_sync_cfg(),
        nonideal=NonIdealSpec(p_sa0=0.02, p_sa1=0.02),
        rng=np.random.default_rng(2),
    )
    assert v1.compiled.layout.cells.shape == v2.compiled.layout.cells.shape
    srv.stage(v2.compiled)
    assert srv._candidate.saf_mask is srv._saf_mask
    srv.close()


# --------------------------------------------------------------------------
# manager: registry -> plan -> shadow -> promote, with the wear ledger
# --------------------------------------------------------------------------
def test_manager_full_cycle(retrained_pair, tmp_path):
    v1, v2, (_, _, Xte, _) = retrained_pair
    reg = ModelRegistry(tmp_path / "reg")
    r1 = reg.publish(v1.compiled, "iris")
    r2 = reg.publish(v2.compiled, "iris", parents=[r1.version_id])

    srv = TCAMServer(v1.compiled, config=_sync_cfg())
    mgr = LifecycleManager(reg, srv, live_version=r1.version_id)
    assert mgr.live_version == r1.version_id
    assert mgr.wear.plans_recorded == 1       # initial full program

    plan = mgr.stage(r2.version_id, mirror_fraction=1.0)
    assert plan.kind == "delta" and srv.staged
    assert mgr.candidate_version == r2.version_id
    assert mgr.wear.plans_recorded == 2

    srv.submit_many(Xte[:16])
    srv.pump(force=True)
    rep = mgr.promote(min_shadow_batches=1, max_disagreement=1.0)
    assert rep.promoted
    assert mgr.live_version == r2.version_id
    assert mgr.candidate_version is None

    st = mgr.status()
    assert st["live_version"] == r2.version_id and not st["staged"]
    assert st["plans_executed"] == 2
    assert st["last_plan_figures"]["energy_j"] > 0
    assert st["wear"]["total_pulses"] > 0

    assert mgr.rollback() == "reverted"
    assert mgr.live_version == r1.version_id
    srv.close()


def test_manager_wear_leveled_stage_stays_functional(retrained_pair,
                                                     tmp_path):
    v1, v2, (_, _, Xte, _) = retrained_pair
    reg = ModelRegistry(tmp_path / "reg")
    r1 = reg.publish(v1.compiled, "iris")
    r2 = reg.publish(v2.compiled, "iris", parents=[r1.version_id])
    srv = TCAMServer(v1.compiled, config=_sync_cfg())
    mgr = LifecycleManager(reg, srv, live_version=r1.version_id)
    mgr.stage(r2.version_id, mirror_fraction=1.0, wear_level=True)
    srv.submit_many(Xte[:16])
    srv.pump(force=True)
    assert mgr.promote(max_disagreement=1.0).promoted
    # wear-leveled promotion still predicts exactly like v2's ideal path
    res = srv.serve(Xte)
    ref = simulate(v2.compiled.layout,
                   encode_inputs(v2.compiled.lut, Xte)).predictions
    np.testing.assert_array_equal([r.prediction for r in res], ref)
    srv.close()


def test_manager_requires_attachment(tmp_path, retrained_pair):
    v1, _, _ = retrained_pair
    reg = ModelRegistry(tmp_path / "reg")
    reg.publish(v1.compiled, "iris")
    mgr = LifecycleManager(reg)
    with pytest.raises(RuntimeError, match="no server attached"):
        mgr.stage("anything")
    with pytest.raises(ValueError, match="requires a server"):
        mgr.attach(None, "anything")


# --------------------------------------------------------------------------
# hot swap under live background load: zero dropped, zero errors
# --------------------------------------------------------------------------
def test_background_hot_swap_drops_nothing(retrained_pair):
    v1, v2, (_, _, Xte, _) = retrained_pair
    cfg = ServeConfig(engine="ref", max_batch=16, max_delay_s=0.001,
                      background=True)
    srv = TCAMServer(v1.compiled, config=cfg)
    rng = np.random.default_rng(0)
    idx = rng.integers(0, len(Xte), size=300)

    futs = []
    for i, x in enumerate(Xte[idx]):
        futs.append(srv.submit(x))
        if i == 100:
            srv.stage(v2.compiled, mirror_fraction=0.5)
        elif i == 200:
            # let the shadow slot see some mirrored batches first
            srv.drain(timeout=60.0)
            rep = srv.promote(min_shadow_batches=1, max_disagreement=1.0)
            assert rep.promoted, rep.reason
    srv.drain(timeout=60.0)

    assert all(f.done() for f in futs), "dropped requests across the swap"
    assert all(f.exception() is None for f in futs), "errored requests"
    p1 = simulate(v1.compiled.layout,
                  encode_inputs(v1.compiled.lut, Xte[idx])).predictions
    p2 = simulate(v2.compiled.layout,
                  encode_inputs(v2.compiled.lut, Xte[idx])).predictions
    served = np.array([f.result().prediction for f in futs])
    # every answer is bit-exact for the model generation that served it
    assert ((served == p1) | (served == p2)).all()
    lc = srv.metrics()["lifecycle"]
    assert lc["promotions"] == 1 and lc["shadow_batches"] >= 1
    srv.close()
