"""The boosted reference (``reference_boosted.py``) agrees with scikit-learn
and with the program, its float32 control is refused, the readers of the
vote span and of the plan's padding give None without their fields, and a
tiny traced run of ``credit-hgb500.batch`` reports every reader."""
import json
import types

import numpy as np
import pytest

import check
import control
import data
import deploy
import run
import tiny

WORKLOAD = "credit-hgb500.batch"
NEW_READERS = ("vote_ms.p50.batch", "match_pad_share")


@pytest.fixture(autouse=True)
def cache(tmp_path, monkeypatch):
    monkeypatch.setattr(deploy, "CACHE", tmp_path / "cache")


def tiny_config(**fit) -> dict:
    """``credit-hgb500`` cut to a size the CPU serves quickly."""
    cfg = json.loads(
        (run.ROOT / "bench" / "configs" / "credit-hgb500.json").read_text())
    cfg["fit"] = {**cfg["fit"], "max_iter": 6, "max_depth": 3, **fit}
    cfg["s"] = 32
    cfg["check_sample"] = 64
    dep = deploy.build(cfg, check_digest=False)
    cfg["digest"] = deploy.kind_module(cfg["kind"]).digest(dep.model)
    return cfg


def tiny_cell(trace: bool) -> run.Cell:
    full = run.load_cell(WORKLOAD, trace)
    return run.Cell(name=WORKLOAD, chips=1, config=tiny_config(),
                    traffic={"loop": "closed", "in_flight": 32},
                    metrics=full.metrics)


@pytest.mark.parametrize("dataset", ["credit", "covid"])
def test_matches_sklearn_and_the_program(dataset):
    import repro
    from repro.forest import forest_infer_ref
    Xtr, ytr, Xte, _ = data.load_split(dataset)
    cfg = {"fit": {"max_iter": 8, "max_depth": 4, "random_state": 0},
           "s": 64}
    kind = deploy.kind_module("sklearn_hgb")
    model = kind.fit(cfg, Xtr[:5000], ytr[:5000])
    forest = kind.compile(model, cfg)
    ref = kind.reference(model, cfg)
    X = Xte[:300]
    got = ref.answers(X)
    np.testing.assert_array_equal(got["prediction"], model.predict(X))
    np.testing.assert_array_equal(ref.margin(X).ravel(),
                                  model.decision_function(X))
    np.testing.assert_array_equal(ref.decide(ref.margin(X)),
                                  model.predict(X))
    r = forest_infer_ref(forest, X)
    active = r.active_evals.sum(axis=0)
    hw = repro.DEFAULT_HW
    np.testing.assert_array_equal(got["prediction"], r.predictions)
    np.testing.assert_array_equal(got["survivor"], -1)
    np.testing.assert_array_equal(got["n_survivors"],
                                  (r.n_survivors > 0).sum(axis=0))
    np.testing.assert_array_equal(got["active_evals"], active)
    np.testing.assert_array_equal(
        got["energy_j"],
        active.astype(np.float64) * hw.e_row + forest.n_banks * hw.e_mem)
    assert [(b.rows, b.cols) for b in ref.banks] == [
        (b.layout.n_rows, b.layout.width + 1) for b in forest.banks]


def test_control_is_refused():
    import jax
    cell = tiny_cell(False)
    rows = control.control_numbers(cell, [5, 6, 7], 1.0, jax.devices()[:1])
    for r in rows:
        assert r["program_correct"], r
        assert not r["control_correct"], r
        assert r["control"]["wrong_energy_j"] > 0
        assert set(r["control"]) == set(r["program"]) \
            == {"checked", *check.LIMITS}


def _records(fields):
    dt = [("batch", "i8"), ("t_form", "f8"), ("n", "i8")] + [
        (f, "f8") for f in fields]
    rec = np.zeros(3, dt)
    rec["batch"] = np.arange(3)
    rec["t_form"] = [11.0, 12.0, 13.0]
    rec["n"] = 8
    for f in fields:
        rec[f] = [1.0, 2.0, 4.0]
    return rec


def _run(batches):
    return types.SimpleNamespace(
        window=types.SimpleNamespace(t0=10.0, t_end=30.0), batches=batches,
        trace=None, trace_host_bounds=None)


def _read(name, r):
    return deploy.load_module(run.reader_path(name)).read(r)


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_without_its_fields_is_none(name):
    assert _read(name, _run(None)) is None
    # a program that records spans and bytes but no vote or cells
    assert _read(name, _run(_records(["batch_s", "finalize_s"]))) is None


@pytest.mark.parametrize("name,want", [
    ("vote_ms.p50.batch", 2000.0),
    # lut cells 1 + 2 + 4 of match cells 4 + 8 + 16
    ("match_pad_share", 75.0),
])
def test_new_reader_on_records(name, want):
    rec = _records(["vote_s", "match_cells", "lut_cells"])
    rec["match_cells"] *= 4
    assert _read(name, _run(rec)) == pytest.approx(want)


def test_tiny_traced_run_reports_every_reader():
    cell = tiny_cell(True)
    result, numbers, _ = tiny.run_tiny(cell, trace=True, seconds=2.0)
    assert result["correct"], numbers
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert {m["name"] for m in cell.metrics} - {
        "device_idle_share", "match_roofline"} <= set(got)
    assert set(NEW_READERS) <= set(got)
    assert 0 < got["match_pad_share"] < 100
    assert got["vote_ms.p50.batch"] > 0
