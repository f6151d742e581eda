"""The reader of ``match_bytes_per_decision`` gives None without its
field and Σ bytes over Σ requests with it, and a tiny traced run of
``credit-rf100-shard10.batch``, its group matched by the streamed banked
form, is correct and reports every reader the CPU can feed."""
import types

import numpy as np
import pytest

import deploy
import run
import tiny

WORKLOAD = "credit-rf100-shard10.batch"
READER = "match_bytes_per_decision"


@pytest.fixture(autouse=True)
def cache(tmp_path, monkeypatch):
    monkeypatch.setattr(deploy, "CACHE", tmp_path / "cache")


def _run(batches):
    return types.SimpleNamespace(
        window=types.SimpleNamespace(t0=10.0, t_end=30.0), batches=batches,
        trace=None, trace_host_bounds=None)


def _records(fields):
    rec = np.zeros(3, [("batch", "i8"), ("t_form", "f8"), ("n", "i8")]
                   + [(f, "i8") for f in fields])
    rec["batch"] = np.arange(3)
    rec["t_form"] = [11.0, 12.0, 13.0]
    rec["n"] = [8, 8, 4]
    for f in fields:
        rec[f] = 1000
    return rec


def _read(r):
    return deploy.load_module(run.reader_path(READER)).read(r)


def test_reader_without_its_field_is_none():
    assert _read(_run(None)) is None
    assert _read(_run(_records(["h2d_bytes", "match_cells"]))) is None


def test_reader_on_records():
    assert _read(_run(_records(["match_bytes"]))) == pytest.approx(150.0)


def test_tiny_traced_run_streams_and_reports_every_reader(monkeypatch):
    import jax

    import repro.kernels.ops as kops
    monkeypatch.setattr(kops, "ONE_SHOT_BYTES", 0)
    jax.clear_caches()
    cell = tiny.tiny_cell(WORKLOAD, {"loop": "closed", "in_flight": 32},
                          trace=True)
    try:
        result, numbers, _ = tiny.run_tiny(cell, trace=True, seconds=2.0)
    finally:
        jax.clear_caches()
    assert result["correct"], numbers
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert {m["name"] for m in cell.metrics} - {
        "device_idle_share", "match_roofline"} <= set(got)
    assert got[READER] > 0
