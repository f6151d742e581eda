"""The readers of the program's batch records (``spans.py``): exact values
on hand-made records, nothing without records, and a tiny traced run of
each cell that reports every one of them."""
import types

import numpy as np
import pytest

import deploy
import run
import spans
import tiny

READERS = ("encode_ms.p50.batch", "d2h_ms.p50.batch", "finalize_ms.p50.batch",
           "resolve_ms.p50.batch", "h2d_bytes_per_decision",
           "d2h_bytes_per_decision", "idle_waiting_share")
PHASES = ("batch", "encode", "dispatch", "device_wait", "d2h", "finalize",
          "resolve")


def _records(rows):
    """Records from (t_form, n, batch_s, encode_s, h2d_bytes) rows; the
    other spans are a tenth of the encode span, d2h bytes 16 per row."""
    dt = [("batch", "i8"), ("t_form", "f8"), ("n", "i8"), ("bucket", "i8")] \
        + [(f"{p}_s", "f8") for p in PHASES] \
        + [("h2d_bytes", "i8"), ("d2h_bytes", "i8")]
    rec = np.zeros(len(rows), dt)
    for i, (t, n, batch_s, enc, h2d) in enumerate(rows):
        rec[i] = (i, t, n, 8, batch_s, enc, enc / 10, enc / 10, enc / 10,
                  enc / 10, enc / 10, h2d, 16 * n)
    return rec


def _run(batches, traced=True):
    window = types.SimpleNamespace(t0=10.0, t_end=30.0)
    return types.SimpleNamespace(
        window=window, batches=batches,
        trace=object() if traced else None,
        trace_host_bounds=(20.0, 24.0) if traced else None)


def _read(name, r):
    return deploy.load_module(run.reader_path(name)).read(r)


# formed before the window, three inside it before the profiler, two in
# the traced slice [20, 24] (one running past its end), one after it
ROWS = [(9.0, 8, 0.5, 9.0, 800),
        (11.0, 4, 1.0, 0.002, 400), (12.0, 8, 1.0, 0.004, 640),
        (19.5, 2, 1.0, 0.001, 200),
        (21.0, 8, 1.0, 0.1, 800), (23.5, 8, 1.0, 0.1, 800),
        (26.0, 8, 1.0, 0.1, 800)]


@pytest.mark.parametrize("name,want", [
    ("encode_ms.p50.batch", 2.0),          # nearest rank of 1, 2, 4 ms
    ("d2h_ms.p50.batch", 0.2),
    ("finalize_ms.p50.batch", 0.2),
    ("resolve_ms.p50.batch", 0.2),
    ("h2d_bytes_per_decision", 1240 / 14),
    ("d2h_bytes_per_decision", 16.0),
    # [20, 24] holds 0.5 s of the batch from 19.5, 1 s from 21, 0.5 s from
    # 23.5: 2 of 4 s
    ("idle_waiting_share", 50.0),
])
def test_reader_on_records(name, want):
    assert _read(name, _run(_records(ROWS))) == pytest.approx(want)


def test_untraced_cut_is_the_window():
    r = _run(_records(ROWS), traced=False)
    assert spans.unprofiled(r)["batch"].tolist() == [1, 2, 3, 4, 5, 6]
    assert _read("idle_waiting_share", r) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_without_records_is_none(name):
    assert _read(name, _run(None)) is None


@pytest.mark.parametrize("name", READERS[:-1])
def test_reader_without_batches_in_the_cut_is_none(name):
    assert _read(name, _run(_records(ROWS[:1]))) is None


def test_records_found_up_the_stack():
    store = types.SimpleNamespace(batch_records=lambda: "records")
    server = types.SimpleNamespace(metrics_store=store)   # noqa: F841
    r = types.SimpleNamespace()
    assert spans.records(r) == "records"
    old = types.SimpleNamespace(metrics_store=object())   # a program without

    def caller(server):
        return spans.records(r)
    assert caller(old) is None


@pytest.fixture
def cache(tmp_path, monkeypatch):
    monkeypatch.setattr(deploy, "CACHE", tmp_path / "cache")


@pytest.mark.parametrize("workload", ["credit-tree.batch",
                                      "covid-rf100.batch"])
def test_tiny_traced_run_reports_every_reader(workload, cache):
    import repro
    cell = tiny.tiny_cell(workload, {"loop": "closed", "in_flight": 32},
                          True)
    result, numbers, _ = tiny.run_tiny(cell, trace=True, seconds=2.0)
    assert result["correct"], numbers
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(READERS) <= set(got)
    # bytes from the shapes: padded rows only raise them
    server = repro.TCAMServer(deploy.build(cell.config).compiled,
                              config=repro.ServeConfig(background=False))
    if workload.startswith("credit"):
        lay = server._layout
        h2d, d2h = lay.n_cwd * lay.s, 16
    else:
        groups = server._f_plan.groups
        h2d = sum(g.n_banks * g.width for g in groups)
        d2h = sum(8 * g.n_banks * g.r_pad for g in groups)
    server.close()
    assert got["h2d_bytes_per_decision"] >= h2d
    assert got["d2h_bytes_per_decision"] >= d2h
    assert got["d2h_bytes_per_decision"] / d2h == pytest.approx(
        got["h2d_bytes_per_decision"] / h2d)
    assert 0 <= got["idle_waiting_share"] <= 100
