"""The trace reduction: on a hand-made trace, and on a small trace recorded
on the chip (``record_trace.py``)."""
from pathlib import Path

import pytest

import tracing

DATA = Path(__file__).resolve().parent / "data"


def _trace(ops, host):
    return tracing.Trace({
        "/device:TPU:0": {tracing.OPS_LINE: ops},
        "/host:CPU": {"python3": [(tracing.SLICE, 0, 100)],
                      "python3#1": host},
    })


def test_busy_gaps_and_names():
    ops = [("%a.1 = f32[] add(x)", 0, 10), ("%a.1 = f32[] add(x)", 5, 15),
           ("%b = f32[] mul(x)", 30, 10), ("%c = f32[] neg(x)", 95, 20)]
    host = [("$engine.py:672 _process", 0, 60),
            ("$encode.py:111 encode_inputs", 22, 6),
            ("$threading.py:323 wait", 60, 40)]
    s = tracing.reduce(_trace(ops, host), chips=1)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(35e-9)        # [0,20] [30,40] [95,100]
    assert s.idle_share == pytest.approx(0.65)
    assert s.device_ops[0] == ["a.1", pytest.approx(25e-9)]
    assert [g[1] for g in s.idle_gaps] == pytest.approx([55e-9, 10e-9])
    assert s.idle_gaps[0][0] == "threading.py:323 wait"
    assert s.idle_gaps[1][0] == \
        "engine.py:672 _process > encode.py:111 encode_inputs"


def test_no_device_plane_has_no_idle_share():
    s = tracing.reduce(_trace([], [("$engine.py:672 _process", 0, 9)]), 1)
    assert s.busy_s == 0 and s.idle_share is None


def test_recorded_chip_trace():
    path = DATA / "credit-tree.batch.trace.json.gz"
    s = tracing.reduce(tracing.Trace.from_json(str(path)), chips=1)
    assert 0.1 < s.window_s < 0.3
    assert 0 < s.busy_s < s.window_s
    assert any("tcam_match_packed_pallas" in name for name, _ in s.device_ops)
    assert s.device_ops[0][1] <= s.busy_s
    assert len(s.idle_gaps) == tracing.TOP
    assert all(name != "no host frame" for name, _ in s.idle_gaps[:3])
