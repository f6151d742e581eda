"""The control: the reference computed in float32, in the program's place,
is refused, while the program's own answers pass."""
import numpy as np
import pytest

import check
import control
import deploy
import tiny


@pytest.fixture(autouse=True)
def cache(tmp_path, monkeypatch):
    monkeypatch.setattr(deploy, "CACHE", tmp_path / "cache")


@pytest.mark.parametrize("workload,traffic", [
    ("credit-tree.batch",
     {"loop": "open", "arrivals": "poisson", "rate_per_s": 200}),
    ("covid-rf100.batch", {"loop": "closed", "in_flight": 32}),
])
def test_control_is_refused(workload, traffic):
    import jax
    cell = tiny.tiny_cell(workload, traffic)
    rows = control.control_numbers(cell, [5, 6, 7], 1.0, jax.devices()[:1])
    for r in rows:
        assert r["program_correct"], r
        assert not r["control_correct"], r
        assert r["control"]["wrong_energy_j"] > 0
        assert set(r["control"]) == set(r["program"]) \
            == {"checked", *check.LIMITS}
    assert np.all([r["control"]["checked"] > 0 for r in rows])
