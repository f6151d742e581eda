"""The roofline's work is counted from the deployment, not from how an
engine places it: the same for the packed, mxu and banked placements."""
import pytest

import data
import deploy
import roofline


@pytest.fixture(scope="module")
def tree():
    Xtr, ytr, _, _ = data.load_split("credit")
    cfg = {"fit": {"max_depth": 10, "max_leaves": 200}, "s": 32}
    kind = deploy.kind_module("tree")
    t = kind.fit(cfg, Xtr, ytr)
    return kind.compile(t, cfg), kind.reference(t, cfg)


def test_same_work_for_every_placement(tree):
    import repro
    compiled, ref = tree
    forest = repro.compile_forest([compiled.tree], s=compiled.layout.s)
    banks = [(b.rows, b.cols) for b in ref.banks]
    work = []
    for served, engine in ((compiled, "packed"), (compiled, "mxu"),
                           (forest, "banked")):
        cfg = repro.ServeConfig(engine=engine, background=False)
        with repro.TCAMServer(served, config=cfg) as server:
            assert server.engine == engine
        work.append(roofline.match_work(banks, 256))
    assert work[0] == work[1] == work[2]
    lay = compiled.layout
    assert banks == [(lay.n_rows, lay.width + 1)]
    nbytes, ops = work[0]
    assert ops == 2 * 256 * lay.n_rows * (lay.width + 1)
    cols = lay.width + 1
    assert nbytes == lay.n_rows * cols / 4 + 256 * cols / 8


def test_least_time_uses_the_larger_bound():
    pk = roofline.peak("TPU v5 lite")
    banks = [(8476, 4938)]
    nbytes, ops = roofline.match_work(banks, 256)
    assert roofline.least_seconds(banks, 256, pk) == pytest.approx(
        ops / 393e12)
    assert ops / 393e12 > nbytes / 819e9


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peak("cpu")
