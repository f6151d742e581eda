"""A run whose timed path is broken underneath comes out not correct: an
answer altered where the program produces it.  The check samples every
request here, so one altered answer per batch is always among them."""
import pytest

import deploy
import tiny

ALL = 1 << 30


@pytest.fixture(autouse=True)
def cache(tmp_path, monkeypatch):
    monkeypatch.setattr(deploy, "CACHE", tmp_path / "cache")


def _flip_first(a):
    return a.at[0].set(1 - a[0])


@pytest.mark.parametrize("field", [0, 1, 2, 3])
def test_tree_answer_altered(monkeypatch, field):
    """One of (prediction, survivor, survivor count, active evaluations)
    altered in the first request of every batch of the served program."""
    from repro.serve import engine
    real = engine.serve_batch

    def broken(*args, **kw):
        out = list(real(*args, **kw))
        out[field] = _flip_first(out[field]) if field == 0 \
            else out[field].at[0].add(1)
        return tuple(out)

    monkeypatch.setattr(engine, "serve_batch", broken)
    cell = tiny.tiny_cell("credit-tree.batch",
                          {"loop": "open", "arrivals": "poisson",
                           "rate_per_s": 200})
    cell.config["check_sample"] = ALL
    result, numbers, _ = tiny.run_tiny(cell)
    assert not result["correct"], numbers


def test_forest_vote_altered(monkeypatch):
    """The ensemble's vote altered for the first request of every batch."""
    import repro.forest.compiler as fc
    real = fc.aggregate_votes

    def broken(*args, **kw):
        pred, score = real(*args, **kw)
        pred = pred.copy()
        pred[0] = 1 - pred[0]
        return pred, score

    monkeypatch.setattr(fc, "aggregate_votes", broken)
    cell = tiny.tiny_cell("covid-rf100.batch",
                          {"loop": "closed", "in_flight": 32})
    cell.config["check_sample"] = ALL
    result, numbers, _ = tiny.run_tiny(cell)
    assert not result["correct"], numbers
    assert numbers["wrong_prediction"] > 0
