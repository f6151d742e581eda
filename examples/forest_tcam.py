"""Forests on TCAM banks: compile a bagged ensemble to one bank per tree,
then run it sharded — every same-shape group of banks evaluates as ONE
batched kernel invocation, groups pipelined, votes aggregated.

    PYTHONPATH=src python examples/forest_tcam.py

Shows the blessed top-level API (``import repro``): ``train_forest`` ->
``compile_forest`` -> ``forest_infer_ref`` (numpy oracle) and
``ForestExecutor`` (banked jax path), plus multi-bank serving through the
same ``TCAMServer`` that serves single trees.
"""
import numpy as np

import repro
from repro.dt import load_split


def main():
    repro.enable_compile_cache()
    Xtr, ytr, Xte, yte = load_split("cancer")

    # one CART tree per TCAM bank, bagged
    trees = repro.train_forest(Xtr, ytr, n_trees=8, max_depth=8, seed=0)
    forest = repro.compile_forest(trees, s=128)
    print(f"forest: {forest.n_banks} banks, "
          f"{sum(l.n_rows for l in forest.layouts)} rules total")

    # numpy oracle: per-bank functional sim + majority vote
    ref = repro.forest_infer_ref(forest, Xte)
    print(f"ref accuracy       : {ref.accuracy(yte):.4f}")
    agg = ref.figures["aggregate"]
    print(f"modelled aggregate : {agg['decs_pipe'] / 1e6:.0f} M dec/s over "
          f"{agg['n_banks']} pipelined banks "
          f"({agg['ensemble_decs_pipe'] / 1e6:.0f} M ensemble dec/s)")

    # banked jax execution: same survivors, same votes, bit-exact
    ex = repro.ForestExecutor(forest, engine="banked")
    res = ex.infer(Xte)
    assert (res.predictions == ref.predictions).all()
    print(f"banked engine      : parity with ref "
          f"({ex.plan.n_groups} execution group(s))")

    # serving: TCAMServer detects the forest and shards the batch path
    with repro.TCAMServer(forest) as server:
        server.warmup()
        results = server.serve(Xte[:64])
        preds = np.array([r.prediction for r in results])
    assert (preds == ref.predictions[:64]).all()
    print("served 64 requests : parity with ref")


if __name__ == "__main__":
    main()
