"""Tree-ensemble -> multi-bank TCAM: compiler, sharding plan, executors.

The paper's pipelined multi-array throughput story generalizes from one tree
on one chip to a forest sharded across TCAM banks:

  sklearn_io.py — lossless import of fitted sklearn trees/forests and
                  histogram gradient-boosting predictors
  compiler.py   — compile_forest / ForestBank / CompiledForest + the
                  pure-numpy reference executor and vote aggregation
  plan.py       — ForestPlan: power-of-two shape bucketing, bank stacking
  executor.py   — ForestExecutor: the one owner of a served forest, its
                  placed plan groups and batch steps (encode, dispatch,
                  gather), used by TCAMServer's forest mode; engines
                  'banked' and 'ref' (imported lazily — needs jax)

``compile_forest`` + ``forest_infer_ref`` are numpy-only; accessing
``ForestExecutor`` (or anything from ``executor``) pulls in jax on demand.
"""
from .compiler import (
    VOTES,
    CompiledForest,
    ForestBank,
    ForestResult,
    aggregate_votes,
    compile_forest,
    forest_infer_ref,
    train_forest,
)
from .plan import ForestPlan, PlanGroup, plan_forest
from .sklearn_io import (
    from_hgb_predictor,
    from_sklearn_tree,
    is_sklearn_boosting,
    is_sklearn_forest,
    leaf_proba_rows,
    leaf_value_rows,
)

__all__ = [
    "VOTES", "CompiledForest", "ForestBank", "ForestResult",
    "aggregate_votes", "compile_forest", "forest_infer_ref", "train_forest",
    "ForestPlan", "PlanGroup", "plan_forest",
    "from_sklearn_tree", "is_sklearn_forest", "leaf_proba_rows",
    "from_hgb_predictor", "is_sklearn_boosting", "leaf_value_rows",
    "ForestExecutor", "FOREST_ENGINES", "encode_group",
]

_LAZY = {"ForestExecutor", "FOREST_ENGINES", "encode_group"}


def __getattr__(name: str):
    if name in _LAZY:
        from . import executor

        return getattr(executor, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
