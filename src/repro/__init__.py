"""DT2CAM reproduction — blessed public API.

Import policy (see README "Import policy"): user code — examples, benchmarks,
notebooks, downstream services — imports from **this** module (or the stable
sub-packages ``repro.core``, ``repro.forest``, ``repro.serve``, ``repro.dt``,
``repro.degradation``), never from deep module paths like
``repro.core.compiler`` or ``repro.serve.engine``.  Deep paths are
implementation detail and move without deprecation; everything in ``__all__``
below is covered by the one-release deprecation policy.

Single tree:

    >>> import repro
    >>> model = repro.DT2CAM(s=128).fit(X, y)
    >>> res = model.infer(Xq)                       # numpy oracle
    >>> res = model.infer(Xq, backend="jax")        # Pallas kernels

Forest (multi-bank):

    >>> forest = repro.compile_forest(sklearn_rf, s=128)
    >>> res = repro.forest_infer_ref(forest, Xq)    # numpy oracle
    >>> ex = repro.ForestExecutor(forest)           # banked jax execution
    >>> res = ex.infer(Xq)

Serving (both single- and multi-bank models):

    >>> with repro.TCAMServer(compiled) as srv:
    ...     preds = [r.prediction for r in srv.serve(Xq)]

Model lifecycle (versioned registry, delta reprogramming, hot swap):

    >>> reg = repro.ModelRegistry("artifacts/registry")
    >>> v1 = reg.publish(model.compiled, "traffic")
    >>> mgr = repro.LifecycleManager(reg, srv, live_version=v1.version_id)
    >>> mgr.stage(v2.version_id); ...; mgr.promote(max_disagreement=0.05)

Everything importable eagerly here is numpy-only; jax-dependent names
(``TCAMServer``, ``ForestExecutor``, the kernel entry points) load on first
access via module ``__getattr__``.
"""
from .core import (
    CELL_0,
    CELL_1,
    CELL_MM,
    CELL_X,
    DEFAULT_HW,
    DT2CAM,
    IDEAL,
    CompiledDT,
    DecisionTree,
    DriftModel,
    DriftSpec,
    FeatureMismatch,
    HardwareParams,
    NonIdealSpec,
    RuleTable,
    SAFMask,
    SenseMargins,
    SimResult,
    TCAMLayout,
    TernaryLUT,
    bank_figures,
    check_feature_count,
    compile_tree,
    encode_inputs,
    encode_table,
    forest_figures,
    mismatch_probability,
    reduce_tree,
    sample_drift,
    sensing_margins,
    simulate,
    synthesize,
    train_tree,
)
from .degradation import (
    ScrubPolicy,
    ScrubReport,
    ScrubScheduler,
    layout_margins,
    plan_refresh,
)
from .dt import DATASETS, load, load_split, normalize
from .lifecycle import (
    LifecycleManager,
    ModelRegistry,
    ModelVersion,
    RemapResult,
    WearTracker,
    WritePlan,
    content_hash,
    plan_delta,
    plan_forest_delta,
    plan_full,
    wear_level_rows,
)
from .forest import (
    CompiledForest,
    ForestBank,
    ForestPlan,
    ForestResult,
    aggregate_votes,
    compile_forest,
    forest_infer_ref,
    plan_forest,
    train_forest,
)

__all__ = [
    # core: compile + simulate
    "DT2CAM", "CompiledDT", "compile_tree", "DecisionTree", "train_tree",
    "RuleTable", "reduce_tree", "encode_table", "encode_inputs",
    "TernaryLUT", "TCAMLayout", "synthesize", "simulate", "SimResult",
    "CELL_0", "CELL_1", "CELL_X", "CELL_MM",
    # validation + non-idealities
    "FeatureMismatch", "check_feature_count",
    "NonIdealSpec", "IDEAL", "SAFMask",
    "DriftSpec", "DriftModel", "sample_drift",
    # hardware model
    "HardwareParams", "DEFAULT_HW", "bank_figures", "forest_figures",
    "SenseMargins", "sensing_margins", "mismatch_probability",
    # degradation: scrub-and-refresh scheduling
    "ScrubPolicy", "ScrubReport", "ScrubScheduler",
    "plan_refresh", "layout_margins",
    # forests
    "CompiledForest", "ForestBank", "ForestResult", "compile_forest",
    "train_forest", "forest_infer_ref", "aggregate_votes",
    "ForestPlan", "plan_forest",
    # datasets
    "DATASETS", "load", "load_split", "normalize",
    # lifecycle: registry + delta reprogramming + wear
    "ModelRegistry", "ModelVersion", "content_hash",
    "WritePlan", "plan_delta", "plan_full", "plan_forest_delta",
    "WearTracker", "RemapResult", "wear_level_rows", "LifecycleManager",
    # jax-dependent (lazy): kernels
    "tcam_infer", "tcam_match", "tcam_match_banked", "ENGINES",
    "BANKED_ENGINES", "select_engine", "finalize_result",
    # jax-dependent (lazy): executors + serving
    "ForestExecutor", "FOREST_ENGINES",
    "TCAMServer", "ServeConfig", "RequestResult", "PromotionReport",
    "ServingError", "Rejected", "DeadlineExceeded", "ComputeFailed",
    # jax-dependent (lazy): entry-point setup
    "enable_compile_cache",
]

_LAZY = {
    "tcam_infer": "kernels",
    "tcam_match": "kernels",
    "tcam_match_banked": "kernels",
    "ENGINES": "kernels",
    "BANKED_ENGINES": "kernels",
    "select_engine": "kernels",
    "finalize_result": "kernels",
    "ForestExecutor": "forest",
    "FOREST_ENGINES": "forest",
    "TCAMServer": "serve",
    "ServeConfig": "serve",
    "RequestResult": "serve",
    "PromotionReport": "serve",
    "ServingError": "serve",
    "Rejected": "serve",
    "DeadlineExceeded": "serve",
    "ComputeFailed": "serve",
    "enable_compile_cache": "jax_cache",
}


def __getattr__(name: str):
    pkg = _LAZY.get(name)
    if pkg is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(f".{pkg}", __name__), name)


def __dir__():
    return sorted(set(globals()) | set(__all__))
