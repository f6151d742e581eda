"""Sharded multi-bank forest executor (JAX paths).

Runs a compiled forest's execution plan on the banked kernels: every
``PlanGroup`` evaluates as ONE batched/vmapped kernel invocation (engine
'banked' = batched einsum, 'mxu' = vmapped Pallas bitplane kernel), reduced
on the device to each bank's first survivor, survivor count and active
evaluations (``kernels.ops.serve_group``), with groups *pipelined* — group
g+1's host-side input encoding overlaps group g's device compute via JAX
async dispatch.  Engine 'ref' delegates to the pure-numpy oracle
(``forest_infer_ref``); all engines produce bit-identical survivors and
therefore bit-identical votes.

Compiled batch functions are cached per (batch-bucket, engine, group,
plan_id) through the serving engine's ``CompileCache``, with batch shapes
bucketed up the same power-of-two ladder the server uses — a stream of
varying batch sizes costs a bounded number of jit compiles.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.energy import DEFAULT_HW, HardwareParams, forest_figures
from ..core.encode import encode_inputs
from ..kernels.ops import default_interpret, place_cells, serve_group
from ..serve.batching import BucketPolicy
from ..serve.cache import CompileCache
from .compiler import CompiledForest, ForestResult, aggregate_votes, forest_infer_ref
from .plan import ForestPlan, PlanGroup, plan_forest

__all__ = ["ForestExecutor", "FOREST_ENGINES", "encode_group"]

FOREST_ENGINES = ("banked", "mxu", "ref")


def encode_group(
    forest: CompiledForest, group: PlanGroup, Xp: np.ndarray
) -> np.ndarray:
    """Per-bank encode + pad to the group's stacked shape: (G, B, W_pad).

    Each bank encodes the SAME raw inputs through its OWN thresholds — banks
    cannot share search words, which is why the stacked input carries a bank
    axis instead of broadcasting one batch.
    """
    b = Xp.shape[0]
    out = np.zeros((group.n_banks, b, group.width), dtype=np.uint8)
    for slot, bank_id in enumerate(group.bank_ids):
        bank = forest.banks[int(bank_id)]
        xpad = bank.layout.pad_inputs(encode_inputs(bank.lut, Xp))
        out[slot, :, : xpad.shape[1]] = xpad
    return out


class ForestExecutor:
    """Execute a ``CompiledForest`` on the banked kernels.

    >>> ex = ForestExecutor(forest, engine="banked")
    >>> res = ex.infer(X)
    >>> res.predictions, res.figures["aggregate"]["decs_pipe"]
    """

    def __init__(
        self,
        forest: CompiledForest,
        *,
        engine: str = "banked",
        hw: HardwareParams = DEFAULT_HW,
        interpret: Optional[bool] = None,
        block_b: int = 128,
        block_r: int = 128,
        min_bucket: int = 8,
        plan: Optional[ForestPlan] = None,
        kmax: Optional[list] = None,   # per-group (G, R, D) overrides
    ) -> None:
        if engine not in FOREST_ENGINES:
            raise ValueError(
                f"unknown forest engine {engine!r}; "
                f"expected one of {FOREST_ENGINES}"
            )
        self.forest = forest
        self.engine = engine
        self.hw = hw
        self.interpret = default_interpret() if interpret is None else interpret
        self.block_b = block_b
        self.block_r = block_r
        self.min_bucket = min_bucket
        self.plan = plan if plan is not None else plan_forest(forest)
        self._kmax = (
            [g.kmax0 for g in self.plan.groups] if kmax is None else list(kmax)
        )
        self._placed: dict = {}       # "engine:g<i>" -> serve_group args
        self.cache = CompileCache(self._build, self.plan.plan_id)

    # -- compile machinery --------------------------------------------------
    def _build(self, bucket: int, key: str):
        """One jit'd ``serve_group`` per (batch-bucket, engine, group); the
        group's grids, real rows and real divisions are placed on the device
        once and passed as arguments."""
        engine, gi = key.rsplit(":g", 1)
        args = self._placed.get(key)
        if args is None:
            grp = self.plan.groups[int(gi)]
            args = self._placed[key] = (
                place_cells(grp.cells, grp.s, self._kmax[int(gi)],
                            engine=engine, block_r=self.block_r),
                jnp.asarray(grp.rows, jnp.int32),
                jnp.asarray(grp.d_real, jnp.int32),
            )
        return functools.partial(serve_group, *args, block_b=self.block_b,
                                 interpret=self.interpret)

    def _bucket_for(self, b: int) -> int:
        top = self.min_bucket
        while top < b:
            top *= 2
        policy = BucketPolicy(max_batch=top, min_bucket=self.min_bucket)
        return policy.bucket_for(b)

    def warmup(self, batch: int = 8) -> int:
        """Pre-compile every group for one batch bucket; returns #compiles."""
        if self.engine == "ref":
            return 0
        before = self.cache.misses
        bucket = self._bucket_for(batch)
        for gi, grp in enumerate(self.plan.groups):
            fn = self.cache.get(bucket, f"{self.engine}:g{gi}")
            x = jnp.zeros((grp.n_banks, bucket, grp.width), jnp.uint8)
            jax.block_until_ready(fn(x))
        return self.cache.misses - before

    # -- execution ----------------------------------------------------------
    def infer(
        self,
        X: np.ndarray,
        *,
        selective_precharge: bool = True,
        enabled: Optional[np.ndarray] = None,
    ) -> ForestResult:
        if self.engine == "ref":
            return forest_infer_ref(
                self.forest, X, hw=self.hw,
                selective_precharge=selective_precharge, enabled=enabled,
            )
        forest = self.forest
        Xp = forest.prepare_inputs(X, who="ForestExecutor.infer")
        b = Xp.shape[0]
        bucket = self._bucket_for(b)

        # pipelined dispatch: JAX queues group g's device compute
        # asynchronously, so encoding group g+1 on the host overlaps it
        pending = []
        for gi, grp in enumerate(self.plan.groups):
            xpad = encode_group(forest, grp, Xp)
            if bucket > b:
                xpad = np.pad(xpad, ((0, 0), (0, bucket - b), (0, 0)))
            fn = self.cache.get(bucket, f"{self.engine}:g{gi}")
            pending.append((grp, fn(jnp.asarray(xpad))))

        survivors = np.empty((forest.n_banks, b), np.int32)
        n_survivors = np.empty((forest.n_banks, b), np.int32)
        active = np.empty((forest.n_banks, b), np.int64)
        for grp, out in pending:
            first, ns, act = np.asarray(out)[:, :, :b]
            survivors[grp.bank_ids] = np.where(ns > 0, first, -1)
            n_survivors[grp.bank_ids] = ns
            active[grp.bank_ids] = (
                act if selective_precharge
                else (grp.rows * grp.d_real)[:, None]
            )

        predictions, score = aggregate_votes(forest, survivors, enabled)
        en = (np.ones(forest.n_banks, bool) if enabled is None
              else np.asarray(enabled, bool))
        figures = forest_figures(
            forest.layouts, self.hw,
            mean_active_evals=[float(a.mean()) for a in active],
        )
        return ForestResult(
            predictions=predictions,
            score=score,
            survivors=survivors,
            n_survivors=n_survivors,
            active_evals=active,
            enabled=en,
            engine=self.engine,
            figures=figures,
        )

    __call__ = infer
