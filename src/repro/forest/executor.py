"""Sharded multi-bank forest executor (JAX paths).

Runs a compiled forest's execution plan on the banked kernels: every
``PlanGroup`` evaluates as ONE batched/vmapped kernel invocation (engine
'banked' = batched einsum, 'mxu' = vmapped Pallas bitplane kernel), reduced
on the device to each bank's first survivor, survivor count and active
evaluations (``kernels.ops.serve_group``).  The host encodes a batch once,
as (B, F) range indices against the union of every bank's thresholds per
feature (``encode_ranges``); each group expands them into its banks'
thermometer codes on the device through its column tables
(``group_columns``).  Engine 'ref' delegates to the pure-numpy oracle
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
from .compiler import CompiledForest, ForestResult, _aggregate, forest_infer_ref
from .plan import ForestPlan, PlanGroup, plan_forest

__all__ = ["ForestExecutor", "FOREST_ENGINES", "encode_group",
           "encode_ranges", "group_columns", "union_thresholds"]

FOREST_ENGINES = ("banked", "mxu", "ref")


def encode_group(
    forest: CompiledForest, group: PlanGroup, Xp: np.ndarray
) -> np.ndarray:
    """Per-bank encode + pad to the group's stacked shape: (G, B, W_pad).

    Each bank encodes the SAME raw inputs through its OWN thresholds — banks
    cannot share search words, which is why the stacked input carries a bank
    axis instead of broadcasting one batch.  The host reference for the
    served path, which builds these words on the device from
    ``encode_ranges`` and ``group_columns`` (``kernels.ops.expand_ranges``).
    """
    b = Xp.shape[0]
    out = np.zeros((group.n_banks, b, group.width), dtype=np.uint8)
    for slot, bank_id in enumerate(group.bank_ids):
        bank = forest.banks[int(bank_id)]
        xpad = bank.layout.pad_inputs(encode_inputs(bank.lut, Xp))
        out[slot, :, : xpad.shape[1]] = xpad
    return out


# a column table entry no range index reaches: the decoder bit and the
# padding columns, always 0
_NEVER = np.iinfo(np.int32).max


def union_thresholds(forest: CompiledForest) -> list[np.ndarray]:
    """Per feature j, the sorted float64 union of every bank's thresholds."""
    return [np.unique(np.concatenate(
        [np.asarray(b.lut.thresholds[j], np.float64) for b in forest.banks]))
        for j in range(forest.n_features)]


def group_columns(forest: CompiledForest, group: PlanGroup,
                  union: list[np.ndarray]) -> tuple[np.ndarray, np.ndarray]:
    """A plan group's column tables ``(feat, q)``, both (G, W_pad) int32:
    column c of bank slot g's search word is ``u[:, feat[g, c]] >=
    q[g, c]`` for the range indices ``u = encode_ranges(union, Xp)``.

    Bit p of a bank's n-wide code for feature j is 1 iff that bank has at
    least m = n - 1 - p thresholds below the value: always for m = 0, else
    iff its m-th threshold, at index i of ``union[j]``, is below it, that
    is iff ``u >= i + 1``."""
    feat = np.zeros((group.n_banks, group.width), np.int32)
    q = np.full((group.n_banks, group.width), _NEVER, np.int32)
    for slot, bank_id in enumerate(group.bank_ids):
        lut = forest.banks[int(bank_id)].lut
        for j, th in enumerate(lut.thresholds):
            lo, hi = 1 + lut.feat_offsets[j], 1 + lut.feat_offsets[j + 1]
            at_least = np.concatenate(
                [[0], 1 + np.searchsorted(union[j], th)])   # by m
            feat[slot, lo:hi] = j
            q[slot, lo:hi] = at_least[::-1]                 # m = n-1 .. 0
    return feat, q


def encode_ranges(union: list[np.ndarray], Xp: np.ndarray,
                  bucket: Optional[int] = None) -> np.ndarray:
    """(bucket, F) int32 range indices: ``u[b, j]`` counts the thresholds in
    ``union[j]`` below ``Xp[b, j]`` (NaN counts all of them, as in
    ``encode_inputs``).  Rows past ``len(Xp)`` are zero padding."""
    Xp = np.asarray(Xp, np.float64)
    u = np.zeros((bucket or len(Xp), len(union)), np.int32)
    for j, th in enumerate(union):
        u[: len(Xp), j] = np.searchsorted(th, Xp[:, j], side="left")
    return u


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
        self._union = union_thresholds(forest)
        self.cache = CompileCache(self._build, self.plan.plan_id)

    # -- compile machinery --------------------------------------------------
    def _build(self, bucket: int, key: str):
        """One jit'd ``serve_group`` per (batch-bucket, engine, group); the
        group's grids, real rows, real divisions and column tables are
        placed on the device once and passed as arguments."""
        engine, gi = key.rsplit(":g", 1)
        args = self._placed.get(key)
        if args is None:
            grp = self.plan.groups[int(gi)]
            args = self._placed[key] = (
                place_cells(grp.cells, grp.s, self._kmax[int(gi)],
                            engine=engine, block_r=self.block_r),
                jnp.asarray(grp.rows, jnp.int32),
                jnp.asarray(grp.d_real, jnp.int32),
                *map(jnp.asarray,
                     group_columns(self.forest, grp, self._union)),
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
        u = jnp.zeros((bucket, self.forest.n_features), jnp.int32)
        for gi in range(self.plan.n_groups):
            fn = self.cache.get(bucket, f"{self.engine}:g{gi}")
            jax.block_until_ready(fn(u))
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

        # one (bucket, F) copy of range indices serves every group; each
        # expands it into its banks' search words on the device
        u = jnp.asarray(encode_ranges(self._union, Xp, bucket))
        pending = [
            (grp, self.cache.get(bucket, f"{self.engine}:g{gi}")(u))
            for gi, grp in enumerate(self.plan.groups)
        ]

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

        predictions, score, margin = _aggregate(forest, survivors, enabled)
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
            margin=margin,
        )

    __call__ = infer
