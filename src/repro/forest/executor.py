"""The one owner of a served forest: its placed plan groups and batch steps.

``ForestExecutor`` holds what serving a ``CompiledForest`` needs: the served
bank layouts (the compiled ones, or a chip's faulted and repaired ones),
each bank's SA-variability ``kmax``, the physical->LUT row maps, the
``enabled`` mask, the plan, the threshold union, each group's column tables
and the groups placed on the device once.  A batch runs in three steps,
which ``TCAMServer`` calls inside its spans and ``infer`` composes:

* ``encode`` — the batch once on the host, as (bucket, F) int32 range
  indices against the union of every bank's thresholds per feature
  (``encode_ranges``);
* ``dispatch`` — one copy of them to the device, then one
  ``kernels.ops.serve_group`` call per plan group, which expands them into
  the group's thermometer codes through its column tables
  (``group_columns``), matches the whole stack of banks in one invocation
  and reduces it to each bank's first survivor, survivor count and active
  evaluations;
* ``gather`` — one group's (3, G, B) host array into per-bank survivors,
  translated through the row maps, survivor counts and active evaluations.

Engines: 'banked' (the batched einsum, ``kernels.ref.tcam_match_banked_ref``,
or for a group too large for it the same match streamed division by
division, ``tcam_match_banked_stream``; what 'auto' picks) and 'ref' (the
vmapped jnp oracle ``tcam_match_ref``),
both through the same ``serve_group``; the numpy ``forest_infer_ref`` is the
oracle the tests hold them to.

Compiled batch functions are cached per batch bucket, one entry holding the
list of group functions, with batch shapes bucketed up one ``BucketPolicy``
ladder, so a stream of varying batch sizes costs a bounded number of jit
compiles.
"""
from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.energy import DEFAULT_HW, HardwareParams, forest_figures
from ..core.encode import encode_inputs
from ..kernels.ops import default_interpret, place_cells, serve_group
from ..serve.batching import BucketPolicy
from ..serve.cache import CompileCache
from .compiler import CompiledForest, ForestResult, _aggregate
from .plan import PlanGroup, plan_forest

__all__ = ["ForestExecutor", "FOREST_ENGINES", "encode_group",
           "encode_ranges", "group_columns", "resolve_engine",
           "union_thresholds"]

FOREST_ENGINES = ("banked", "ref")


def resolve_engine(requested: str) -> str:
    """The forest engine for a requested one: 'auto' is 'banked'; 'packed'
    cannot take stacked banks and falls back to 'banked' with a
    ``RuntimeWarning``; anything else outside ``FOREST_ENGINES`` raises."""
    if requested == "auto":
        return "banked"
    if requested == "packed":
        warnings.warn(
            "engine 'packed' is not available in forest mode; "
            "falling back to 'banked'",
            RuntimeWarning,
            stacklevel=3,
        )
        return "banked"
    if requested not in FOREST_ENGINES:
        raise ValueError(
            f"unknown forest engine {requested!r}; expected 'auto', "
            "'banked', 'ref' or 'packed' (served as 'banked')"
        )
    return requested


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
    """Serve a ``CompiledForest`` on the device, batch by batch.

    >>> ex = ForestExecutor(forest)
    >>> res = ex.infer(X)
    >>> res.predictions, res.figures["aggregate"]["decs_pipe"]

    ``layouts`` are the served bank layouts (default: the compiled ones) and
    ``kmax`` each bank's (R, D) SA-variability ``kmax`` (``None``: ideal).
    ``policy`` is the batch bucket ladder; ``infer`` splits a larger ``X``
    into batches of at most ``policy.max_batch`` rows.
    """

    def __init__(
        self,
        forest: CompiledForest,
        *,
        engine: str = "auto",
        hw: HardwareParams = DEFAULT_HW,
        interpret: Optional[bool] = None,
        layouts: Optional[list] = None,
        kmax: Optional[list] = None,
        policy: BucketPolicy = BucketPolicy(),
        cache_size: Optional[int] = None,
    ) -> None:
        self.forest = forest
        self.engine = resolve_engine(engine)
        self.hw = hw
        self.interpret = default_interpret() if interpret is None else interpret
        self.policy = policy
        self._cache_size = cache_size
        self.layouts = list(forest.layouts if layouts is None else layouts)
        self.kmax = [None] * forest.n_banks if kmax is None else list(kmax)
        self.enabled = np.ones(forest.n_banks, bool)
        # physical row -> LUT (vote-table) row; spares start unassigned and
        # inherit a LUT row when repair remaps a defective rule onto them
        self.row_maps = []
        for lay in self.layouts:
            rm = np.full(lay.cells.shape[0], -1, np.int32)
            rm[: lay.n_rows] = np.arange(lay.n_rows, dtype=np.int32)
            self.row_maps.append(rm)
        self.union = union_thresholds(forest)
        self.lut_cells = sum(b.lut.n_total for b in forest.banks)
        self._replan()

    def _replan(self) -> None:
        """Shard the served layouts into plan groups, splice each bank's
        ``kmax`` and row map into its group slot, build the column tables
        and place every group on the device once; a fresh compile cache
        serves the new plan."""
        self.plan = plan_forest(self.layouts)
        self.columns = [group_columns(self.forest, grp, self.union)
                        for grp in self.plan.groups]
        self._placed, self._group_row_maps = [], []
        for grp, cols in zip(self.plan.groups, self.columns):
            km = np.array(grp.kmax0, copy=True)
            rm = np.full((grp.n_banks, grp.r_pad), -1, np.int32)
            for slot, bank_id in enumerate(grp.bank_ids):
                k = self.kmax[bank_id]
                if k is not None:
                    km[slot, : k.shape[0], : k.shape[1]] = k
                rm[slot, : len(self.row_maps[bank_id])] = \
                    self.row_maps[bank_id]
            self._placed.append((
                place_cells(grp.cells, grp.s, km, engine=self.engine),
                jnp.asarray(grp.rows, jnp.int32),
                jnp.asarray(grp.d_real, jnp.int32),
                *map(jnp.asarray, cols)))
            self._group_row_maps.append(rm)
        # cells the device compares for one request, padding included
        self.match_cells = sum(g.n_banks * g.r_pad * g.width
                               for g in self.plan.groups)
        # bytes of the placed operands the match reads (bitplanes, kmax)
        self.match_bytes = sum(a.nbytes for ops, *_ in self._placed
                               for a in ops.arrays)
        self.cache = CompileCache(self._build, self.plan.plan_id,
                                  maxsize=self._cache_size)

    def _build(self, bucket: int, engine: str) -> list:
        """One jit'd ``serve_group`` per plan group, on its placed arrays."""
        return [functools.partial(serve_group, *args,
                                  interpret=self.interpret)
                for args in self._placed]

    # -- chip state ---------------------------------------------------------
    def repair(self, repaired: dict) -> None:
        """Serve repaired banks: ``repaired`` maps a bank to its repaired
        layout and its spare assignments (defective row -> spare row).
        Each spare inherits the LUT row it now carries, so survivors on it
        vote as the rule it replaced; then the forest is re-planned."""
        for bank, (layout, assignments) in repaired.items():
            self.layouts[bank] = layout
            rm = self.row_maps[bank]
            for orig, spare in assignments.items():
                rm[int(spare)] = rm[int(orig)]
        self._replan()

    def disable_bank(self, bank: int) -> None:
        """Drop one bank out of the vote and its divisor."""
        mask = self.enabled.copy()
        mask[int(bank)] = False
        if not mask.any():
            raise RuntimeError("cannot disable the last voting bank")
        self.enabled = mask

    # -- batch steps ----------------------------------------------------------
    def encode(self, X: np.ndarray, *, who: str = "ForestExecutor"
               ) -> np.ndarray:
        """A batch's (bucket, F) int32 range indices, padded to its
        bucket."""
        Xp = self.forest.prepare_inputs(X, who=who)
        return encode_ranges(self.union, Xp, self.policy.bucket_for(len(Xp)))

    def dispatch(self, u: np.ndarray) -> list:
        """Copy ``u`` to the device once and start every group on it; one
        pending (3, G, bucket) array per group."""
        fns = self.cache.get(len(u), self.engine)
        u_dev = jnp.asarray(u)
        return [fn(u_dev) for fn in fns]

    def outputs(self, n: int) -> tuple:
        """Empty per-bank (survivors, n_survivors, active) for ``n``
        requests, for ``gather`` to fill."""
        shape = (self.forest.n_banks, n)
        return (np.empty(shape, np.int32), np.empty(shape, np.int32),
                np.empty(shape, np.int64))

    def gather(self, gi: int, host: np.ndarray, out: tuple) -> None:
        """Write group ``gi``'s (3, G, bucket) host array into ``out``:
        each bank's first survivor as a LUT row (-1: none), its survivor
        count and its active evaluations."""
        survivors, n_survivors, active = out
        grp = self.plan.groups[gi]
        first, ns, act = host[:, :, : survivors.shape[1]]
        lut_row = np.take_along_axis(self._group_row_maps[gi], first, axis=1)
        survivors[grp.bank_ids] = np.where(ns > 0, lut_row, -1)
        n_survivors[grp.bank_ids] = ns
        active[grp.bank_ids] = act

    def warmup(self, batch: Optional[int] = None) -> int:
        """Pre-compile every group for the bucket ``infer`` runs ``batch``
        rows in, or for every bucket when ``batch`` is None; returns
        #compiles."""
        before = self.cache.misses
        buckets = (self.policy.buckets if batch is None else
                   [self.policy.bucket_for(min(batch, self.policy.max_batch))])
        for b in buckets:
            u = np.zeros((b, self.forest.n_features), np.int32)
            jax.block_until_ready(self.dispatch(u))
        return self.cache.misses - before

    # -- execution ----------------------------------------------------------
    def infer(
        self,
        X: np.ndarray,
        *,
        selective_precharge: bool = True,
        enabled: Optional[np.ndarray] = None,
    ) -> ForestResult:
        forest = self.forest
        Xp = forest.prepare_inputs(X, who="ForestExecutor.infer")
        out = self.outputs(len(Xp))
        step = self.policy.max_batch
        for lo in range(0, len(Xp), step):
            u = self.encode(Xp[lo: lo + step], who="ForestExecutor.infer")
            part = tuple(a[:, lo: lo + step] for a in out)
            for gi, dev in enumerate(self.dispatch(u)):
                self.gather(gi, np.asarray(dev), part)
        survivors, n_survivors, active = out
        if not selective_precharge:
            for grp in self.plan.groups:
                active[grp.bank_ids] = (grp.rows * grp.d_real)[:, None]

        en = self.enabled if enabled is None else np.asarray(enabled, bool)
        predictions, score, margin = _aggregate(forest, survivors, en)
        figures = forest_figures(
            self.layouts, self.hw,
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
