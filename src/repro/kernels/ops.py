"""Public jit'd TCAM-match ops: engine selection, cell placement, padding,
packing, and the JAX serving path (`tcam_infer`) that the examples / serving
stack use.

Engines:
  'mxu'    — float bitplane matmul kernel (tcam_match.py); handles every cell
             state incl. SAF-induced CELL_MM.
  'packed' — bit-packed popcount kernel (tcam_packed.py); 16x fewer HBM bytes;
             requires S % 32 == 0 and no CELL_MM cells.
  'ref'    — pure-jnp oracle (ref.py).
  'auto'   — packed when legal, else mxu.
  'banked' — batched einsum over a leading bank axis (forest groups only);
             a group whose one-shot einsum would not fit the device streams
             its divisions instead (``banked_streams``).

All engines share the contract: inputs are the *padded search words* from
``TCAMLayout.pad_inputs`` (decoder bit + encoded features + padding) and the
layout's cell grid; outputs are (survive, evals) as defined in ref.py.

The serving paths reduce those (…, B, R) outputs on the device and copy back
only what the host needs: ``serve_batch`` (one tree) returns four (B,)
arrays, ``serve_group`` (one forest plan group) one (3, G, B) array.
``serve_group`` takes (B, F) range indices and builds each bank's search
words itself (``expand_ranges``).

A cell grid is placed on the device once (``place_cells``) in the layout its
engine's kernel reads, and every batch passes those arrays to the jitted
match (``match_cells``) as arguments: the compiled program holds no cells, so
one executable serves every grid of the same shape — after repair, scrub or
a promotion too.  ``tcam_match`` does both steps in one call.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.energy import DEFAULT_HW, HardwareParams, f_max, t_cwd
from ..core.lut import CELL_0, CELL_1, CELL_MM, bitplanes
from ..core.simulate import SimResult, sense_voltage
from ..core.synth import TCAMLayout
from .ref import (pack_bits, tcam_match_banked_ref, tcam_match_banked_stream,
                  tcam_match_ref)
from .tcam_match import tcam_match_pallas
from .tcam_packed import tcam_match_packed_pallas

__all__ = ["tcam_match", "tcam_infer", "sa_kmax", "select_engine",
           "finalize_result", "default_interpret", "ENGINES", "CellOperands",
           "place_cells", "match_cells", "serve_batch", "expand_ranges",
           "serve_group", "banked_streams", "ONE_SHOT_BYTES"]

ENGINES = ("auto", "mxu", "packed", "ref")

# The most f32 intermediate the one-shot banked einsum may build: a quarter
# of one TPU v5e chip's 16 GB of HBM.
ONE_SHOT_BYTES = 2 ** 32


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def select_engine(cells: np.ndarray, s: int, engine: str = "auto") -> str:
    """Resolve an engine request against the layout's legality constraints.

    'auto' picks 'packed' (16x fewer HBM bytes) when legal — S % 32 == 0 and
    no SAF-induced CELL_MM cells (unrepresentable in packed bitplanes) — else
    'mxu'.  An explicit illegal 'packed' request raises.
    """
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; expected one of {ENGINES}")
    has_mm = bool(np.any(np.asarray(cells) == CELL_MM))
    packed_ok = s % 32 == 0 and not has_mm
    if engine == "auto":
        return "packed" if packed_ok else "mxu"
    if engine == "packed" and not packed_ok:
        raise ValueError("packed engine needs S % 32 == 0 and no CELL_MM cells")
    return engine


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["arrays"],
                   meta_fields=["engine", "s", "rows", "block_r"])
@dataclasses.dataclass(frozen=True)
class CellOperands:
    """A cell grid (optionally a stack of bank grids) on the device, in the
    layout one engine reads.  ``arrays`` are jit arguments; the rest is
    static.  'mxu' / 'packed' hold the division-major kernel operands with
    rows padded to ``block_r`` (pad rows kmax = -1: always mismatch); 'ref' /
    'banked' hold the (…, R, W) u8 bitplanes and (…, R, D) kmax."""

    arrays: tuple
    engine: str
    s: int
    rows: int
    block_r: int


def banked_streams(g: int, b: int, r: int, d: int, s: int) -> bool:
    """Whether a plan group of ``g`` banks of ``r`` rows and ``d`` divisions
    of width ``s``, matched against a ``b``-row batch, takes the streamed
    banked form (``ref.tcam_match_banked_stream``).  The one-shot form
    (``ref.tcam_match_banked_ref``) builds the (G, B, R, D) f32 mismatch
    counts and casts both (G, R, D·S) planes to f32; it is kept while those
    stay within ``ONE_SHOT_BYTES``."""
    return 4 * g * r * (b * d + 2 * d * s) > ONE_SHOT_BYTES


def _division_major(a, width: int):
    """(..., N, D·width) -> (..., D, N, width): one division per leading
    slice."""
    *lead, n, w = a.shape
    return a.reshape(*lead, n, w // width, width).swapaxes(-3, -2)


def _pack_words(bits: np.ndarray) -> np.ndarray:
    """numpy twin of ``ref.pack_bits``: (..., W) {0,1} -> (..., W/32) u32,
    bit i of word j = column 32·j + i."""
    *lead, w = bits.shape
    assert w % 32 == 0, w
    b = bits.astype(np.uint32).reshape(*lead, w // 32, 32)
    return (b << np.arange(32, dtype=np.uint32)).sum(-1, dtype=np.uint32)


def _device_plane(cells: np.ndarray, state: int) -> jax.Array:
    """``bitplanes``' u8 plane of ``state`` (a ``CELL_MM`` cell sets both),
    built one bank at a time and copied to the device: the host holds one
    plane, not each comparison's full-size temporary."""
    plane = np.empty(cells.shape, np.uint8)
    grid = cells.shape[-2:]
    for src, dst in zip(cells.reshape(-1, *grid),
                        plane.reshape(-1, *grid).view(bool)):
        np.equal(src, state, out=dst)
        dst |= src == CELL_MM
    return jnp.asarray(plane)


def place_cells(
    cells: np.ndarray,            # (..., R, W) int8 cell states
    s: int,
    kmax=None,                    # (..., R, D) int32; None = ideal (zeros)
    *,
    engine: str,                  # a resolved engine: no 'auto'
    block_r: int = 128,
) -> CellOperands:
    """Copy a cell grid to the device in ``engine``'s layout (see
    ``CellOperands``).  Done once per grid; every batch reuses the arrays."""
    cells = np.asarray(cells)
    *lead, r, w = cells.shape
    assert w % s == 0, (w, s)
    d = w // s
    km = (np.zeros((*lead, r, d), np.int32) if kmax is None
          else np.asarray(kmax, np.int32))
    if engine in ("ref", "banked"):
        arrays = (_device_plane(cells, CELL_0), _device_plane(cells, CELL_1),
                  km)
    elif engine in ("mxu", "packed"):
        is0, is1 = bitplanes(cells)
        rows_pad = [(0, 0)] * len(lead) + [(0, (-r) % block_r), (0, 0)]
        km = np.pad(km, rows_pad, constant_values=-1)
        km = km.swapaxes(-1, -2)[..., None, :]              # (..., D, 1, R)
        is0, is1 = np.pad(is0, rows_pad), np.pad(is1, rows_pad)
        if engine == "mxu":
            # (..., D, S, R) f32: the kernel's RHS, rows on the lanes
            arrays = tuple(
                _division_major(p, s).swapaxes(-2, -1).astype(np.float32)
                for p in (is0, is1)
            ) + (km,)
        else:
            if s % 32:
                raise ValueError("packed engine needs S % 32 == 0")
            arrays = tuple(
                _division_major(_pack_words(p), s // 32).swapaxes(-2, -1)
                for p in (is1, is0 | is1)                   # val, care
            ) + (km,)
    else:
        raise ValueError(f"unknown engine {engine!r}")
    return CellOperands(
        arrays=tuple(jnp.asarray(a) for a in arrays),
        engine=engine, s=s, rows=r, block_r=block_r,
    )


def _pad_to(a: jax.Array, axis: int, mult: int) -> jax.Array:
    n = a.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def match_cells(
    ops: CellOperands,
    xpad: jax.Array,              # (..., B, W) padded search words {0,1}
    *,
    block_b: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Match search words against placed cells; returns (survive, evals),
    both (..., B, R) int32, selective-precharge semantics (see ref.py).
    Leading axes are banks: the kernels are vmapped over them."""
    *lead, b, _ = xpad.shape
    s = ops.s
    if ops.engine == "banked":
        g, r, w = ops.arrays[0].shape
        fn = (tcam_match_banked_stream if banked_streams(g, b, r, w // s, s)
              else tcam_match_banked_ref)
        return fn(xpad, *ops.arrays[:2], s, ops.arrays[2])
    if ops.engine == "ref":
        fn = lambda x, p0, p1, km: tcam_match_ref(x, p0, p1, s, km)
    else:
        # batch blocks: the TPU tile's 8 sublanes at least, block_b at most
        bb = min(block_b, b + (-b) % 8)
        if ops.engine == "packed":
            kernel = tcam_match_packed_pallas
            prep = lambda x: _division_major(pack_bits(x), s // 32)
        else:
            kernel = tcam_match_pallas
            prep = lambda x: _division_major(x, s).astype(jnp.float32)
        xpad = prep(_pad_to(xpad, -2, bb))
        fn = functools.partial(kernel, block_b=bb, block_r=ops.block_r,
                               interpret=interpret)
    for _ in lead:
        fn = jax.vmap(fn)
    survive, evals = fn(xpad, *ops.arrays)
    return survive[..., :b, :ops.rows], evals[..., :b, :ops.rows]


def tcam_match(
    cells: np.ndarray,            # (R, W) int8 cell states (layout.cells)
    xpad: jax.Array,              # (B, W) padded search words {0,1}
    s: int,
    kmax: Optional[jax.Array] = None,   # (R, D) int32
    *,
    engine: str = "auto",
    block_b: int = 128,
    block_r: int = 128,
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array]:
    """Match search words against a tiled TCAM; returns (survive, evals),
    both (B, R) int32, selective-precharge semantics (see ref.py)."""
    interpret = default_interpret() if interpret is None else interpret
    engine = select_engine(cells, s, engine)
    ops = place_cells(cells, s, kmax, engine=engine, block_r=block_r)
    return match_cells(ops, jnp.asarray(xpad), block_b=block_b,
                       interpret=interpret)


def sa_kmax(
    layout: TCAMLayout,
    sa_offsets: np.ndarray,       # (R, D) sampled SA V_ref offsets
    hw: HardwareParams = DEFAULT_HW,
) -> np.ndarray:
    """Lower analog SA-variability to an integer mismatch tolerance:
    row r (division d) senses 'match' iff V_ml(mism) > V_ref(d) + offset[r,d];
    V_ml is monotone decreasing in the mismatch count, so the analog decision
    equals ``mism <= kmax[r, d]`` with kmax = #{k : V(k) > thresh} - 1.

    kmax = -1 encodes 'always mismatch' (offset pushed V_ref above V_fm);
    ideal hardware is kmax = 0 everywhere.
    """
    s, n_cwd = layout.s, layout.n_cwd
    rows = layout.cells.shape[0]
    used = 1 + layout.width
    n_eff = np.array(
        [max(0, min((d + 1) * s, used) - d * s) for d in range(n_cwd)], np.int64
    )
    # V(k) for k = 0..S per division (n_eff varies only in the last division)
    ks = np.arange(s + 1)
    kmax = np.zeros((rows, n_cwd), np.int64)
    for d_i in range(n_cwd):
        if n_eff[d_i] == 0:
            kmax[:, d_i] = s  # fully masked division: always matches
            continue
        v = sense_voltage(ks, np.full_like(ks, n_eff[d_i]), s, hw)  # (S+1,)
        v_fm = v[0]
        v_1mm = sense_voltage(np.array([1]), np.array([n_eff[d_i]]), s, hw)[0]
        v_ref = 0.5 * (v_fm + v_1mm)
        thresh = v_ref + sa_offsets[:, d_i]          # (R,)
        kmax[:, d_i] = (v[None, :] > thresh[:, None]).sum(axis=1) - 1
    return kmax.astype(np.int32)


@jax.jit
def _finalize(survive, evals, classes):
    n_survivors = survive.sum(axis=1).astype(jnp.int32)
    first = jnp.argmax(survive, axis=1).astype(jnp.int32)
    survivors = jnp.where(n_survivors > 0, first, -1)
    preds = jnp.where(n_survivors > 0, classes[jnp.maximum(survivors, 0)], 0)
    active_evals = evals.sum(axis=1)
    return preds.astype(jnp.int32), survivors, n_survivors, active_evals


@functools.partial(jax.jit, static_argnames=("interpret",))
def serve_batch(ops: CellOperands, classes: jax.Array, xpad: jax.Array, *,
                interpret: bool = False):
    """One served batch: (B, W) padded search words -> (preds, survivors,
    n_survivors, active_evals), the cells and classes passed as arguments."""
    survive, evals = match_cells(ops, xpad, interpret=interpret)
    return _finalize(survive, evals, classes)


def expand_ranges(feat: jax.Array, q: jax.Array, u: jax.Array) -> jax.Array:
    """Per-bank search words from per-feature range indices: (G, W) column
    tables ``feat``, ``q`` and (B, F) ``u`` -> (G, B, W) uint8 with

      bit[g, b, c] = u[b, feat[g, c]] >= q[g, c].

    ``u[b, j]`` counts the thresholds of feature j, over every bank, below
    request b's value (``forest.executor.encode_ranges``); a column's
    ``q`` places one bank's threshold in that union, so the compare is that
    bank's thermometer bit (``forest.executor.group_columns``).  One select
    per feature picks each column's index, so no gather runs."""
    g, w = feat.shape
    picked = jnp.zeros((g, u.shape[0], w), jnp.int32)
    for j in range(u.shape[1]):
        picked = jnp.where(feat[:, None, :] == j, u[None, :, j, None], picked)
    return (picked >= q[:, None, :]).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def serve_group(ops: CellOperands, rows: jax.Array, d_real: jax.Array,
                feat: jax.Array, q: jax.Array, u: jax.Array, *,
                block_b: int = 128, interpret: bool = False) -> jax.Array:
    """One served batch of a forest plan group: (B, F) int32 range indices
    ``u``, expanded on the device into the group's (G, B, W) search words
    through its column tables ``feat`` and ``q`` (``expand_ranges``) ->
    one (3, G, B) int32 array holding, per bank and request,

      0: the first surviving row (0 when none survives),
      1: the number of surviving rows,
      2: the active row-division evaluations, each row's evals clamped to
         the bank's real division count ``d_real[g]`` (padding divisions
         trivially match).

    Rows at or above a bank's real row count ``rows[g]`` count in none of
    the three.  ``rows``, ``d_real``, ``feat`` and ``q`` are arguments like
    the placed cells, so a plan rebuilt with the same shapes reuses the
    compiled program."""
    xpad = expand_ranges(feat, q, u)
    with jax.named_scope("forest.match"):
        survive, evals = match_cells(ops, xpad, block_b=block_b,
                                     interpret=interpret)
    real = jnp.arange(survive.shape[-1]) < rows[:, None, None]
    survive = jnp.where(real, survive, 0)
    evals = jnp.where(real, jnp.minimum(evals, d_real[:, None, None]), 0)
    return jnp.stack([jnp.argmax(survive, axis=-1), survive.sum(axis=-1),
                      evals.sum(axis=-1)]).astype(jnp.int32)


def finalize_result(
    layout: TCAMLayout,
    preds: np.ndarray,
    survivors: np.ndarray,
    n_survivors: np.ndarray,
    active_evals: np.ndarray,
    *,
    hw: HardwareParams = DEFAULT_HW,
    selective_precharge: bool = True,
) -> SimResult:
    """Assemble the kernel outputs into a ``SimResult``.

    Energy/latency/throughput use the exact float64 formulas of the numpy
    oracle (``core.simulate.simulate``) on the integer activity counts, so the
    JAX path is bit-identical to the oracle on ideal hardware — not merely
    numerically close.
    """
    b = preds.shape[0]
    if selective_precharge:
        active = np.asarray(active_evals).astype(np.int64)
    else:
        active = np.full(b, layout.cells.shape[0] * layout.n_cwd, np.int64)
    energy = active.astype(np.float64) * hw.e_row + hw.e_mem
    fm = f_max(layout.s, hw)
    return SimResult(
        predictions=np.asarray(preds).astype(np.int32),
        survivors=np.asarray(survivors).astype(np.int32),
        n_survivors=np.asarray(n_survivors).astype(np.int32),
        active_evals=active,
        energy_per_dec=energy,
        latency_s=layout.n_cwd * t_cwd(layout.s, hw) + hw.t_mem,
        throughput_seq=fm / layout.n_cwd,
        throughput_pipe=fm / hw.pipeline_ii_cycles,
        s=layout.s,
        n_cwd=layout.n_cwd,
        n_rwd=layout.n_rwd,
    )


def tcam_infer(
    layout: TCAMLayout,
    xbits: np.ndarray,
    *,
    hw: HardwareParams = DEFAULT_HW,
    kmax: Optional[np.ndarray] = None,
    engine: str = "auto",
    selective_precharge: bool = True,
    interpret: Optional[bool] = None,
) -> SimResult:
    """JAX serving path: encoded inputs -> ``SimResult``.  Functionally
    identical to ``core.simulate.simulate`` (tested bit-exact) but runs the
    match on the Pallas kernels.

    .. versionchanged:: 0.8
       This once returned a bare 5-tuple and the returned ``SimResult`` kept
       a one-release tuple-unpacking shim; the shim has expired — use the
       named fields.
    """
    xpad = jnp.asarray(layout.pad_inputs(np.asarray(xbits, np.uint8)))
    km = None if kmax is None else jnp.asarray(kmax)
    survive, evals = tcam_match(
        layout.cells, xpad, layout.s, km, engine=engine, interpret=interpret
    )
    preds, survivors, n_survivors, active = _finalize(
        survive, evals, jnp.asarray(layout.classes)
    )
    return finalize_result(
        layout, preds, survivors, n_survivors, active,
        hw=hw, selective_precharge=selective_precharge,
    )
