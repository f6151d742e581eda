"""Pallas TPU kernel: MXU-formulation ternary CAM match with selective
precharge (DESIGN.md §2).

Hardware mapping of the paper's ReCAM array:
  * one column division (width S)  -> one grid step along the innermost
    (sequential) grid axis; TPU grids execute sequentially so the carried
    ``active`` block implements selective precharge *for free*,
  * match-line evaluation          -> two MXU matmuls per division:
    ``mism = X·is0 + (1-X)·is1`` (a don't-care cell sets neither plane and
    contributes nothing — exactly the TCAM semantics),
  * sense-amp threshold            -> ``mism <= kmax[division, row]``
    (kmax = 0 is ideal hardware; per-SA reference-voltage offsets lower to a
    precomputed integer tolerance, keeping the analog model out of the hot
    loop),
  * row-parallel tiles             -> the (batch-block × row-block) grid axes.

Division-major layout: every operand carries the division as its leading
axis, so each block's last two dimensions are either whole array dimensions
(S, 1) or multiples of the (8, 128) TPU tile — for every S in {16..128}
(Table IV) and any number of divisions:
  X    (D, B, S)  block (Bb, S)   — search words of one division,
  is0  (D, S, R)  block (S, Rb)   — planes pre-transposed: a plain X·P matmul,
  kmax (D, 1, R)  block (1, Rb).
The {0,1} operands arrive as f32 (``ops.place_cells`` / ``ops.match_cells``
cast outside the kernel; Mosaic does not lower a u8 -> f32 cast).

Outputs are revisited accumulator blocks (index map ignores the sequential
axis), so the carry lives in VMEM without explicit scratch:
  active (B, R) int32 — after the last division: survive mask,
  evals  (B, R) int32 — number of divisions the row was evaluated in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["tcam_match_pallas"]


def _kernel(x_ref, is0_ref, is1_ref, kmax_ref, active_ref, evals_ref):
    d = pl.program_id(2)

    @pl.when(d == 0)
    def _init():
        active_ref[...] = jnp.ones_like(active_ref)
        evals_ref[...] = jnp.zeros_like(evals_ref)

    x = x_ref[...]                                    # (Bb, S) f32 {0,1}
    # Two MXU matmuls; f32 accumulation is exact (counts <= S).
    mism = jnp.dot(
        x, is0_ref[...], preferred_element_type=jnp.float32
    ) + jnp.dot(1.0 - x, is1_ref[...], preferred_element_type=jnp.float32)
    match = (mism <= kmax_ref[...].astype(jnp.float32)).astype(jnp.int32)

    act = active_ref[...]                             # carried across d
    evals_ref[...] += act                             # active => evaluated
    active_ref[...] = act * match                     # selective precharge


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_r", "interpret")
)
def tcam_match_pallas(
    x: jax.Array,               # (D, B, S) f32 {0,1}
    is0: jax.Array,             # (D, S, R) f32
    is1: jax.Array,             # (D, S, R) f32
    kmax: jax.Array,            # (D, 1, R) int32
    *,
    block_b: int = 128,
    block_r: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (survive (B,R) int32, evals (B,R) int32).  B % block_b == 0
    and R % block_r == 0 — callers pad via ``ops.match_cells``."""
    d, b, s = x.shape
    r = is0.shape[2]
    assert b % block_b == 0 and r % block_r == 0, (b, r, block_b, block_r)
    assert is0.shape == is1.shape == (d, s, r), (is0.shape, (d, s, r))
    assert kmax.shape == (d, 1, r), (kmax.shape, (d, 1, r))

    grid = (b // block_b, r // block_r, d)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_b, s), lambda i, j, k: (k, i, 0)),
            pl.BlockSpec((None, s, block_r), lambda i, j, k: (k, 0, j)),
            pl.BlockSpec((None, s, block_r), lambda i, j, k: (k, 0, j)),
            pl.BlockSpec((None, 1, block_r), lambda i, j, k: (k, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, block_r), lambda i, j, k: (i, j)),
            pl.BlockSpec((block_b, block_r), lambda i, j, k: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, r), jnp.int32),
            jax.ShapeDtypeStruct((b, r), jnp.int32),
        ],
        interpret=interpret,
    )(x, is0, is1, kmax)
