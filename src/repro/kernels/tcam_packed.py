"""Pallas TPU kernel: bit-packed ternary CAM match (VPU formulation).

Beyond-paper optimization for the memory-bound regime (DESIGN.md §2): the
MXU kernel streams f32 bitplanes (8 bytes/cell for both planes); this kernel
packs 32 cells into one uint32 word per plane (1/16 the bytes), and replaces
the matmuls with XOR/AND + ``lax.population_count`` on the VPU:

    mism[b, r] = Σ_w popcount((x[b, w] ^ val[w, r]) & care[w, r])

The selective-precharge carry and the division-major layout are those of
``tcam_match.py``, with SW = S/32 words per division in place of S bits:
  X    (D, B, SW)  block (Bb, SW),
  val  (D, SW, R)  block (SW, Rb)   — rows on the lanes,
  care (D, SW, R)  block (SW, Rb),
  kmax (D, 1, R)   block (1, Rb).
CELL_MM (SAF-induced always-mismatch) is not representable packed —
``ops.select_engine`` picks the MXU kernel when the LUT contains MM cells.

The word loop is a static Python unroll (S/32 <= 4 words per division for
Table IV sizes) of (Bb × Rb) broadcast compares — fully vectorized on the
8x128 VPU lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["tcam_match_packed_pallas"]


def _kernel(sw: int, x_ref, val_ref, care_ref, kmax_ref, active_ref, evals_ref):
    d = pl.program_id(2)

    @pl.when(d == 0)
    def _init():
        active_ref[...] = jnp.ones_like(active_ref)
        evals_ref[...] = jnp.zeros_like(evals_ref)

    mism = jnp.zeros(active_ref.shape, jnp.int32)
    for w in range(sw):  # static unroll: S/32 words per division
        xw = x_ref[:, w:w + 1]             # (Bb, 1) uint32
        vw = val_ref[w:w + 1, :]           # (1, Rb) uint32
        cw = care_ref[w:w + 1, :]
        diff = (xw ^ vw) & cw              # (Bb, Rb)
        mism += jax.lax.population_count(diff).astype(jnp.int32)

    match = (mism <= kmax_ref[...]).astype(jnp.int32)
    act = active_ref[...]
    evals_ref[...] += act
    active_ref[...] = act * match


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_r", "interpret")
)
def tcam_match_packed_pallas(
    xpacked: jax.Array,        # (D, B, SW) uint32
    val: jax.Array,            # (D, SW, R) uint32 — packed is1
    care: jax.Array,           # (D, SW, R) uint32 — packed (is0 | is1)
    kmax: jax.Array,           # (D, 1, R) int32
    *,
    block_b: int = 128,
    block_r: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (survive (B,R) int32, evals (B,R) int32).  B % block_b == 0
    and R % block_r == 0 — callers pad via ``ops.match_cells``."""
    d, b, sw = xpacked.shape
    r = val.shape[2]
    assert b % block_b == 0 and r % block_r == 0, (b, r, block_b, block_r)
    assert val.shape == care.shape == (d, sw, r), (val.shape, (d, sw, r))
    assert kmax.shape == (d, 1, r), (kmax.shape, (d, 1, r))

    grid = (b // block_b, r // block_r, d)
    return pl.pallas_call(
        functools.partial(_kernel, sw),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_b, sw), lambda i, j, k: (k, i, 0)),
            pl.BlockSpec((None, sw, block_r), lambda i, j, k: (k, 0, j)),
            pl.BlockSpec((None, sw, block_r), lambda i, j, k: (k, 0, j)),
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
    )(xpacked, val, care, kmax)
