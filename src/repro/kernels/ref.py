"""Pure-jnp oracles for the TCAM kernels.

Semantics (shared by both kernels, see DESIGN.md §2):

Given encoded search words ``x ∈ {0,1}^{B×W}`` (decoder bit included, padded
to W = n_cwd·S), bitplanes ``is0, is1 ∈ {0,1}^{R×W}`` (CELL_X sets neither,
CELL_MM sets both) and a per-(row, division) mismatch tolerance
``kmax ∈ ℤ^{R×D}`` (0 = ideal hardware; >0 models SA reference-voltage
offsets that would sense a near-match as a match):

  for each column division d (width S, sequential — selective precharge):
    mism[b, r, d]  = Σ_{w∈d} x·is0 + (1-x)·is1
    match[b, r, d] = mism[b, r, d] <= kmax[r, d]
    a row is *active* in division d iff it matched all previous divisions;
    an *active evaluation* is (row, division) pair with the row active.

Returns:
  survive (B, R) int32 — 1 iff the row matched every division,
  evals   (B, R) int32 — number of divisions the row was evaluated in
                          (∈ [1, D]; this drives the energy model).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["tcam_match_ref", "tcam_match_packed_ref", "tcam_match_banked_ref",
           "pack_bits"]


def tcam_match_ref(
    xbits: jax.Array,   # (B, W) any int/float dtype with {0,1} values
    is0: jax.Array,     # (R, W)
    is1: jax.Array,     # (R, W)
    s: int,             # column-division width (tile edge S)
    kmax: jax.Array | None = None,   # (R, D) int32, default ideal (zeros)
) -> tuple[jax.Array, jax.Array]:
    b, w = xbits.shape
    r = is0.shape[0]
    assert w % s == 0, (w, s)
    d = w // s
    x = xbits.astype(jnp.float32).reshape(b, d, s)
    p0 = is0.astype(jnp.float32).reshape(r, d, s)
    p1 = is1.astype(jnp.float32).reshape(r, d, s)
    # (B, R, D) mismatch counts, exact in f32 (counts <= S < 2^24)
    mism = jnp.einsum("bds,rds->brd", x, p0) + jnp.einsum(
        "bds,rds->brd", 1.0 - x, p1
    )
    if kmax is None:
        kmax = jnp.zeros((r, d), jnp.int32)
    match = mism <= kmax[None].astype(jnp.float32)
    # active in division j iff matched divisions 0..j-1
    prior = jnp.cumprod(
        jnp.concatenate([jnp.ones((b, r, 1), bool), match[:, :, :-1]], axis=2),
        axis=2,
    )
    survive = (prior[:, :, -1] & match[:, :, -1]).astype(jnp.int32)
    evals = prior.sum(axis=2).astype(jnp.int32)
    return survive, evals


def pack_bits(bits: jax.Array) -> jax.Array:
    """Pack a (..., W) array of {0,1} into (..., W//32) uint32, little-endian
    within each word (bit i of word j = column 32*j + i).  W % 32 == 0."""
    *lead, w = bits.shape
    assert w % 32 == 0, w
    b = bits.astype(jnp.uint32).reshape(*lead, w // 32, 32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return (b << shifts).sum(axis=-1).astype(jnp.uint32)


def tcam_match_packed_ref(
    xpacked: jax.Array,   # (B, W32) uint32
    val: jax.Array,       # (R, W32) uint32 — packed is1 (stored bit values)
    care: jax.Array,      # (R, W32) uint32 — packed (is0 | is1)
    s: int,               # division width in BITS (multiple of 32)
    kmax: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Packed-domain oracle.  A cell mismatches iff care-bit set and the input
    bit differs from the value bit: popcount((x ^ val) & care).

    CELL_MM (both planes set) is *not representable* in packed form — packed
    kernels are for defect-free LUTs (ideal or SA-variability studies); the
    unpacked kernel handles SAF-injected cells.
    """
    b, w32 = xpacked.shape
    r = val.shape[0]
    assert s % 32 == 0
    sw = s // 32
    assert w32 % sw == 0
    d = w32 // sw
    xw = xpacked.reshape(b, d, sw)
    vw = val.reshape(r, d, sw)
    cw = care.reshape(r, d, sw)
    diff = (xw[:, None] ^ vw[None]) & cw[None]          # (B, R, D, SW)
    mism = jax.lax.population_count(diff).astype(jnp.int32).sum(axis=-1)
    if kmax is None:
        kmax = jnp.zeros((r, d), jnp.int32)
    match = mism <= kmax[None]
    prior = jnp.cumprod(
        jnp.concatenate([jnp.ones((b, r, 1), bool), match[:, :, :-1]], axis=2),
        axis=2,
    )
    survive = (prior[:, :, -1] & match[:, :, -1]).astype(jnp.int32)
    evals = prior.sum(axis=2).astype(jnp.int32)
    return survive, evals


@functools.partial(jax.jit, static_argnames=("s",))
def tcam_match_banked_ref(
    xpad: jax.Array,    # (G, B, W) {0,1} search words, per-bank encodings
    is0: jax.Array,     # (G, R, W)
    is1: jax.Array,     # (G, R, W)
    s: int,
    kmax: jax.Array,    # (G, R, D) int32; -1 rows always mismatch
) -> tuple[jax.Array, jax.Array]:
    """Batched-einsum banked match: (survive, evals), both (G, B, R) int32."""
    g, b, w = xpad.shape
    r = is0.shape[1]
    assert w % s == 0, (w, s)
    d = w // s
    x = xpad.astype(jnp.float32).reshape(g, b, d, s)
    p0 = is0.astype(jnp.float32).reshape(g, r, d, s)
    p1 = is1.astype(jnp.float32).reshape(g, r, d, s)
    # (G, B, R, D) mismatch counts, exact in f32 (counts <= S < 2^24)
    mism = jnp.einsum("gbds,grds->gbrd", x, p0) + jnp.einsum(
        "gbds,grds->gbrd", 1.0 - x, p1
    )
    match = mism <= kmax[:, None].astype(jnp.float32)
    if d == 1:
        # single division: every row is evaluated exactly once and survives
        # iff it matches — skip the cumprod (slow XLA constant-fold)
        return (
            match[:, :, :, 0].astype(jnp.int32),
            jnp.ones((g, b, r), jnp.int32),
        )
    prior = jnp.cumprod(
        jnp.concatenate(
            [jnp.ones((g, b, r, 1), bool), match[:, :, :, :-1]], axis=3
        ),
        axis=3,
    )
    survive = (prior[:, :, :, -1] & match[:, :, :, -1]).astype(jnp.int32)
    evals = prior.sum(axis=3).astype(jnp.int32)
    return survive, evals
