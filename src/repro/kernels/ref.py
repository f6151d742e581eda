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
from jax import lax

__all__ = ["tcam_match_ref", "tcam_match_packed_ref", "tcam_match_banked_ref",
           "tcam_match_banked_stream", "pack_bits"]


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
    """The forest 'banked' engine: one batched einsum over a plan group's
    G same-shape banks, each with its own search words (each tree has its
    own thresholds).  Returns (survive, evals), both (G, B, R) int32, with
    the single-bank selective-precharge semantics of ``tcam_match_ref``:

      mism[g, b, r, d] = Σ_{w∈d} x[g]·is0[g] + (1 - x[g])·is1[g]

    Padding rows carry ``kmax = -1`` (always mismatch) and padding
    divisions are all-CELL_X (trivially match).  Padding rows still report
    one evaluation each and padding divisions inflate the counts, so whoever
    reduces them masks rows at or above each bank's real row count and
    clamps with ``min(evals, d_real)`` (``ops.serve_group``)."""
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


@functools.partial(jax.jit, static_argnames=("s",))
def tcam_match_banked_stream(
    xpad: jax.Array,    # (G, B, W) {0,1} search words, per-bank encodings
    is0: jax.Array,     # (G, R, W)
    is1: jax.Array,     # (G, R, W)
    s: int,
    kmax: jax.Array,    # (G, R, D) int32; -1 rows always mismatch
) -> tuple[jax.Array, jax.Array]:
    """``tcam_match_banked_ref`` streamed division by division, as the
    array precharges them: the same (survive, evals), both (G, B, R) int32,
    bit for bit, without its (G, B, R, D) counts or f32 copies of the
    planes, so a group of deep, wide banks fits the device.

    Each step is one einsum of division d's (G, B, S) search words against
    its (G, R, S) slice of ``is0 - is1``, bf16 operands in {-1, 0, 1}
    accumulated in f32, so the counts are exact; adding the row's count of
    ``is1`` cells in the division gives the mismatch count

      mism[g, b, r] = Σ_{w∈d} x·is0 + (1 - x)·is1
                    = Σ_{w∈d} x·(is0 - is1) + Σ_{w∈d} is1.

    The loop carries the precharge as one (G, B, R) array: the division in
    which each row first mismatched, -1 while it is still alive.  A row
    alive after the last division survives; a row's evaluations are its
    first mismatching division plus one, or D."""
    g, b, w = xpad.shape
    r = is0.shape[1]
    assert w % s == 0, (w, s)
    d = w // s
    bf16 = jnp.bfloat16
    x = xpad.astype(bf16)
    dt = jnp.int8 if d <= 128 else jnp.int32     # holds -1 .. d - 1

    def step(j, died):
        lo = j * s
        xs = lax.dynamic_slice_in_dim(x, lo, s, axis=2)
        p0 = lax.dynamic_slice_in_dim(is0, lo, s, axis=2)
        p1 = lax.dynamic_slice_in_dim(is1, lo, s, axis=2)
        diff = p0.astype(bf16) - p1.astype(bf16)
        m = jnp.einsum("gbs,grs->gbr", xs, diff,
                       preferred_element_type=jnp.float32)
        km = lax.dynamic_index_in_dim(kmax, j, axis=2, keepdims=False)
        km = (km - p1.astype(jnp.int32).sum(axis=2)).astype(jnp.float32)
        miss = m > km[:, None, :]
        return jnp.where((died < 0) & miss, j.astype(dt), died)

    died = lax.fori_loop(0, d, step, jnp.full((g, b, r), -1, dt))
    alive = died < 0
    return (alive.astype(jnp.int32),
            jnp.where(alive, d, died.astype(jnp.int32) + 1))
