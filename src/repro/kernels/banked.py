"""Banked (multi-array) TCAM match — the ensemble execution hot-spot.

A compiled forest is a set of G banks, each an independent tiled TCAM with its
*own* search-word encoding (each tree has its own thresholds).  Banks in one
execution group share a padded shape (R rows, W = D·S columns, from the
power-of-two bucketing in ``repro.forest.plan``), so the whole group evaluates
as one batched kernel invocation over a leading bank axis:

  mism[g, b, r, d] = Σ_{w∈d} x[g]·is0[g] + (1 - x[g])·is1[g]

with the same selective-precharge cumprod over divisions as the single-bank
kernels (ref.py).  Padding rows carry ``kmax = -1`` (always mismatch) and
padding divisions are all-CELL_X (trivially match).  The match returns
(survive, evals), both (G, B, R); padding rows still report one evaluation
each, and padding divisions inflate the counts, so whoever reduces them
masks rows at or above each bank's real row count and clamps with
``min(evals, d_real)``.

Engines:
  'banked' — one batched einsum over all banks (default jax path; a single
             XLA kernel invocation for the whole group).
  'mxu'    — ``jax.vmap`` of the Pallas MXU bitplane kernel over the bank
             axis (one pallas_call whose grid covers every bank).
  'ref'    — ``jax.vmap`` of the single-bank ``tcam_match_ref`` oracle.

Serving paths place a group's planes once (``ops.place_cells``) and call
``ops.serve_group`` per batch, which does that reduction on the device and
copies back one (3, G, B) array of first survivor, survivor count and
clamped evals instead of the (G, B, R) pair.  ``tcam_match_banked`` places
and matches in one call and returns the unreduced pair.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from .ops import default_interpret, match_cells, place_cells
from .ref import tcam_match_banked_ref

__all__ = ["tcam_match_banked", "tcam_match_banked_ref", "BANKED_ENGINES"]

BANKED_ENGINES = ("banked", "mxu", "ref")


def tcam_match_banked(
    cells: np.ndarray,            # (G, R, W) int8 stacked bank cell grids
    xpad: jax.Array,              # (G, B, W) per-bank padded search words
    s: int,
    kmax: Optional[jax.Array] = None,   # (G, R, D) int32
    *,
    engine: str = "banked",
    block_b: int = 128,
    block_r: int = 128,
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array]:
    """Match a group of same-shape banks in one invocation.

    Returns (survive, evals), both (G, B, R) int32, selective-precharge
    semantics per bank (see module docstring for padding conventions).
    """
    if engine not in BANKED_ENGINES:
        raise ValueError(
            f"unknown banked engine {engine!r}; expected one of {BANKED_ENGINES}"
        )
    interpret = default_interpret() if interpret is None else interpret
    ops = place_cells(cells, s, kmax, engine=engine, block_r=block_r)
    return match_cells(ops, xpad, block_b=block_b, interpret=interpret)
