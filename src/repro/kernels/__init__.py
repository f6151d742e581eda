"""Pallas TPU kernels for the paper's compute hot-spot: the massively
parallel ternary match (TCAM search).  See DESIGN.md §2 for the
analog-ReCAM -> TPU mapping.

  tcam_match.py  — MXU bitplane-matmul kernel, grid-sequential selective
                   precharge (handles all cell states incl. SAF CELL_MM)
  tcam_packed.py — bit-packed XOR/AND/popcount VPU kernel (16x fewer bytes)
  ops.py         — engine selection, cell placement, padding,
                   SA-variability lowering, jit'd serving paths (a tree's
                   batch, a forest plan group's batch)
  ref.py         — pure-jnp oracles both kernels are validated against
  banked.py      — multi-bank (ensemble) batched/vmapped match
"""
from .banked import BANKED_ENGINES, tcam_match_banked
from .ops import (ENGINES, CellOperands, default_interpret, finalize_result,
                  match_cells, place_cells, sa_kmax, select_engine,
                  serve_batch, serve_group, tcam_infer, tcam_match)
from .ref import (pack_bits, tcam_match_banked_ref, tcam_match_packed_ref,
                  tcam_match_ref)
from .tcam_match import tcam_match_pallas
from .tcam_packed import tcam_match_packed_pallas

__all__ = [
    "ENGINES", "default_interpret", "finalize_result", "sa_kmax",
    "select_engine", "tcam_infer", "tcam_match",
    "CellOperands", "place_cells", "match_cells", "serve_batch", "serve_group",
    "pack_bits", "tcam_match_packed_ref", "tcam_match_ref",
    "tcam_match_pallas", "tcam_match_packed_pallas",
    "BANKED_ENGINES", "tcam_match_banked", "tcam_match_banked_ref",
]
