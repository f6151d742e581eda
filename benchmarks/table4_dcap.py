"""Paper Table IV: dynamic-range limit -> max cells/row -> chosen S."""
from repro import enable_compile_cache
from repro.core import choose_tile_size, dynamic_range, max_cells_per_row

from .common import emit

PAPER = {0.2: (154, 128), 0.3: (86, 64), 0.4: (53, 32), 0.5: (33, 32),
         0.6: (21, 16)}


def run() -> list[dict]:
    rows = []
    for d_limit, (p_cells, p_s) in PAPER.items():
        cells = max_cells_per_row(d_limit)
        s = choose_tile_size(d_limit)
        rows.append({
            "d_limit_V": d_limit,
            "max_cells_per_row": cells,
            "paper_max_cells": p_cells,
            "chosen_S": s,
            "paper_S": p_s,
            "match": cells == p_cells and s == p_s,
            "d_at_S": round(dynamic_range(s), 4),
        })
    return rows


def main():
    enable_compile_cache()
    emit(run(), "Table IV — D_cap limit vs TCAM row size (Eqn 6)")


if __name__ == "__main__":
    main()
