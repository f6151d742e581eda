"""Paper Table V: LUT sizes and TCAM tile counts per dataset per S.

Runs the full DT-HW compiler on every Table II dataset (embedded Iris +
synthetic stand-ins, DESIGN.md §7) and reports LUT shape + N_rwd x N_cwd
tiles for S in {16, 32, 64, 128}, side by side with the paper's values.
"""
from repro import enable_compile_cache
from repro.core import synthesize
from repro.dt import DATASETS

from .common import compiled, emit

SIZES = (16, 32, 64, 128)


def run() -> list[dict]:
    rows = []
    for name, spec in DATASETS.items():
        c, _ = compiled(name, 128)
        row = {
            "dataset": name,
            "lut_rows": c.lut.n_rows,
            "lut_width": c.lut.width,
            "paper_lut": f"{spec.paper_lut[0]}x{spec.paper_lut[1]}",
        }
        for s in SIZES:
            lay = synthesize(c.lut, s)
            row[f"tiles_S{s}"] = f"{lay.n_rwd}x{lay.n_cwd}"
        rows.append(row)
    return rows


def main():
    enable_compile_cache()
    emit(run(), "Table V — LUT sizes and tile counts")


if __name__ == "__main__":
    main()
