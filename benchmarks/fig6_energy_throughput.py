"""Paper Fig 6: (a) energy/decision vs throughput per dataset per S,
(b) EDP vs S, (c) % EDP reduction from selective precharge.

Large datasets evaluate on a subsample of test inputs (energy is a mean per
decision; the paper also reports means).
"""
import numpy as np

from repro import enable_compile_cache
from repro.core import synthesize
from repro.core import encode_inputs, simulate

from .common import compiled, emit

SIZES = (16, 32, 64, 128)
MAX_EVAL = 512


def run(datasets=None) -> list[dict]:
    from repro.dt import DATASETS
    rows = []
    for name in datasets or DATASETS:
        c, (Xtr, ytr, Xte, yte) = compiled(name, 128)
        n = min(MAX_EVAL, len(Xte))
        xb = encode_inputs(c.lut, Xte[:n])
        for s in SIZES:
            lay = synthesize(c.lut, s)
            res = simulate(lay, xb)
            res_nosp = simulate(lay, xb, selective_precharge=False)
            edp = res.mean_energy * (1.0 / res.throughput_seq)
            edp_nosp = res_nosp.mean_energy * (1.0 / res_nosp.throughput_seq)
            rows.append({
                "dataset": name,
                "S": s,
                "energy_nj_per_dec": round(res.mean_energy * 1e9, 5),
                "throughput_mdec_s": round(res.throughput_seq / 1e6, 3),
                "throughput_pipe_mdec_s": round(res.throughput_pipe / 1e6, 2),
                "edp_j_s": f"{edp:.3e}",
                "sp_edp_reduction_pct": round(100 * (1 - edp / edp_nosp), 2),
                "tiles": f"{lay.n_rwd}x{lay.n_cwd}",
                "accuracy": round(res.accuracy(yte[:n]), 4),
            })
    return rows


def main():
    enable_compile_cache()
    emit(run(), "Fig 6 — energy / throughput / EDP / SP reduction")


if __name__ == "__main__":
    main()
