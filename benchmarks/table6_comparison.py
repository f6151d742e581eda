"""Paper Table VI: comparison against SOTA DT accelerators on the
traffic-dataset-scale problem (2000 rows x 256 features x 8 bits -> 2048-bit
LUT, S = 128 tiles).

We synthesize the workload exactly as the paper describes: a 2000-path tree
over 256 features with 8-bit (7-threshold) quantized features, compile it
with the DT-HW pipeline, and run the functional simulator on random inputs.
The competitor rows are the paper's reported numbers.
"""
import os

import numpy as np

from repro import enable_compile_cache
from repro.core import compile_tree, train_tree
from repro.core import DEFAULT_HW, encode_inputs, f_max, simulate

from .common import ART, emit

# Accelerator, technology nm, f_clk GHz, throughput dec/s, energy nJ/dec,
# area mm^2, area/bit um^2 — from the paper's Table VI
PAPER_ROWS = [
    ("ASIC [17]", 65, 0.2, 30, 186.7e3, None, None),
    ("ASIC [39]", 65, 0.25, 60, 460e3, None, None),
    ("ASIC IMC [20]", 65, 1.0, 364.4e3, 19.4, None, None),
    ("ACAM [15]", 16, 1.0, 20.8e6, 0.17, 0.266, 0.299),
    ("P-ACAM [15]", 16, 1.0, 333e6, 0.17, 0.266, 0.299),
]
PAPER_DT2CAM = {"throughput": 58.8e6, "energy_nj": 0.098, "area_mm2": 0.07,
                "area_per_bit": 0.017}


def _traffic_like_tree():
    """2000-leaf tree over 256 features quantized to 8 levels."""
    path = os.path.join(ART, "trees", "traffic2000.npz")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    if os.path.exists(path):
        z = np.load(path)
        from repro.core import DecisionTree
        return DecisionTree(z["feature"], z["threshold"], z["left"],
                            z["right"], z["value"], 256, 8)
    rng = np.random.default_rng(0)
    n = 60_000
    X = np.floor(rng.uniform(0, 8, size=(n, 256)))
    # planted rules over a few features + noise for a bushy tree
    y = ((X[:, 0] > 3).astype(int) * 4 + (X[:, 1] > 5).astype(int) * 2
         + (X[:, 2] > 2).astype(int)).astype(np.int64)
    flip = rng.random(n) < 0.35
    y[flip] = rng.integers(0, 8, size=int(flip.sum()))
    tree = train_tree(X, y, max_depth=40, max_leaves=2000)
    np.savez(path, feature=tree.feature, threshold=tree.threshold,
             left=tree.left, right=tree.right, value=tree.value)
    return tree


def run(n_inputs: int = 256) -> list[dict]:
    tree = _traffic_like_tree()
    c = compile_tree(tree, 128)
    rng = np.random.default_rng(1)
    X = np.floor(rng.uniform(0, 8, size=(n_inputs, 256)))
    xb = encode_inputs(c.lut, X)
    res = simulate(c.layout, xb)
    area = c.layout.area_m2() * 1e6          # m^2 -> mm^2
    area_bit = area * 1e6 / c.layout.n_cells  # um^2 / cell

    rows = []
    for name, tech, fclk, thr, e_nj, a, ab in PAPER_ROWS:
        edp = e_nj * 1e-9 * (1.0 / thr)
        rows.append({
            "accelerator": name, "tech_nm": tech, "f_clk_ghz": fclk,
            "throughput_dec_s": f"{thr:.3g}",
            "energy_nj_dec": e_nj,
            "area_mm2": a if a is not None else "-",
            "area_um2_bit": ab if ab is not None else "-",
            "fom_j_s_mm2": f"{edp * a:.3g}" if a else "-",
        })
    for name, thr in (("DT2CAM_128 (ours)", res.throughput_seq),
                      ("P-DT2CAM_128 (ours)", res.throughput_pipe)):
        e_nj = res.mean_energy * 1e9
        edp = res.mean_energy / thr
        rows.append({
            "accelerator": name, "tech_nm": 16, "f_clk_ghz": round(
                f_max(128) / 1e9, 3),
            "throughput_dec_s": f"{thr:.3g}",
            "energy_nj_dec": round(e_nj, 4),
            "area_mm2": round(area, 4),
            "area_um2_bit": round(area_bit, 4),
            "fom_j_s_mm2": f"{edp * area:.3g}",
        })
    rows.append({
        "accelerator": "paper DT2CAM_128 (reference)", "tech_nm": 16,
        "f_clk_ghz": 1.0,
        "throughput_dec_s": f"{PAPER_DT2CAM['throughput']:.3g}",
        "energy_nj_dec": PAPER_DT2CAM["energy_nj"],
        "area_mm2": PAPER_DT2CAM["area_mm2"],
        "area_um2_bit": PAPER_DT2CAM["area_per_bit"],
        "fom_j_s_mm2": "1.22e-19",
    })
    return rows


def main():
    enable_compile_cache()
    emit(run(), "Table VI — SOTA comparison (traffic-scale LUT, S=128)")


if __name__ == "__main__":
    main()
