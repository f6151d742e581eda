"""Quickstart: the paper's Fig 2 pipeline on the real (embedded) Iris data.

    PYTHONPATH=src python examples/quickstart.py

Trains a CART tree, compiles it through the DT-HW pipeline (parse -> column
reduction -> ternary adaptive encoding), synthesizes S x S ReCAM tiles, and
runs the functional simulation — verifying the paper's central claim that
the TCAM-simulated accuracy equals the Python golden-DT accuracy.
"""
import numpy as np

from repro import enable_compile_cache
from repro.core import DT2CAM, NonIdealSpec
from repro.dt import load_split


def main():
    enable_compile_cache()
    Xtr, ytr, Xte, yte = load_split("iris")
    model = DT2CAM(s=16, max_depth=5).fit(Xtr, ytr)

    c = model.compiled
    print(f"tree: {c.tree.n_leaves} leaves, depth {c.tree.depth()}")
    print(f"LUT:  {c.lut.n_rows} x {c.lut.width} ternary cells "
          f"(paper Table V: 9 x 12)")
    print(f"tiles: {c.layout.n_rwd} x {c.layout.n_cwd} of "
          f"{c.layout.s} x {c.layout.s}")

    res = model.infer(Xte)
    golden = model.golden_accuracy(Xte, yte)
    print(f"golden DT accuracy : {golden:.4f}")
    print(f"TCAM sim accuracy  : {res.accuracy(yte):.4f}  "
          f"(must match exactly)")
    assert res.accuracy(yte) == golden

    print(f"energy  : {res.mean_energy * 1e12:.3f} pJ/decision")
    print(f"latency : {res.latency_s * 1e9:.3f} ns/decision")
    print(f"thruput : {res.throughput_seq / 1e6:.1f} M dec/s sequential, "
          f"{res.throughput_pipe / 1e6:.1f} M dec/s pipelined")

    # robustness: stuck-at faults
    faulty = model.infer(Xte, nonideal=NonIdealSpec(p_sa0=0.01, p_sa1=0.01))
    print(f"accuracy w/ 1% SAF : {faulty.accuracy(yte):.4f}")


if __name__ == "__main__":
    main()
