"""Paper Fig 7/8: % accuracy loss under hardware non-idealities
(SAF stuck-at faults, SA reference-voltage variability, input noise) for
Diabetes / Cancer / Covid at two tile sizes."""
import numpy as np

from repro import enable_compile_cache
from repro.core import synthesize
from repro.core import apply_saf, encode_inputs, noisy_inputs, simulate
from repro.core import predict

from .common import compiled, emit

DATASETS = ("diabetes", "cancer", "covid")
SIZES = (32, 128)
SAF = (0.0, 0.001, 0.005, 0.01, 0.05)
SA_SIGMA = (0.0, 0.03, 0.05, 0.1)
IN_SIGMA = (0.0, 0.005, 0.01, 0.05, 0.1)
TRIALS = 3
MAX_EVAL = 400


def run(datasets=DATASETS, trials=TRIALS) -> list[dict]:
    rows = []
    for name in datasets:
        c, (Xtr, ytr, Xte, yte) = compiled(name, 128)
        n = min(MAX_EVAL, len(Xte))
        Xe, ye = Xte[:n], yte[:n]
        golden = float((predict(c.tree, Xe) == ye).mean())
        for s in SIZES:
            lay = synthesize(c.lut, s)
            xb = encode_inputs(c.lut, Xe)

            def acc_loss(p_saf=0.0, sa_sigma=0.0, sigma_in=0.0):
                accs = []
                for t in range(trials):
                    rng = np.random.default_rng(1000 * t + 7)
                    lay_t = lay
                    if p_saf:
                        import dataclasses
                        lay_t = dataclasses.replace(
                            lay, cells=apply_saf(lay.cells, p_saf, p_saf, rng))
                    xb_t = (encode_inputs(c.lut, noisy_inputs(Xe, sigma_in,
                                                              rng))
                            if sigma_in else xb)
                    res = simulate(lay_t, xb_t, sa_sigma=sa_sigma, rng=rng)
                    accs.append(res.accuracy(ye))
                return 100.0 * (golden - float(np.mean(accs)))

            for p in SAF:
                rows.append({"dataset": name, "S": s, "knob": "SAF_pct",
                             "value": p * 100,
                             "acc_loss_pct": round(acc_loss(p_saf=p), 3)})
            for sg in SA_SIGMA:
                rows.append({"dataset": name, "S": s, "knob": "sa_sigma_V",
                             "value": sg,
                             "acc_loss_pct": round(acc_loss(sa_sigma=sg), 3)})
            for si in IN_SIGMA:
                rows.append({"dataset": name, "S": s, "knob": "in_sigma",
                             "value": si,
                             "acc_loss_pct": round(acc_loss(sigma_in=si), 3)})
    return rows


def main():
    enable_compile_cache()
    emit(run(), "Fig 7 — accuracy loss under non-idealities")


if __name__ == "__main__":
    main()
