"""TCAM-kernel benchmark: engines (numpy oracle / jnp ref / MXU formulation /
bit-packed) on the Covid LUT and the traffic-scale LUT.

Wall-clock here is CPU (XLA-compiled jnp for ref; the Pallas kernels run
interpret=True and are validated for correctness, not speed).  The TPU story
is the **bytes model**: per input batch the match must stream the LUT planes
from HBM, so

    MXU engine    ~ 2 planes x f32  = 8 B/cell
    packed engine ~ 2 words / 32    = 0.25 B/cell   (32x fewer bytes)

which moves the kernel's roofline from memory-bound toward compute-bound —
the paper-representative §Perf hillclimb in EXPERIMENTS.md.
"""
import time

import numpy as np

import jax

from repro import enable_compile_cache
from repro.core import bitplanes, encode_inputs, simulate
from repro.kernels import tcam_match_ref, tcam_match_packed_ref, pack_bits

from .common import compiled, emit


def _bench(fn, *args, reps=3):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fn(*args)
        jax.block_until_ready(r)
    return (time.perf_counter() - t0) / reps


def run() -> list[dict]:
    import jax.numpy as jnp
    rows = []
    for name, s, batch in (("covid", 64, 512), ("covid", 128, 512)):
        c, (Xtr, ytr, Xte, yte) = compiled(name, s)
        from repro.core import synthesize
        lay = synthesize(c.lut, s)
        xb = encode_inputs(c.lut, Xte[:batch])
        xp = lay.pad_inputs(xb)
        is0, is1 = bitplanes(lay.cells)
        r, w = lay.cells.shape

        t_np = _bench(lambda: simulate(lay, xb), reps=2)
        j_ref = jax.jit(lambda x, a, b: tcam_match_ref(x, a, b, s))
        t_ref = _bench(j_ref, jnp.asarray(xp, jnp.float32),
                       jnp.asarray(is0), jnp.asarray(is1))
        xq = pack_bits(jnp.asarray(xp))
        val = pack_bits(jnp.asarray(is1))
        care = pack_bits(jnp.asarray(is0 | is1))
        j_pk = jax.jit(lambda x, v, cc: tcam_match_packed_ref(x, v, cc, s))
        t_pk = _bench(j_pk, xq, val, care)

        cells = r * w
        rows.append({
            "workload": f"{name}_S{s}", "rows": r, "width": w,
            "batch": batch,
            "numpy_sim_ms": round(t_np * 1e3, 2),
            "jnp_mxu_ms": round(t_ref * 1e3, 2),
            "jnp_packed_ms": round(t_pk * 1e3, 2),
            "speedup_packed_vs_numpy": round(t_np / t_pk, 1),
            "bytes_per_cell_mxu": 8.0,
            "bytes_per_cell_packed": 0.25,
            "tpu_mem_term_mxu_us": round(cells * 8 / 819e9 * 1e6, 2),
            "tpu_mem_term_packed_us": round(cells * 0.25 / 819e9 * 1e6, 3),
        })
    return rows


def main():
    enable_compile_cache()
    emit(run(), "Kernel engines — functional throughput + TPU bytes model")


if __name__ == "__main__":
    main()
