import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init).  Everything below is ordinary code.

"""Multi-pod dry-run (deliverable e).

For every (architecture × input-shape) cell this lowers + compiles the real
step function (train_step / prefill / serve_step) against ShapeDtypeStruct
stand-ins on the production meshes:

    single-pod  (16, 16)        ("data", "model")        256 chips
    multi-pod   (2, 16, 16)     ("pod", "data", "model") 512 chips

and records, per cell:
  * memory_analysis  — per-device argument/temp/output bytes (proves fit),
  * cost_analysis    — per-device HLO FLOPs & bytes accessed,
  * collective bytes — parsed from the partitioned HLO, by collective type,
into ``artifacts/dryrun/<cell>.json``.

Usage:
  python -m repro.launch.dryrun --arch olmo_1b --shape train_4k
  python -m repro.launch.dryrun --all [--multipod-only | --singlepod-only]
"""
import argparse
import json
import re
import time
import traceback

import jax

from ..configs import ARCHS, SHAPES, get_config, shape_cells, train_settings
from ..optim import AdamWConfig
from ..sharding import make_rules
from ..train import (
    build_decode_step, build_prefill_step, build_train_step, input_specs,
)
from .mesh import make_production_mesh

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "artifacts", "dryrun")

_COLL_RE = re.compile(
    r"=\s*(?:\()?([a-z0-9]+)\[([0-9,]*)\][^=]*?"
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective-type output bytes in the partitioned module (per-chip
    shapes).  `-start/-done` async pairs are counted once (on -start)."""
    out: dict = {}
    for m in _COLL_RE.finditer(hlo_text):
        dt, dims, op = m.group(1), m.group(2), m.group(3)
        if "-done(" in m.group(0):
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[op] = out.get(op, 0) + n * _DTYPE_BYTES.get(dt, 4)
    return out


def _step_fn_and_args(cfg, shape, rules, settings=None):
    settings = settings or {}
    specs = input_specs(cfg, shape, rules, settings)
    if shape.step == "train":
        opt_cfg = AdamWConfig(
            mu_dtype=settings.get("mu_dtype", "float32"),
            nu_dtype=settings.get("nu_dtype", "float32"))
        import jax.numpy as jnp
        fn = build_train_step(cfg, rules, opt_cfg,
                              accum=settings.get("accum", 1),
                              remat=settings.get("remat", "full"),
                              accum_dtype=jnp.dtype(
                                  settings.get("accum_dtype", "float32")))
        args = (specs["state"], specs["batch"])
    elif shape.step == "prefill":
        fn = build_prefill_step(cfg, rules)
        args = (specs["params"], specs["batch"], specs["caches"])
    else:
        fn = build_decode_step(cfg, rules)
        args = (specs["params"], specs["token"], specs["caches"],
                specs["pos"])
    return fn, args


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             donate: bool = True, save: bool = True,
             extra_tag: str = "", settings: dict | None = None) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    if settings is None:
        settings = train_settings(arch) if shape.step == "train" else {}
    if multi_pod and settings.get("accum", 1) > 1:
        # 2x the DP shards on the multi-pod mesh: halve accumulation so
        # per-shard microbatches stay integral
        settings = dict(settings, accum=max(1, settings["accum"] // 2))
    rules = make_rules(
        mesh,
        batch_divisible=(shape.global_batch %
                         (mesh.shape.get("pod", 1) * mesh.shape["data"]) == 0),
        seq_sharded_decode=(shape.step == "decode"),
        seq_parallel=settings.get("seq_parallel", False),
        dp_only=settings.get("dp_only", False),
    )
    fn, args = _step_fn_and_args(cfg, shape, rules, settings)
    t0 = time.time()
    with mesh:
        # donate the mutable state: TrainState for train, caches otherwise
        donate = {"train": (0,), "prefill": (2,), "decode": (2,)}[shape.step]
        jitted = jax.jit(fn, donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    coll = collective_bytes(compiled.as_text())

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": list(mesh.devices.shape),
        "axes": list(mesh.axis_names),
        "step": shape.step,
        "n_devices": int(mesh.devices.size),
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
            "code_bytes": int(getattr(mem, "generated_code_size_in_bytes", 0)),
        },
        "cost": {
            "flops": float(cost.get("flops", -1)),
            "bytes_accessed": float(cost.get("bytes accessed", -1)),
        },
        "collectives": coll,
        "settings": settings,
        "t_lower_s": round(t_lower, 2),
        "t_compile_s": round(t_compile, 2),
        "params_est": cfg.n_params(),
        "params_active_est": cfg.n_active_params(),
    }
    if save:
        os.makedirs(ART_DIR, exist_ok=True)
        tag = "multipod" if multi_pod else "singlepod"
        if extra_tag:
            tag += f"_{extra_tag}"
        path = os.path.join(ART_DIR, f"{arch}__{shape_name}__{tag}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod-only", action="store_true")
    ap.add_argument("--singlepod-only", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in ARCHS:
            for shape in shape_cells(arch):
                cells.append((arch, shape.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = [False, True]
    if args.multipod_only:
        meshes = [True]
    if args.singlepod_only:
        meshes = [False]

    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            tag = "multipod" if mp else "singlepod"
            path = os.path.join(ART_DIR, f"{arch}__{shape}__{tag}.json")
            if args.skip_existing and os.path.exists(path):
                print(f"[skip] {arch} x {shape} x {tag}")
                continue
            try:
                rec = run_cell(arch, shape, multi_pod=mp)
                gb = (rec["memory"]["argument_bytes"]
                      + rec["memory"]["temp_bytes"]) / 2**30
                print(f"[ok]   {arch} x {shape} x {tag}: "
                      f"{gb:.2f} GiB/dev, "
                      f"{rec['cost']['flops']/1e9:.1f} GFLOP/dev, "
                      f"compile {rec['t_compile_s']}s")
            except Exception as e:
                failures += 1
                print(f"[FAIL] {arch} x {shape} x {tag}: {e}")
                traceback.print_exc()
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
