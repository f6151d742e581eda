"""Serve decision trees through ``TCAMServer`` on one TPU chip and check every
answer bit for bit against the numpy oracles.

    python chip_smoke.py

One process holds the chip and runs every phase in turn:

  credit  the Give-Me-Some-Credit tree (Table II shape: 120,269 x 10,
          ``DATASETS["credit"]`` depth and leaf limits, a LUT of about 8476
          rows), fitted from its seeded data and compiled at S=128; 4096 test
          rows served with engine 'auto' (-> packed), then 'mxu'.  Prints
          the batch program's size and compile time with the cell grid baked
          in as constants and passed as arguments.
  covid   1024 rows at S=64 ('auto' -> packed, 'mxu') and at S=16 ('mxu').
  faulty  the credit tree on a chip with stuck-at faults and sense-amp
          offsets (CELL_MM cells: 'auto' -> mxu; kmax != 0), 1024 rows.
  forest  an 8-tree bagged forest on cancer (depth 8, S=128) in forest
          mode on engine 'banked', and through ``ForestExecutor``.

Every phase fails the run unless each Future resolves to a ``RequestResult``
whose prediction, survivor, survivor count, active evaluations and energy
equal the oracle's (``core.simulate`` for trees, ``forest_infer_ref`` for
forests), the server runs the engine asked for on the TPU with interpret mode
off, and no fallback, retry, breaker trip or failed batch was counted.

Each phase prints one JSON line (platform, device kind, compile and warm
batch seconds).  The last line is ``{"ok": true, "device": {...}}``.  Without
a TPU the script exits 1 before any phase and prints no result.  Compiled
programs go to JAX's persistent cache (``repro.enable_compile_cache``).
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# what every phase must find
PLATFORM = "tpu"
INTERPRET = False
SEED = 0
ORACLE_CHUNK = 256      # rows per oracle call: bounds its (B, R, D) arrays

_compile_cache = {"hits": 0, "misses": 0}


def _count_cache_events(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _compile_cache["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _compile_cache["misses"] += 1


class SmokeFailure(RuntimeError):
    pass


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_info() -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def fit(name: str):
    """The dataset's seeded split and its tree, fitted with the dataset's
    own depth and leaf limits (never read from an on-disk cache)."""
    import repro

    spec = repro.DATASETS[name]
    Xtr, ytr, Xte, yte = repro.load_split(name)
    t0 = time.perf_counter()
    tree = repro.train_tree(Xtr, ytr, max_depth=spec.max_depth,
                            max_leaves=spec.max_leaves,
                            min_samples_leaf=spec.min_samples_leaf)
    return tree, Xte, time.perf_counter() - t0


def rows(X: np.ndarray, n: int) -> np.ndarray:
    idx = np.random.default_rng(SEED).integers(0, len(X), size=n)
    return X[idx]


def tree_oracle(layout, lut, X, *, sa_sigma: float = 0.0, rng_state=None):
    """``core.simulate`` on the served grid, in chunks of rows.  With SA
    offsets, every chunk redraws them from the same generator state, so
    all rows see the offsets the server drew."""
    import repro

    out = {k: [] for k in ("predictions", "survivors", "n_survivors",
                           "active_evals", "energy_per_dec")}
    for lo in range(0, len(X), ORACLE_CHUNK):
        rng = None
        if rng_state is not None:
            rng = np.random.default_rng()
            rng.bit_generator.state = rng_state
        res = repro.simulate(layout,
                             repro.encode_inputs(lut, X[lo:lo + ORACLE_CHUNK]),
                             sa_sigma=sa_sigma, rng=rng)
        for k in out:
            out[k].append(getattr(res, k))
    return {k: np.concatenate(v) for k, v in out.items()}


def served(results) -> dict:
    return {
        "predictions": np.array([r.prediction for r in results]),
        "survivors": np.array([r.survivor for r in results]),
        "n_survivors": np.array([r.n_survivors for r in results]),
        "active_evals": np.array([r.active_evals for r in results]),
        "energy_per_dec": np.array([r.energy_j for r in results]),
    }


def require_equal(got: dict, want: dict, what: str) -> None:
    for k, v in want.items():
        g = np.asarray(got[k])
        require(g.shape == v.shape and bool(np.array_equal(g, v)),
                f"{what}: {k} differs from the oracle in "
                f"{int(np.sum(g != v)) if g.shape == v.shape else 'shape'}"
                " entries")


def require_clean(server, engine: str, what: str) -> dict:
    m = server.metrics()
    rel = m["reliability"]
    require(server.engine == engine,
            f"{what}: server runs {server.engine!r}, phase asked for "
            f"{engine!r}")
    require(m["interpret"] is INTERPRET,
            f"{what}: interpret={m['interpret']}, expected {INTERPRET}")
    require(m["device"]["platform"] == PLATFORM,
            f"{what}: served on {m['device']['platform']!r}")
    counts = {"engine_fallbacks": m["engine_fallbacks"],
              "retries": rel["retries"],
              "compute_failures": rel["compute_failures"],
              "breaker_trips": rel["breaker_trips"]}
    require(not any(counts.values()), f"{what}: {counts}")
    require(m["requests_served"] == m["requests_enqueued"],
            f"{what}: served {m['requests_served']} of "
            f"{m['requests_enqueued']}")
    return m


def warm_batch_s(server, repeats: int = 5) -> float:
    """Median seconds of the top bucket's jitted batch program, on device
    inputs, after warmup."""
    bucket = server.policy.buckets[-1]
    fn = server.cache.get(bucket, server.engine)
    if isinstance(fn, list):   # forest: one program per plan group
        f = server.metrics()["layout"]["n_features"]
        u = jnp.zeros((bucket, f), jnp.int32)   # range indices, all groups
        call = lambda: [g(u) for g in fn]
    else:
        w = server.metrics()["layout"]["width"]
        x = jnp.zeros((bucket, w), jnp.uint8)
        call = lambda: fn(x)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def serve(compiled, X, config, *, nonideal=None, rng=None):
    """Build a server, warm every bucket, serve X; returns what it served
    and the timings."""
    import repro

    kw = {} if nonideal is None else {"nonideal": nonideal}
    t0 = time.perf_counter()
    with repro.TCAMServer(compiled, config=config, rng=rng, **kw) as server:
        t_build = time.perf_counter() - t0
        t0 = time.perf_counter()
        n_compiles = server.warmup()
        t_compile = time.perf_counter() - t0
        t0 = time.perf_counter()
        results = server.serve(X)   # a failed Future raises here
        t_serve = time.perf_counter() - t0
        require(all(isinstance(r, repro.RequestResult) for r in results),
                "a Future resolved to something other than RequestResult")
        timing = {
            "build_s": t_build, "compile_s": t_compile,
            "compiles": n_compiles, "serve_s": t_serve,
            "warm_batch_s": warm_batch_s(server),
            "batch_compute_p50_ms": None, "batch_compute_p99_ms": None,
        }
    return server, results, timing


def phase_record(name: str, requested: str, server, m: dict, timing: dict,
                 n: int) -> dict:
    timing["batch_compute_p50_ms"] = m["compute_latency"]["p50_ms"]
    timing["batch_compute_p99_ms"] = m["compute_latency"]["p99_ms"]
    return {"phase": name, "engine": f"{requested}->{server.engine}",
            "rows": n, **m["device"], "interpret": m["interpret"],
            **timing, "bit_exact": True}


def constants_vs_arguments(layout, engine: str, bucket: int = 256) -> dict:
    """Size and compile time of one batch program with the cell grid baked
    in as constants, against the served program that takes it as
    arguments."""
    from jax.experimental.serialize_executable import serialize

    from repro.kernels import place_cells, serve_batch

    x = jnp.zeros((bucket, layout.n_cwd * layout.s), jnp.uint8)
    classes = jnp.asarray(layout.classes)
    ops = place_cells(layout.cells, layout.s, engine=engine)
    variants = {
        "constants": (jax.jit(lambda xp: serve_batch(
            place_cells(layout.cells, layout.s, engine=engine), classes, xp,
            interpret=INTERPRET)), (x,)),
        "arguments": (jax.jit(lambda o, c, xp: serve_batch(
            o, c, xp, interpret=INTERPRET)), (ops, classes, x)),
    }
    out = {}
    for name, (fn, args) in variants.items():
        t0 = time.perf_counter()
        compiled = fn.lower(*args).compile()
        dt = time.perf_counter() - t0
        mem = compiled.memory_analysis()
        out[name] = {
            "compile_s": dt,
            "executable_bytes": len(serialize(compiled)[0]),
            "code_bytes": mem.generated_code_size_in_bytes,
            "argument_bytes": mem.argument_size_in_bytes,
        }
    return out


def tree_phase(name: str, fitted, s: int, engines, n: int, *,
               nonideal=None, measure_program: bool = False) -> None:
    """Serve one tree at tile size ``s`` through each (requested, resolved)
    engine pair and hold every answer to ``core.simulate``."""
    import repro

    tree, Xte, t_fit = fitted
    compiled = repro.compile_tree(tree, s)
    X = rows(Xte, n)
    lay = compiled.layout
    label = f"{name} S={s}" + (" faulty" if nonideal is not None else "")
    if nonideal is None:
        t0 = time.perf_counter()
        want = tree_oracle(lay, compiled.lut, X)
        t_oracle = time.perf_counter() - t0
    for requested, engine in engines:
        config = repro.ServeConfig(engine=requested)
        rng = None
        if nonideal is not None:
            rng = np.random.default_rng(SEED)
        server, results, timing = serve(compiled, X, config,
                                        nonideal=nonideal, rng=rng)
        m = require_clean(server, engine, label)
        if nonideal is not None:
            # replay the server's draws: stuck-at mask, then SA offsets
            replay = np.random.default_rng(SEED)
            mask = repro.core.sample_saf(lay.cells.shape, nonideal.p_sa0,
                                         nonideal.p_sa1, replay)
            grid = repro.core.apply_saf_mask(lay.cells, mask)
            grid[:, 1 + lay.width:] = repro.CELL_X
            live = server.live_layout
            require(bool(np.array_equal(grid, live.cells)),
                    f"{label}: replayed faults differ from the served grid")
            require(bool(np.any(live.cells == repro.CELL_MM)),
                    f"{label}: no CELL_MM cell on the faulty chip")
            t0 = time.perf_counter()
            want = tree_oracle(live, compiled.lut, X,
                               sa_sigma=nonideal.sa_sigma,
                               rng_state=replay.bit_generator.state)
            t_oracle = time.perf_counter() - t0
        require_equal(served(results), want, f"{label} {requested}")
        rec = phase_record(label, requested, server, m, timing, n)
        rec.update(fit_s=t_fit, oracle_s=t_oracle,
                   lut=list(compiled.lut_shape),
                   grid=[int(lay.cells.shape[0]), int(lay.cells.shape[1])],
                   divisions=lay.n_cwd)
        emit(rec)
        if measure_program and requested == "auto":
            emit({"phase": f"{label} batch program", "engine": engine,
                  **constants_vs_arguments(lay, engine)})


def forest_phase(engines, n: int) -> None:
    """8 bagged trees on cancer (depth 8, S=128) in forest mode, held to
    ``forest_infer_ref``; the per-bank survivors through
    ``ForestExecutor``."""
    import repro

    Xtr, ytr, Xte, _ = repro.load_split("cancer")
    trees = repro.train_forest(Xtr, ytr, n_trees=8, max_depth=8, seed=SEED)
    forest = repro.compile_forest(trees, s=128)
    X = rows(Xte, n)
    ref = repro.forest_infer_ref(forest, X)
    hw = repro.DEFAULT_HW
    active = ref.active_evals.sum(axis=0)
    want = {
        "predictions": ref.predictions,
        "survivors": np.full(n, -1),
        "n_survivors": (ref.n_survivors > 0).sum(axis=0),
        "active_evals": active,
        "energy_per_dec": (active.astype(np.float64) * hw.e_row
                           + forest.n_banks * hw.e_mem),
    }
    for engine in engines:
        config = repro.ServeConfig(engine=engine)
        server, results, timing = serve(forest, X, config)
        m = require_clean(server, engine, f"forest {engine}")
        require_equal(served(results), want, f"forest {engine}")
        ex = repro.ForestExecutor(forest, engine=engine)
        require(ex.interpret is INTERPRET,
                f"ForestExecutor interpret={ex.interpret}")
        res = ex.infer(X)
        require_equal(
            {k: getattr(res, k) for k in ("predictions", "survivors",
                                          "n_survivors", "active_evals")},
            {k: getattr(ref, k) for k in ("predictions", "survivors",
                                          "n_survivors", "active_evals")},
            f"ForestExecutor {engine}")
        rec = phase_record("forest cancer x8 S=128", engine, server, m,
                           timing, n)
        rec["banks"] = forest.n_banks
        emit(rec)


def main() -> int:
    dev = device_info()
    if dev["platform"] != PLATFORM:
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev['platform']!r} ({dev['kind']})", file=sys.stderr)
        return 1
    import repro

    cache_dir = repro.enable_compile_cache()
    jax.monitoring.register_event_listener(_count_cache_events)
    t_start = time.perf_counter()

    credit = fit("credit")
    tree_phase("credit", credit, 128, [("auto", "packed"), ("mxu", "mxu")],
               4096, measure_program=True)
    covid = fit("covid")
    tree_phase("covid", covid, 64, [("auto", "packed"), ("mxu", "mxu")], 1024)
    tree_phase("covid", covid, 16, [("mxu", "mxu")], 1024)
    tree_phase("credit", credit, 128, [("auto", "mxu")], 1024,
               nonideal=repro.NonIdealSpec(p_sa0=0.01, p_sa1=0.01,
                                           sa_sigma=0.05))
    forest_phase(["banked"], 512)

    emit({"phase": "total", "seconds": time.perf_counter() - t_start,
          "compile_cache_dir": cache_dir,
          "persistent_cache_hits": _compile_cache["hits"],
          "persistent_cache_misses": _compile_cache["misses"]})
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
