"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload credit-tree.online --seed 7 \\
        --seconds 10 --trace 0

The cell (``BENCHMARK.json``: ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``); each metric is read by the reader in
``bench/metrics/`` named by the longest dotted prefix of its name
(``compute_ms.p50.batch`` by ``compute_ms.p50.py``).  One process holds the chip and does, in turn:

1. set-up, timed as ``setup_s``: build the deployment (``deploy.py``), start
   a ``TCAMServer`` on it, warm every bucket (``warmup``) and prime it with
   warm-up traffic through ``submit`` until every bucket has served a batch;
2. the window: drive ``submit`` from the traffic mix for ``--seconds``
   (``loadgen.py``); with ``--trace 1`` a slice of it is profiled
   (``tracing.py``);
3. the check: a sample of the window's answers against the plain reference
   (``check.py``, ``reference.py``);
4. the last line of standard output, one JSON object: ``--trace 0`` reports
   the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics.

Compilations inside the window are counted through ``jax.monitoring`` and
printed on an earlier line, with whether set-up found the fitted and compiled
model and every program in their caches (``cold`` is true for a run that
fitted, compiled or missed the compile cache).  Without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

# A --trace 1 window: its first third runs unprofiled (the host-clock
# per-layer metrics read the requests due in it), the device is profiled
# from a third of the way in (a fifth of the window, at most DEVICE_SLICE_S)
# and the host, with the Python tracer, from two thirds of the way in (a
# tenth, at most HOST_SLICE_S).
DEVICE_SLICE_S = 3.0
HOST_SLICE_S = 1.0


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: list          # the BENCHMARK.json entries this run reports


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""

    window: object         # loadgen.Window
    setup_s: float
    trace: object          # tracing.Summary, or None without --trace 1
    trace_host_bounds: tuple  # the device slice on the host's clock
    banks: list            # (rows, columns) of each TCAM bank
    device_kind: str

    @property
    def unprofiled(self):
        """The records of requests due before any profiling started."""
        rec = self.window.rec
        if self.trace is None:
            return rec
        return rec[rec["due"] < self.trace_host_bounds[0]]


def load_cell(name: str, trace: bool, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == w["config"])
    kind = "per_layer" if trace else "end_to_end"
    metrics = [m for m in bench[kind]
               if name in m.get("workloads", [name])]
    return Cell(
        name=name, chips=w["chips"],
        config=json.loads((root / config["file"]).read_text()),
        traffic=json.loads(
            (BENCH / "traffic" / f"{w['traffic']}.json").read_text()),
        metrics=metrics)


def require_chips(n: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(f"needs {n} TPU chip(s); JAX found {len(devs)} "
                     f"{devs[0].platform} device(s) ({devs[0].device_kind})")
    return devs[:n]


class CompileCounter:
    """Counts compilations (persistent-cache loads included), traces, and
    the persistent compile cache's hits and misses."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")
    CACHE = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self) -> None:
        import jax
        self.counts = dict.fromkeys(self.EVENTS, 0)
        self.cache = dict.fromkeys(self.CACHE.values(), 0)
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event in self.counts:
            self.counts[event] += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event in self.CACHE:
            self.cache[self.CACHE[event]] += 1

    def total(self) -> int:
        return sum(self.counts.values())


def reader_path(metric: str) -> Path:
    """The reader of ``metric``: ``bench/metrics/<p>.py`` for the longest
    dotted prefix ``p`` of its name that has one."""
    parts = metric.split(".")
    for k in range(len(parts), 0, -1):
        path = BENCH / "metrics" / (".".join(parts[:k]) + ".py")
        if path.is_file():
            return path
    raise FileNotFoundError(f"no reader in bench/metrics/ for {metric!r}")


def prime(server, X, seed: int, rounds: int = 4) -> list:
    """Warm-up traffic: bursts of rows through ``submit`` until every bucket
    has served a batch, so that no bucket's first batch (its first copy of
    inputs to the device and of results back) falls in the window.  A burst
    of ``b`` rows is served as one batch of bucket ``b`` when the batcher
    takes it whole; the largest bucket gets two bursts' worth.  Returns the
    buckets served."""
    rng = np.random.default_rng([seed, 3])
    buckets = server.policy.buckets
    seen = set()
    for _ in range(rounds):
        for b in buckets:
            if b in seen:
                continue
            n = 2 * b if b == buckets[-1] else b
            futs = [server.submit(X[i]) for i in rng.integers(0, len(X), n)]
            seen.update(f.result(timeout=120).bucket for f in futs)
        if seen.issuperset(buckets):
            break
    return sorted(seen)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, devices: list):
    """One run; returns (result line, check numbers, window)."""
    import jax

    import check
    import deploy
    import loadgen
    import repro
    from tracing import SliceProfiler, reduce

    compiles = CompileCounter()
    dep = deploy.build(cell.config)
    server = repro.TCAMServer(
        dep.compiled, config=repro.ServeConfig(**cell.config["serve"]))
    try:
        n_warm = server.warmup()
        primed = prime(server, dep.X_test, seed)
        before = compiles.total()
        setup_s = time.perf_counter() - t_start
        profs = []
        if trace:
            profs = [SliceProfiler(seconds / 3, min(DEVICE_SLICE_S,
                                                    seconds / 5), False),
                     SliceProfiler(2 * seconds / 3, min(HOST_SLICE_S,
                                                        seconds / 10), True)]

        def arm(t0):
            for p in profs:
                p.arm(t0)

        window = loadgen.drive(server.submit, cell.traffic, dep.X_test,
                               seconds, seed, on_start=arm)
        in_window = compiles.total() - before
        stats = [d.memory_stats() or {} for d in devices]
        mem_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
        info = {"engine": server.engine, "interpret": server.interpret,
                "warmup_compiles": n_warm, "primed_buckets": primed,
                "fit_cached": dep.cached["fit"],
                "compiled_cached": dep.cached["compiled"],
                "compile_cache": compiles.cache,
                "cold": not all(dep.cached.values())
                or compiles.cache["misses"] > 0,
                "window_compiles": in_window}
    finally:
        server.close()
    summary = None
    if profs:
        summary = reduce(profs[0].load(), len(devices))
        summary.idle_gaps = reduce(profs[1].load(), len(devices)).idle_gaps
    print(json.dumps({"workload": cell.name, "seed": seed, **info}),
          flush=True)

    reference = deploy.reference(dep)
    numbers = check.run_check(window.rec, reference, dep.X_test,
                              cell.config["check_sample"], seed)
    dev = devices[0]
    run = Run(window=window, setup_s=setup_s, trace=summary,
              trace_host_bounds=profs[0].host_bounds if profs else None,
              banks=[(b.rows, b.cols) for b in reference.banks],
              device_kind=dev.device_kind)
    metrics = {}
    for m in cell.metrics:
        reader = deploy.load_module(reader_path(m["name"]))
        v = reader.read(run)
        if v is not None and np.isfinite(v):
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    rec = window.rec
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
    result = {"correct": check.verdict(numbers),
              "attempted": int(rec.size),
              "failed": int((~rec["ok"]).sum()),
              "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["check"] = {k: {"value": v, "limit": check.LIMITS.get(k)}
                       for k, v in numbers.items()}
    return result, numbers, window


def report(result: dict) -> None:
    """The numbers compared, beside their limits, as the last lines on
    standard error; the result as the last line on standard output."""
    for k, v in result["check"].items():
        lim = "" if v["limit"] is None else f" limit {v['limit']}"
        print(f"check {k} {v['value']}{lim}", file=sys.stderr)
    print(f"check correct {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"run.py: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 2
    cell = load_cell(args.workload, bool(args.trace))
    try:
        devices = require_chips(cell.chips)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    import repro
    repro.enable_compile_cache()
    result, _, _ = run_cell(cell, abs(args.seed), args.seconds,
                         bool(args.trace), t_start=T_START, devices=devices)
    report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
