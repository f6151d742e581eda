"""End-to-end runs on the CPU at a tiny size: one open-loop and one
closed-loop cell give the contract's last line; a run without a TPU fails."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import deploy
import tiny

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
OPEN = {"loop": "open", "arrivals": "poisson", "rate_per_s": 200}
CLOSED = {"loop": "closed", "in_flight": 32}


@pytest.fixture(autouse=True)
def cache(tmp_path, monkeypatch):
    monkeypatch.setattr(deploy, "CACHE", tmp_path / "cache")


@pytest.mark.parametrize("workload,traffic,trace", [
    ("credit-tree.batch", OPEN, False),
    ("credit-tree.batch", OPEN, True),
    ("credit-tree.batch", CLOSED, False),
    ("covid-rf100.batch", CLOSED, True),
])
def test_tiny_run(workload, traffic, trace):
    cell = tiny.tiny_cell(workload, traffic, trace)
    result, numbers, window = tiny.run_tiny(cell, trace=trace,
                                            seconds=2.0 if trace else 1.0)
    assert KEYS <= set(result) and list(result)[-1] == "check"
    assert result["correct"], numbers
    assert result["attempted"] == window.rec.size > 0
    assert result["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(result["device"])
    names = {m["name"] for m in cell.metrics}
    assert set(result["metrics"]) <= names
    if not trace:
        assert "setup_s" in result["metrics"]
        assert set(result["metrics"]) == names
    else:
        assert {"busy_s", "window_s"} <= set(result["device"])
        assert "breakdown" in result
    json.dumps(result)


def _run(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "credit-tree.batch",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero():
    p = _run(tiny.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(tiny.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("cache", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("metric,reader", [
    ("compute_ms.p50.online", "compute_ms.p50.py"),
    ("compute_ms.p50.batch", "compute_ms.p50.py"),
    ("device_idle_share", "device_idle_share.py"),
    ("device_idle_share.online", "device_idle_share.py"),
    ("queue_ms.p99", "queue_ms.p99.py"),
])
def test_reader_by_longest_prefix(metric, reader):
    import run
    assert run.reader_path(metric).name == reader


def test_prime_serves_every_bucket():
    import repro
    import run
    cfg = tiny.tiny_config("credit-tree")
    dep = deploy.build(cfg)
    server = repro.TCAMServer(dep.compiled,
                              config=repro.ServeConfig(**cfg["serve"]))
    try:
        server.warmup()
        assert run.prime(server, dep.X_test, 7) == \
            sorted(server.policy.buckets)
    finally:
        server.close()


def test_open_loop_readers():
    """The readers kept for an open-loop cell read a tiny open window."""
    import run
    cell = tiny.tiny_cell("credit-tree.batch", OPEN)
    names = ("latency_p50_ms", "generator_lag_ms.p99", "queue_ms.p99",
             "compute_ms.p50.online")
    cell = run.Cell(name=cell.name, chips=1, config=cell.config,
                    traffic=OPEN, metrics=[{"name": n, "unit": "ms"}
                                           for n in names])
    result, numbers, _ = tiny.run_tiny(cell, seconds=1.0)
    assert result["correct"], numbers
    assert set(result["metrics"]) == set(names)
