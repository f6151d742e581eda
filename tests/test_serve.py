"""Serving engine: adaptive batching, bucket-bounded jit compiles, metrics,
engine fallback, and the >=1k-request smoke test from the PR acceptance
criteria."""
import threading
import time

import numpy as np
import pytest

from repro.core import DT2CAM, NonIdealSpec
from repro.dt import load_split
from repro.serve import (AdaptiveBatcher, BucketPolicy, CompileCache,
                         ComputeFailed, DeadlineExceeded, LatencyStats,
                         Rejected, ServeConfig, TCAMServer)
from repro.serve.metrics import BATCH_CAPACITY, PHASES


@pytest.fixture(scope="module")
def iris_model():
    Xtr, ytr, Xte, yte = load_split("iris")
    return DT2CAM(s=16, max_depth=5).fit(Xtr, ytr), Xte, yte


# --------------------------------------------------------------------------
# pure-logic units
# --------------------------------------------------------------------------
def test_bucket_policy_ladder_and_lookup():
    p = BucketPolicy(max_batch=100, min_bucket=8)
    assert p.buckets == (8, 16, 32, 64, 100)
    assert p.bucket_for(1) == 8
    assert p.bucket_for(8) == 8
    assert p.bucket_for(9) == 16
    assert p.bucket_for(65) == 100
    assert p.bucket_for(100) == 100
    with pytest.raises(ValueError):
        p.bucket_for(101)
    with pytest.raises(ValueError):
        p.bucket_for(0)
    with pytest.raises(ValueError):
        BucketPolicy(max_batch=4, min_bucket=8)


def test_adaptive_batcher_flush_rules():
    b = AdaptiveBatcher(max_batch=4, max_delay_s=1.0)
    assert not b.ready(0.0) and b.deadline() is None
    b.add("a", 0.0)
    assert b.deadline() == 1.0
    assert not b.ready(0.5)          # neither full nor expired
    assert b.ready(1.0)              # oldest hit its deadline
    for x in "bcd":
        b.add(x, 0.1)
    assert b.ready(0.2)              # full
    batch = b.pop_batch()
    assert [p.item for p in batch] == list("abcd")   # FIFO order
    assert len(b) == 0 and not b.ready(2.0)


def test_adaptive_batcher_expiry_awareness():
    b = AdaptiveBatcher(max_batch=8, max_delay_s=1.0, timeout_s=0.1)
    b.add("a", 0.0)
    b.add("b", 0.05)
    assert b.deadline() == pytest.approx(0.1)    # expiry before flush
    assert not b.flush_due(0.2) and b.ready(0.2)  # woken by expiry alone
    b.add("c", 0.15)
    assert [p.item for p in b.pop_expired(0.2)] == ["a", "b"]
    assert [p.item for p in b.pop_expired(0.2)] == []   # "c" still live
    assert len(b) == 1
    assert b.deadline() == pytest.approx(0.25)
    with pytest.raises(ValueError):
        AdaptiveBatcher(max_batch=8, max_delay_s=1.0, timeout_s=-1.0)
    # without a timeout the old flush-only semantics are unchanged
    nb = AdaptiveBatcher(max_batch=8, max_delay_s=1.0)
    nb.add("x", 0.0)
    assert nb.deadline() == 1.0 and nb.pop_expired(100.0) == []


def test_latency_stats_percentiles():
    ls = LatencyStats(capacity=100)
    for v in np.linspace(0.001, 0.1, 100):
        ls.record(float(v))
    assert ls.count == 100
    assert ls.p50 == pytest.approx(0.0505, rel=0.05)
    assert ls.p99 > ls.p50
    assert np.isnan(LatencyStats().p50)


def test_latency_stats_empty_window_is_nan_everywhere():
    ls = LatencyStats()
    assert ls.count == 0
    for v in (ls.p50, ls.p99, ls.mean, ls.percentile(10.0)):
        assert np.isnan(v)
    s = ls.summary_ms()
    assert np.isnan(s["p50_ms"]) and np.isnan(s["p99_ms"])
    assert np.isnan(s["mean_ms"]) and s["count"] == 0.0


def test_latency_stats_single_sample_collapses_percentiles():
    ls = LatencyStats()
    ls.record(0.042)
    assert ls.count == 1
    assert ls.p50 == ls.p99 == ls.mean == pytest.approx(0.042)
    s = ls.summary_ms()
    assert s["p50_ms"] == s["p99_ms"] == pytest.approx(42.0)


def test_latency_stats_identical_samples_p50_equals_p99():
    ls = LatencyStats(capacity=16)
    for _ in range(50):                  # also wraps the bounded ring
        ls.record(0.007)
    assert ls.count == 50
    assert ls.p50 == ls.p99 == pytest.approx(0.007)
    assert ls.percentile(0.0) == ls.percentile(100.0) == pytest.approx(0.007)


def test_compile_cache_lru_bound_and_eviction_counter():
    built = []

    def builder(bucket, engine):
        built.append((bucket, engine))
        return lambda x, b=bucket: (b, x)

    c = CompileCache(builder, "lay0", maxsize=2)
    c.get(8, "mxu")
    c.get(16, "mxu")
    assert c.get(8, "mxu")(0) == (8, 0)          # hit, now most recent
    c.get(32, "mxu")                             # evicts LRU key (16)
    assert len(c) == 2 and c.evictions == 1
    c.get(16, "mxu")                             # rebuild: a fresh miss
    assert built == [(8, "mxu"), (16, "mxu"), (32, "mxu"), (16, "mxu")]
    st = c.stats()
    assert st == {"hits": 1, "misses": 4, "evictions": 2,
                  "size": 2, "maxsize": 2}
    with pytest.raises(ValueError):
        CompileCache(builder, "lay0", maxsize=0)
    # unbounded default: nothing ever evicted
    u = CompileCache(builder, "lay1")
    for b in (8, 16, 32, 64):
        u.get(b, "ref")
    assert len(u) == 4 and u.evictions == 0
    assert u.stats()["maxsize"] is None


def test_server_honors_compile_cache_size(iris_model):
    m, Xte, _ = iris_model
    cfg = ServeConfig(background=False, max_batch=64, min_bucket=8,
                      engine="ref", compile_cache_size=2)
    srv = TCAMServer(m.compiled, config=cfg)
    srv.warmup()                                 # 4 buckets through size-2 LRU
    st = srv.cache.stats()
    assert st["size"] <= 2 and st["evictions"] >= 2
    res = srv.serve(Xte[:5])                     # evicted shapes rebuild fine
    assert len(res) == 5
    srv.close()


def test_fault_hook_old_name_expired(iris_model):
    """The compute_fault_hook -> fault_injection_hook deprecation window is
    over: the old name now raises an actionable AttributeError both ways
    (see README migration notes)."""
    m, _, _ = iris_model
    srv = TCAMServer(m.compiled, config=ServeConfig(background=False))
    with pytest.raises(AttributeError, match="fault_injection_hook"):
        srv.compute_fault_hook = lambda _X: None
    with pytest.raises(AttributeError, match="fault_injection_hook"):
        _ = srv.compute_fault_hook
    srv.fault_injection_hook = None          # the new name still works
    assert srv.fault_injection_hook is None
    srv.close()


# --------------------------------------------------------------------------
# acceptance smoke: >= 1k requests, bounded compiles
# --------------------------------------------------------------------------
def test_smoke_1k_requests_bucket_batching(iris_model):
    m, Xte, yte = iris_model
    n_requests = 1024
    cfg = ServeConfig(max_batch=64, min_bucket=8, background=False)
    srv = TCAMServer(m.compiled, config=cfg)

    rng = np.random.default_rng(0)
    idx = rng.integers(0, len(Xte), size=n_requests)
    futs = []
    sent = 0
    while sent < n_requests:                     # bursty arrivals
        burst = int(rng.integers(1, 2 * cfg.max_batch))
        take = idx[sent : sent + burst]
        futs += srv.submit_many(Xte[take])
        sent += len(take)
        while srv.pump(force=True):
            pass
    srv.drain()

    res = [f.result() for f in futs]
    assert len(res) == n_requests
    stats = srv.metrics()
    assert stats["requests_served"] == n_requests

    # jit cache misses bounded by buckets x engines (acceptance criterion)
    n_buckets = len(srv.policy.buckets)
    assert stats["jit_cache"]["misses"] <= n_buckets * 1
    assert stats["jit_cache"]["hits"] == stats["batches"] - stats["jit_cache"]["misses"]
    # multiple buckets actually exercised by the bursty arrivals
    assert len({r.bucket for r in res}) > 1

    # served decisions identical to the one-shot jax backend
    preds = np.array([r.prediction for r in res])
    ref = m.infer(Xte[idx], backend="jax")
    np.testing.assert_array_equal(preds, ref.predictions)
    np.testing.assert_array_equal(
        np.array([r.energy_j for r in res]), ref.energy_per_dec
    )
    assert stats["total_latency"]["p99_ms"] >= stats["total_latency"]["p50_ms"]
    srv.close()


def test_background_worker_futures_and_deadline_flush(iris_model):
    m, Xte, _ = iris_model
    cfg = ServeConfig(max_batch=512, min_bucket=4, max_delay_s=0.01)
    with TCAMServer(m.compiled, config=cfg) as srv:
        futs = srv.submit_many(Xte[:3])          # far below max_batch
        res = [f.result(timeout=30) for f in futs]   # deadline must flush
        assert all(r.bucket == 4 for r in res)
        stats = srv.metrics()
        assert stats["deadline_flushes"] >= 1
        assert stats["requests_served"] == 3


def test_warmup_precompiles_all_buckets(iris_model):
    m, _, _ = iris_model
    cfg = ServeConfig(max_batch=32, min_bucket=8, background=False)
    srv = TCAMServer(m.compiled, config=cfg)
    assert srv.warmup() == len(srv.policy.buckets)
    assert srv.warmup() == 0                     # second call: all hits
    srv.close()


def test_engine_fallback_when_packed_illegal(iris_model):
    m, Xte, _ = iris_model                       # s=16: packed illegal
    cfg = ServeConfig(engine="packed", background=False, max_batch=8)
    with pytest.warns(RuntimeWarning, match="falling back"):
        srv = TCAMServer(m.compiled, config=cfg)
    assert srv.engine == "mxu"
    res = srv.serve(Xte[:5])
    assert len(res) == 5 and all(r.engine == "mxu" for r in res)
    assert srv.metrics()["engine_fallbacks"] == 1
    srv.close()


def test_packed_engine_served_when_legal():
    Xtr, ytr, Xte, _ = load_split("iris")
    m = DT2CAM(s=32, max_depth=5).fit(Xtr, ytr)
    cfg = ServeConfig(background=False, max_batch=8)
    srv = TCAMServer(m.compiled, config=cfg)
    assert srv.engine == "packed"
    res = srv.serve(Xte[:8])
    ref = m.infer(Xte[:8], backend="jax", engine="packed")
    np.testing.assert_array_equal(
        np.array([r.prediction for r in res]), ref.predictions
    )
    srv.close()


def test_nonideal_serving_runs_and_counts(iris_model):
    m, Xte, yte = iris_model
    cfg = ServeConfig(background=False, max_batch=16)
    srv = TCAMServer(
        m.compiled, config=cfg,
        nonideal=NonIdealSpec(p_sa0=0.01, sa_sigma=0.02, sigma_in=0.02),
        rng=np.random.default_rng(5),
    )
    res = srv.serve(np.tile(Xte, (3, 1)))
    assert len(res) == 3 * len(Xte)
    acc = (np.array([r.prediction for r in res]) == np.tile(yte, 3)).mean()
    assert acc > 0.5                             # degraded but functional
    srv.close()


def test_submit_after_close_rejected(iris_model):
    m, Xte, _ = iris_model
    srv = TCAMServer(m.compiled, config=ServeConfig(background=False))
    srv.close()
    with pytest.raises(RuntimeError, match="closed"):
        srv.submit(Xte[0])


def test_concurrent_submitters_background(iris_model):
    """Several client threads pushing into one server: everything resolves
    and counts line up."""
    m, Xte, _ = iris_model
    cfg = ServeConfig(max_batch=32, min_bucket=8, max_delay_s=0.005)
    results = []
    lock = threading.Lock()
    with TCAMServer(m.compiled, config=cfg) as srv:
        def client(seed):
            rng = np.random.default_rng(seed)
            futs = [srv.submit(Xte[rng.integers(0, len(Xte))])
                    for _ in range(50)]
            out = [f.result(timeout=60) for f in futs]
            with lock:
                results.extend(out)

        threads = [threading.Thread(target=client, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = srv.metrics()
    assert len(results) == 200
    assert stats["requests_served"] == 200
    assert stats["jit_cache"]["misses"] <= len(srv.policy.buckets)


# --------------------------------------------------------------------------
# serving protections: worker survival, load shedding, deadlines, retries
# --------------------------------------------------------------------------
def test_worker_survives_batch_compute_failure(iris_model):
    """A batch whose kernel raises fails its futures with ComputeFailed,
    decrements the outstanding count, and leaves the worker alive for the
    next batch."""
    m, Xte, _ = iris_model
    cfg = ServeConfig(max_batch=8, min_bucket=8, max_delay_s=0.001)
    with TCAMServer(m.compiled, config=cfg) as srv:
        boom = [True]

        def hook(_X):
            if boom[0]:
                raise RuntimeError("injected device fault")

        srv.fault_injection_hook = hook
        futs = srv.submit_many(Xte[:8])
        srv.drain(timeout=30)
        for f in futs:
            err = f.exception(timeout=5)
            assert isinstance(err, ComputeFailed)
            assert isinstance(err.__cause__, RuntimeError)
        assert srv._outstanding == 0
        assert srv.metrics()["reliability"]["compute_failures"] == 1

        boom[0] = False                          # worker must still be alive
        res = [f.result(timeout=30) for f in srv.submit_many(Xte[:8])]
        assert len(res) == 8
        assert srv._outstanding == 0


def test_sync_compute_failure_raises_and_recovers(iris_model):
    m, Xte, _ = iris_model
    cfg = ServeConfig(background=False, max_batch=8)
    srv = TCAMServer(m.compiled, config=cfg)

    def hook(_X):
        raise RuntimeError("injected device fault")

    srv.fault_injection_hook = hook
    futs = srv.submit_many(Xte[:4])
    with pytest.raises(ComputeFailed):           # sync mode surfaces the error
        srv.drain()
    assert all(isinstance(f.exception(), ComputeFailed) for f in futs)
    assert srv._outstanding == 0
    srv.fault_injection_hook = None
    assert len(srv.serve(Xte[:4])) == 4
    srv.close()


def test_drain_timeout_raises_with_counters_intact(iris_model):
    m, Xte, _ = iris_model
    cfg = ServeConfig(max_batch=4, min_bucket=4, max_delay_s=0.001)
    gate = threading.Event()
    with TCAMServer(m.compiled, config=cfg) as srv:
        srv.fault_injection_hook = lambda _X: gate.wait(30)
        futs = srv.submit_many(Xte[:4])
        with pytest.raises(TimeoutError):
            srv.drain(timeout=0.1)
        gate.set()                               # un-stick the worker
        srv.drain(timeout=30)
        assert all(f.result(timeout=5) for f in futs)
        assert srv._outstanding == 0
        assert srv.metrics()["requests_served"] == 4


def test_bounded_queue_sheds_with_typed_rejection(iris_model):
    m, Xte, _ = iris_model
    cfg = ServeConfig(max_batch=4, min_bucket=4, max_delay_s=0.001,
                      max_queue=4)
    gate = threading.Event()
    with TCAMServer(m.compiled, config=cfg) as srv:
        srv.fault_injection_hook = lambda _X: gate.wait(30)
        futs = [srv.submit(Xte[i % len(Xte)]) for i in range(30)]
        shed = [f for f in futs if f.done()
                and isinstance(f.exception(), Rejected)]
        assert shed                              # queue cap enforced
        gate.set()
        srv.drain(timeout=30)
        assert all(f.done() for f in futs)       # every future resolved
        assert srv.metrics()["reliability"]["shed"] == len(shed)


def test_request_deadline_expires_in_queue(iris_model):
    m, Xte, _ = iris_model
    cfg = ServeConfig(max_batch=4, min_bucket=4, max_delay_s=0.001,
                      request_timeout_s=0.02)
    gate = threading.Event()
    with TCAMServer(m.compiled, config=cfg) as srv:
        srv.fault_injection_hook = lambda _X: gate.wait(30)
        futs = srv.submit_many(Xte[:12])         # batch 1 stalls; rest queue
        time.sleep(0.1)                          # queued requests expire
        gate.set()
        srv.drain(timeout=30)
        expired = [f for f in futs
                   if isinstance(f.exception(), DeadlineExceeded)]
        assert expired
        assert all(f.done() for f in futs)
        assert (srv.metrics()["reliability"]["deadline_exceeded"]
                == len(expired))


def test_deadline_fires_without_flush_trigger(iris_model):
    # a lone queued request whose timeout is far shorter than max_delay_s
    # must be failed at expiry — the worker wakes on the batcher's expiry
    # deadline, not the (10 s away) flush deadline
    m, Xte, _ = iris_model
    cfg = ServeConfig(max_batch=64, max_delay_s=10.0,
                      request_timeout_s=0.05)
    with TCAMServer(m.compiled, config=cfg) as srv:
        fut = srv.submit(Xte[0])
        t0 = time.time()
        with pytest.raises(DeadlineExceeded):
            fut.result(timeout=5)
        assert time.time() - t0 < 2.0            # nowhere near max_delay_s
        assert srv.metrics()["reliability"]["deadline_exceeded"] == 1


def test_retry_budget_absorbs_transient_faults(iris_model):
    m, Xte, _ = iris_model
    cfg = ServeConfig(background=False, max_batch=8,
                      max_retries=3, retry_backoff_s=0.001)
    srv = TCAMServer(m.compiled, config=cfg)
    fails = [2]

    def flaky(_X):
        if fails[0] > 0:
            fails[0] -= 1
            raise RuntimeError("transient")

    srv.fault_injection_hook = flaky
    res = srv.serve(Xte[:8])
    assert len(res) == 8                         # recovered within budget
    rel = srv.metrics()["reliability"]
    assert rel["retries"] == 2 and rel["compute_failures"] == 0
    srv.close()


# --------------------------------------------------------------------------
# batch records, spans and copy counters
# --------------------------------------------------------------------------
@pytest.mark.parametrize("capacity,chunks", [
    (16, [5, 7]),                 # fits the ring
    (16, [10, 9, 3]),             # wraps inside a call
    (16, [3, 40, 2]),             # one call longer than the ring
])
def test_latency_stats_record_many_equals_record(capacity, chunks):
    rng = np.random.default_rng(capacity + sum(chunks))
    many, one = LatencyStats(capacity), LatencyStats(capacity)
    for k in chunks:
        x = rng.random(k)
        many.record_many(x)
        for v in x:
            one.record(float(v))
    assert many.count == one.count == sum(chunks)
    np.testing.assert_array_equal(many._buf, one._buf)


@pytest.fixture(scope="module")
def iris_forest():
    from repro.forest import compile_forest, train_forest
    Xtr, ytr, Xte, _ = load_split("iris")
    trees = train_forest(Xtr, ytr, n_trees=3, max_depth=4, seed=0)
    return compile_forest(trees, s=32), Xte


def _served(model, X, sizes):
    """Serve rows of ``X`` in one synchronous batch per entry of
    ``sizes``."""
    srv = TCAMServer(model, config=ServeConfig(
        max_batch=16, min_bucket=4, background=False))
    results = []
    for k in sizes:
        futs = srv.submit_many(X[np.arange(k) % len(X)])
        srv.pump(force=True)
        results += [f.result() for f in futs]
    return srv, results


def _copy_bytes(srv, bucket):
    """(h2d, d2h) bytes of one batch of ``bucket`` rows, from the shapes."""
    if srv._forest is None:
        return bucket * srv._layout.n_cwd * srv._layout.s, 4 * 4 * bucket
    groups = srv._f_plan.groups
    return (sum(g.n_banks * bucket * g.width for g in groups),
            sum(3 * 4 * g.n_banks * bucket for g in groups))


@pytest.mark.parametrize("mode", ["tree", "forest"])
def test_batch_records_spans_and_bytes(mode, iris_model, iris_forest):
    model, X = ((iris_model[0].compiled, iris_model[1]) if mode == "tree"
                else iris_forest)
    sizes = [16, 3, 9]
    srv, results = _served(model, X, sizes)
    rec = srv.metrics_store.batch_records()
    assert rec.size == len(sizes)
    np.testing.assert_array_equal(rec["n"], sizes)
    np.testing.assert_array_equal(rec["bucket"], [16, 4, 16])
    assert len(set(rec["batch"])) == len(sizes)
    ids = dict(zip(rec["batch"], rec["n"]))
    assert {r.batch for r in results} == set(ids)
    for b, n in ids.items():
        assert sum(r.batch == b for r in results) == n
    want = np.array([_copy_bytes(srv, b) for b in rec["bucket"]])
    np.testing.assert_array_equal(rec["h2d_bytes"], want[:, 0])
    np.testing.assert_array_equal(rec["d2h_bytes"], want[:, 1])
    phases = np.stack([rec[f"{p}_s"] for p in PHASES[1:]], 1)
    assert (phases >= 0).all() and (rec["batch_s"] > 0).all()
    assert (phases.sum(1) <= rec["batch_s"]).all()
    assert np.all(np.diff(rec["t_form"]) > 0)

    snap = srv.metrics()
    assert snap["h2d_bytes"] == want[:, 0].sum()
    assert snap["d2h_bytes"] == want[:, 1].sum()
    assert snap["d2h_bytes_per_decision"] == pytest.approx(
        want[:, 1].sum() / sum(sizes))
    assert set(snap["phases"]) == set(PHASES)
    for p, v in snap["phases"].items():
        assert 0 <= v["p50_ms"] <= v["p99_ms"], p


def test_batch_records_ring_keeps_the_newest():
    from repro.serve import ServeMetrics
    m = ServeMetrics()
    assert m.batch_records().size == 0
    assert np.isnan(m.snapshot()["phases"]["encode"]["p50_ms"])
    n = BATCH_CAPACITY + 2
    for b in range(n):
        m.on_batch_record(b, float(b), 1, 8, [0.1] * len(PHASES), 10, 20)
    rec = m.batch_records()
    np.testing.assert_array_equal(rec["batch"], np.arange(2, n))
    assert m.h2d_bytes == 10 * n and m.d2h_bytes == 20 * n
    rec["n"] = 0                       # a copy: the ring is untouched
    assert (m.batch_records()["n"] == 1).all()


def test_serve_batch_spans_in_profiler_trace(iris_model, tmp_path):
    import glob

    import jax
    m, Xte, _ = iris_model
    srv = TCAMServer(m.compiled, config=ServeConfig(
        max_batch=16, min_bucket=4, background=False))
    srv.warmup()
    jax.profiler.start_trace(str(tmp_path))
    try:
        srv.serve(Xte[np.arange(40) % len(Xte)])
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    ids = {}
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    batch, = (v for k, v in ev.stats if k == "batch")
                    ids.setdefault(ev.name, []).append(batch)
    rec = srv.metrics_store.batch_records()
    assert rec.size == 3                         # 16 + 16 + 8
    assert sorted(ids["serve.batch"]) == sorted(rec["batch"])
    for p in PHASES[1:]:
        assert sorted(ids[f"serve.{p}"]) == sorted(rec["batch"]), p


def test_background_worker_records_every_batch(iris_model):
    m, Xte, _ = iris_model
    cfg = ServeConfig(max_batch=16, min_bucket=4, max_delay_s=0.001)
    with TCAMServer(m.compiled, config=cfg) as srv:
        res = srv.serve(Xte[np.arange(50) % len(Xte)])
    rec = srv.metrics_store.batch_records()      # the worker has stopped
    assert rec["n"].sum() == len(res) == 50
    assert {r.batch for r in res} == set(rec["batch"])
