"""Observability tests: registry thread-safety, histogram percentile
correctness against the np.percentile oracle, the stage primitive (nesting,
histograms, trace spans, cost), per-query trace completeness on every
execution path, the engine's stage coverage, and exporter round-trips."""
import threading
import time

import jax
import numpy as np
import pytest

from repro.core.rfann import RNSGIndex
from repro.data.ann import make_attrs, make_vectors, mixed_workload
from repro.obs import (DISPATCHER_STAGES, Counter, Gauge, Histogram,
                       MetricsRegistry, QueryTrace, format_stats_line,
                       parse_prometheus, set_batch, stage, to_prometheus)
from repro.search import SearchCache
from repro.serving.distributed import DistributedRFANN
from repro.serving.engine import RFANNEngine

REQUIRED_SPANS = {"resolve", "plan", "assemble"}


def _has_dispatch(names) -> bool:
    return any(n.endswith("_dispatch") for n in names)


# ------------------------------------------------------------- metrics core
def test_counter_thread_safety():
    """8 threads x 5000 increments must land exactly — the per-metric lock
    never loses an update."""
    c = Counter("hammer")
    n_threads, per = 8, 5000

    def work():
        for _ in range(per):
            c.inc()

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n_threads * per


@pytest.mark.parametrize("path", ["observe_many", "observe"])
def test_histogram_concurrent_observe(path):
    """More threads than cores and a short switch interval: no update of
    the batched or the scalar path is lost."""
    import os
    import sys
    h = Histogram("lat")
    n_threads, per = max(6, 2 * (os.cpu_count() or 1)), 400

    def work(seed):
        rng = np.random.default_rng(seed)
        for _ in range(per // 8):
            vals = rng.uniform(0.1, 100.0, 8)
            if path == "observe_many":
                h.observe_many(vals)
            else:
                for v in vals:
                    h.observe(v)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=work, args=(i,))
              for i in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in ts)
    assert h.count == n_threads * per
    edges, cum = h.bucket_counts()
    assert int(cum[-1]) == h.count              # cumulative folds everything
    want = sum(np.random.default_rng(i).uniform(0.1, 100.0, per).sum()
               for i in range(n_threads))
    assert h.sum == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("dist_name", ["lognormal", "uniform", "bimodal"])
def test_histogram_percentiles_vs_oracle(dist_name):
    """p50/p90/p99 within one bucket's relative width (growth - 1) of the
    exact np.percentile answer."""
    rng = np.random.default_rng(3)
    vals = {
        "lognormal": np.exp(rng.normal(1.0, 1.2, 20_000)),
        "uniform": rng.uniform(0.5, 300.0, 20_000),
        "bimodal": np.concatenate([rng.uniform(0.2, 2.0, 10_000),
                                   rng.uniform(50.0, 500.0, 10_000)]),
    }[dist_name]
    growth = 1.25
    h = Histogram("lat", growth=growth)
    h.observe_many(vals)
    for p in (50, 90, 99):
        # the histogram implements the rank (inverted-CDF) quantile; the
        # default linear interpolation diverges arbitrarily at density gaps
        exact = float(np.percentile(vals, p, method="inverted_cdf"))
        got = h.percentile(p)
        rel = abs(got - exact) / exact
        assert rel <= (growth - 1) + 0.02, (p, got, exact, rel)
    snap = h.snapshot()
    assert snap["count"] == len(vals)
    assert np.isclose(snap["mean"], vals.mean())        # sum is exact
    assert snap["min"] == pytest.approx(vals.min())
    assert snap["max"] == pytest.approx(vals.max())


def test_histogram_edge_cases():
    h = Histogram("lat")
    assert h.percentile(50) == 0.0                      # empty -> 0
    assert h.snapshot()["count"] == 0
    h.observe(7.5)
    # single value: every percentile clamps to the one observation
    assert h.percentile(1) == pytest.approx(7.5)
    assert h.percentile(50) == pytest.approx(7.5)
    assert h.percentile(99) == pytest.approx(7.5)
    h2 = Histogram("tiny")
    h2.observe(1e-9)                                    # below first edge
    assert h2.percentile(50) == pytest.approx(1e-9)     # clamped to min
    h2.observe(1e9)                                     # overflow bucket
    assert h2.percentile(99) == pytest.approx(1e9)      # clamped to max


def test_registry_get_or_create_and_type_conflict():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    reg.gauge("g").set(4.5)
    reg.histogram("h").observe(2.0)
    reg.register_producer("section", lambda: dict(a=1, nested=dict(b=2.5),
                                                  skipped="str"))
    snap = reg.snapshot()
    assert snap["counters"]["x"] == 0
    assert snap["gauges"]["g"] == 4.5
    assert snap["histograms"]["h"]["count"] == 1
    assert snap["section"] == {"a": 1.0, "nested_b": 2.5}


def test_registry_dead_producer_never_kills_export():
    reg = MetricsRegistry()
    reg.register_producer("bad", lambda: 1 / 0)
    reg.register_producer("good", lambda: dict(v=1.0))
    snap = reg.snapshot()
    assert "bad" not in snap and snap["good"] == {"v": 1.0}


# --------------------------------------------------------------- exporters
def test_prometheus_roundtrip_and_bucket_invariants():
    reg = MetricsRegistry()
    reg.counter("reqs").inc(42)
    reg.gauge("depth").set(3)
    h = reg.histogram("lat_ms")
    h.observe_many(np.random.default_rng(0).uniform(0.5, 50.0, 1000))
    reg.register_producer("cache", lambda: dict(bytes=1024))
    text = to_prometheus(reg)
    samples = parse_prometheus(text)
    assert samples[("rnsg_reqs", "")] == 42
    assert samples[("rnsg_depth", "")] == 3
    assert samples[("rnsg_cache_bytes", "")] == 1024
    assert samples[("rnsg_lat_ms_count", "")] == 1000
    assert samples[("rnsg_lat_ms_sum", "")] == pytest.approx(h.sum)
    # cumulative buckets: nondecreasing in le, +Inf bucket == count
    buckets = [(float(lbl.split('"')[1].replace("+Inf", "inf")), v)
               for (name, lbl), v in samples.items()
               if name == "rnsg_lat_ms_bucket"]
    buckets.sort()
    vals = [v for _, v in buckets]
    assert vals == sorted(vals)
    assert buckets[-1][0] == float("inf") and buckets[-1][1] == 1000


def test_parse_prometheus_rejects_malformed():
    with pytest.raises(ValueError):
        parse_prometheus("this is { not a metric\n")


def test_format_stats_line_shape():
    reg = MetricsRegistry()
    reg.histogram("engine_e2e_ms").observe_many([1.0, 2.0, 3.0])
    reg.register_producer("engine", lambda: dict(
        served=10, batches=2, mean_batch=5.0, scan_frac=0.5,
        cache_hit_frac=0.1))
    line = format_stats_line(reg.snapshot())
    assert line.startswith("[obs] served=10 batches=2")
    assert "p50=" in line and "p99=" in line


def test_histogram_scalar_observe_matches_observe_many():
    """The numpy-free scalar path lands every value in the bucket
    ``observe_many`` picks — bucket edges themselves included — with the
    same count, sum, min and max."""
    rng = np.random.default_rng(11)
    one, many = Histogram("one"), Histogram("many")
    vals = np.concatenate([np.exp(rng.uniform(np.log(1e-4), np.log(1e5),
                                              10_000)),
                           one.edges, np.nextafter(one.edges, 0),
                           np.nextafter(one.edges, np.inf), [0.0, 1e9]])
    for v in vals:
        one.observe(v)
    many.observe_many(vals)
    assert one._counts == many._counts
    assert one.count == many.count == len(vals)
    assert one.sum == pytest.approx(many.sum, rel=1e-12)
    assert one.snapshot()["min"] == many.snapshot()["min"]
    assert one.snapshot()["max"] == many.snapshot()["max"]
    np.testing.assert_array_equal(one.bucket_counts()[1],
                                  many.bucket_counts()[1])


# ------------------------------------------------------------------- stages
def test_stage_nesting_and_self_time():
    """A nested stage's wall time is taken out of the outer one's self
    time; walls land in ``stage_<name>_ms`` and the trace keeps both."""
    reg, tr = MetricsRegistry(), QueryTrace()
    with stage("outer", reg, tr) as out:
        time.sleep(0.004)
        with stage("inner", reg, tr) as inn:
            time.sleep(0.01)
    assert inn.ms >= 10.0 and inn.self_ms == pytest.approx(inn.ms)
    assert out.ms >= inn.ms + 4.0
    assert out.self_ms == pytest.approx(out.ms - inn.ms, abs=1e-6)
    snap = reg.snapshot()["histograms"]
    assert snap["stage_outer_ms"]["sum"] == pytest.approx(out.ms)
    assert snap["stage_inner_ms"]["sum"] == pytest.approx(inn.ms)
    assert tr.names() == ["inner", "outer"]          # appended on exit
    assert tr.get("outer").self_ms == pytest.approx(out.self_ms, rel=1e-6)
    assert tr.get("inner").self_ms == pytest.approx(inn.ms, rel=1e-6)


def test_stage_mirrors_every_exit_into_its_histogram():
    """Each exit adds one observation — exceptions included — and the
    histogram's sum is the stages' summed wall time."""
    reg = MetricsRegistry()
    walls = []
    for i in range(5):
        with stage("step", reg) as st:
            time.sleep(0.001 * i)
        walls.append(st.ms)
    with pytest.raises(ValueError):
        with stage("step", reg):
            raise ValueError("propagates")
    h = reg.snapshot()["histograms"]["stage_step_ms"]
    assert h["count"] == 6
    assert h["sum"] >= sum(walls) and h["sum"] == pytest.approx(
        sum(walls), abs=1.0)


def test_stage_copies_span_into_query_trace():
    """With a trace the stage appends one span carrying its meta and the
    attributes set while it ran; the batch tag reaches the profiler meta,
    not the span; without a trace attribute writes are dropped."""
    tr = QueryTrace()
    set_batch(42)
    try:
        with stage("plan", None, tr, ns=3) as st:
            st.attrs["pad_rows"] = 7
            st.attrs.update(strategy_mode="auto")
    finally:
        set_batch(None)
    sp = tr.get("plan")
    assert sp.attrs == {"ns": 3, "pad_rows": 7, "strategy_mode": "auto"}
    assert sp.wall_ms == pytest.approx(st.ms, rel=1e-6)
    assert tr.to_dict()["spans"][0]["name"] == "plan"
    with stage("plan", None, None) as st2:
        st2.attrs["k"] = 1                  # dropped, never raises
        st2.attrs.update(x=2)
    assert len(tr.spans) == 1 and st2.ms >= 0.0


def test_stage_without_registry_records_nothing_and_is_cheap():
    """The no-registry, no-trace path only opens the profiler annotation
    and times itself: nothing is created anywhere, and it stays in the
    microseconds (a generous bound, for loaded test machines)."""
    reg = MetricsRegistry()
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with stage("noop", None):
            pass
    per_us = (time.perf_counter() - t0) / n * 1e6
    assert reg.snapshot()["histograms"] == {}
    assert per_us < 25.0, per_us


# ------------------------------------------------------------------- traces


# small shared corpora for the path-coverage matrix -------------------------
N, D, Q = 256, 16, 8


@pytest.fixture(scope="module")
def corpus():
    vecs = make_vectors(N, D, seed=0)
    attrs = make_attrs(N, seed=0)
    qv = make_vectors(Q, D, seed=7)
    ranges, _ = mixed_workload(attrs, Q, seed=3)
    return vecs, attrs, qv, ranges


@pytest.fixture(scope="module")
def local_index(corpus):
    vecs, attrs, _, _ = corpus
    return RNSGIndex.build(vecs, attrs, m=8, ef_spatial=16, ef_attribute=24)


@pytest.fixture(scope="module")
def dist_local(corpus):
    vecs, attrs, _, _ = corpus
    return DistributedRFANN(vecs, attrs, n_shards=2, m=8, ef_spatial=16,
                            ef_attribute=24)


@pytest.fixture(scope="module")
def dist_mesh(corpus):
    vecs, attrs, _, _ = corpus
    mesh = jax.make_mesh((1,), ("data",))
    return DistributedRFANN(vecs, attrs, n_shards=1, mesh=mesh, m=8,
                            ef_spatial=16, ef_attribute=24)


def _index(path, local_index, dist_local, dist_mesh):
    return dict(local=local_index, dist=dist_local, mesh=dist_mesh)[path]


@pytest.mark.parametrize("path", ["local", "dist", "mesh"])
@pytest.mark.parametrize("plan", ["graph", "auto", "scan", "beam"])
def test_trace_completeness(path, plan, corpus, local_index, dist_local,
                            dist_mesh):
    """Every strategy x every execution path yields a complete span set
    with the routing decision and cache outcome recorded — and tracing
    never changes the returned ids."""
    _, _, qv, ranges = corpus
    idx = _index(path, local_index, dist_local, dist_mesh)
    tr = QueryTrace(request_id=f"{path}-{plan}")
    traced = idx.search(qv, ranges, k=5, ef=32, plan=plan, trace=tr)
    plain = idx.search(qv, ranges, k=5, ef=32, plan=plan)
    t_ids = traced[0] if isinstance(traced, tuple) else traced.ids
    p_ids = plain[0] if isinstance(plain, tuple) else plain.ids
    np.testing.assert_array_equal(np.asarray(t_ids), np.asarray(p_ids))

    names = set(tr.names())
    assert REQUIRED_SPANS <= names, (path, plan, tr.names())
    assert _has_dispatch(names), (path, plan, tr.names())
    plan_sp = tr.get("plan")
    assert plan_sp.attrs["strategy_mode"] == plan
    if plan == "graph":
        assert plan_sp.attrs.get("chosen") == "graph"
    else:
        assert "strategy" in plan_sp.attrs       # per-query routing vector
        assert "scan_frac" in plan_sp.attrs
    assert "cache_enabled" in plan_sp.attrs      # cache outcome always there
    assert plan_sp.attrs["cache_enabled"] is False
    for sp in tr.spans:
        assert sp.wall_ms >= 0.0
    # every span survives JSON conversion
    d = tr.to_dict()
    assert {s["name"] for s in d["spans"]} >= REQUIRED_SPANS


@pytest.mark.parametrize("path", ["local", "dist", "mesh"])
def test_trace_cache_outcome(path, corpus, local_index, dist_local,
                             dist_mesh):
    """Second identical batch is served from the cache: the plan span
    records dispatched=0 and cache_hits=Q, no dispatch stage runs, and
    resolve/assemble are still present."""
    _, _, qv, ranges = corpus
    idx = _index(path, local_index, dist_local, dist_mesh)
    cache = SearchCache(max_bytes=4 << 20)
    idx.install_cache(cache)
    try:
        idx.search(qv, ranges, k=5, ef=32, plan="auto")         # populate
        tr = QueryTrace()
        idx.search(qv, ranges, k=5, ef=32, plan="auto", trace=tr)
        plans = tr.all("plan")
        assert plans, tr.names()
        for sp in plans:
            assert sp.attrs["cache_enabled"] is True
            assert sp.attrs["dispatched"] == 0
            assert sp.attrs["cache_hits"] == Q
        assert {"resolve", "plan", "assemble"} <= set(tr.names())
        assert not _has_dispatch(tr.names()), tr.names()
    finally:
        idx.install_cache(None)


# ------------------------------------------------------------------- engine
def test_engine_concurrent_submit_exact_totals(local_index):
    """N client threads x M submits: every future resolves, and both the
    EngineStats and the registry counters account for exactly N*M."""
    eng = RFANNEngine(local_index, k=5, ef=32, plan="auto", max_batch=32,
                      max_wait_ms=1.0)
    try:
        n_threads, per = 4, 24
        rng = np.random.default_rng(0)
        qs = rng.standard_normal((n_threads, per, D)).astype(np.float32)
        errs = []

        def client(t):
            try:
                futs = [eng.submit(qs[t, i], (-0.5, 0.5))
                        for i in range(per)]
                for f in futs:
                    r = f.result(timeout=60)
                    assert r.ids.shape == (5,)
            except Exception as e:          # pragma: no cover - diagnostics
                errs.append(e)

        ts = [threading.Thread(target=client, args=(t,))
              for t in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs
        assert eng.stats.served == n_threads * per
        snap = eng.metrics()
        assert snap["counters"]["engine_requests_total"] == n_threads * per
        assert snap["counters"]["queries_total"] == n_threads * per
        assert snap["engine"]["served"] == n_threads * per
    finally:
        eng.close()


def test_engine_metrics_percentiles_dedup_and_trace(local_index):
    """End-to-end engine observability: non-trivial p50/p99, batch dedup
    surfaced in stats, sampled trace parked on last_trace, prometheus dump
    round-trips with the core families."""
    eng = RFANNEngine(local_index, k=5, ef=32, plan="auto", max_batch=64,
                      max_wait_ms=40.0, cache_bytes=1 << 20,
                      trace_sample_every=1)
    try:
        q = make_vectors(1, D, seed=9)[0]
        # one burst of identical requests coalesces into one batch: row 0
        # misses, rows 1.. are intra-batch duplicates
        futs = [eng.submit(q, (-0.5, 0.5)) for _ in range(16)]
        for f in futs:
            f.result(timeout=60)
        assert eng.stats.dedup_hits > 0
        summ = eng.stats.summary()
        assert summ["dedup_hits"] == eng.stats.dedup_hits
        assert summ["served"] == 16

        snap = eng.metrics()
        lat = snap["histograms"]["engine_e2e_ms"]
        assert lat["count"] == 16
        assert 0 < lat["p50"] <= lat["p99"]
        # the operator summary's percentiles come from the same histogram
        summ = eng.summary()
        assert summ["p50_ms"] == pytest.approx(lat["p50"])
        assert summ["p99_ms"] == pytest.approx(lat["p99"])
        assert summ["served"] == 16
        assert snap["histograms"]["engine_batch_size"]["count"] >= 1
        assert eng.last_trace is not None
        names = eng.last_trace.names()
        assert {"resolve", "plan", "assemble"} <= set(names), names

        text = to_prometheus(eng.registry)
        samples = parse_prometheus(text)
        names = {n for (n, _) in samples}
        assert "rnsg_engine_requests_total" in names
        assert "rnsg_engine_e2e_ms_count" in names
        assert "rnsg_queries_total" in names
        assert samples[("rnsg_engine_requests_total", "")] == 16
    finally:
        eng.close()


@pytest.fixture(scope="module")
def engine_index():
    """A corpus whose beam batches take milliseconds on the CPU, as a
    served batch does on the chip, so fixed per-stage costs weigh as they
    would there."""
    vecs = make_vectors(1024, D, seed=0)
    attrs = make_attrs(1024, seed=0)
    return RNSGIndex.build(vecs, attrs, m=8, ef_spatial=16, ef_attribute=24)


def _serve_lockstep(index, n_batches: int, per: int, reg, seed: int = 0):
    """Serve ``n_batches`` batches of exactly ``per`` requests, one after
    the other; returns the engine (closed) and the wall time from the
    dispatcher's start to its join."""
    eng = RFANNEngine(index, k=5, ef=64, plan="auto", max_batch=per,
                      max_wait_ms=200.0, metrics=reg)
    t0 = time.perf_counter()
    try:
        rng = np.random.default_rng(seed)
        for _ in range(n_batches):
            qs = rng.standard_normal((per, D)).astype(np.float32)
            futs = [eng.submit(q, (-0.5, 0.5)) for q in qs]
            for f in futs:
                f.result(timeout=60)
    finally:
        eng.close()
    return eng, time.perf_counter() - t0


def _window_sums(reg, names):
    h = reg.snapshot()["histograms"]
    return {n: h.get(f"stage_{n}_ms", {}).get("sum", 0.0) for n in names}


def test_engine_stages_tile_the_dispatcher_loop(engine_index):
    """Every instant of the dispatcher thread lies in one leaf stage: over
    50 batches the leaf stages' summed wall covers at least 98 % of the
    loop's wall time (and never more than all of it)."""
    reg = MetricsRegistry()
    _serve_lockstep(engine_index, 10, 16, reg)        # warm every shape
    reg = MetricsRegistry()
    eng, wall_s = _serve_lockstep(engine_index, 50, 16, reg, seed=1)
    assert eng.stats.batches == 50
    covered = sum(_window_sums(reg, DISPATCHER_STAGES).values())
    share = covered / (wall_s * 1e3)
    assert 0.98 <= share <= 1.0 + 1e-3, (share, _window_sums(
        reg, DISPATCHER_STAGES))


def test_engine_wait_and_serve_stages_add_up_to_e2e(engine_index):
    """Per request, queue wait + resolve + hand-off wait + the dispatcher's
    serve stages come within 5 % of the end-to-end mean (batches of equal
    size, so per-batch and per-request means weigh alike)."""
    reg = MetricsRegistry()
    _serve_lockstep(engine_index, 10, 8, reg)         # warm every shape
    reg = MetricsRegistry()
    _serve_lockstep(engine_index, 40, 8, reg, seed=2)
    h = reg.snapshot()["histograms"]
    batches = h["engine_batch_size"]["count"]
    assert h["engine_batch_size"]["sum"] == 8 * batches
    serve = sum(v for n, v in _window_sums(reg, DISPATCHER_STAGES).items()
                if n != "await_batch")
    parts = (h["engine_queue_wait_ms"]["mean"]
             + h["engine_resolve_ms"]["mean"]
             + h["engine_handoff_wait_ms"]["mean"]
             + serve / batches)
    assert h["engine_queue_wait_ms"]["count"] == 8 * batches
    assert h["engine_handoff_wait_ms"]["count"] == batches
    e2e = h["engine_e2e_ms"]["mean"]
    assert parts == pytest.approx(e2e, rel=0.05), (parts, e2e)


def test_engine_trace_survives_untraced_index(corpus):
    """An index predating the trace API (tuple-returning baseline) keeps
    working when trace sampling is on — the engine drops the kwarg."""
    vecs, attrs, qv, _ = corpus

    class Legacy:
        def search(self, q, rg, *, k=10, ef=64, plan="auto"):
            q2 = np.atleast_2d(q)
            return (np.zeros((len(q2), k), np.int32),
                    np.zeros((len(q2), k), np.float32))

    eng = RFANNEngine(Legacy(), k=5, ef=32, plan="auto",
                      trace_sample_every=1, max_wait_ms=1.0)
    try:
        r = eng.submit(qv[0], (-0.5, 0.5)).result(timeout=30)
        assert r.ids.shape == (5,)
        assert eng.stats.served == 1
    finally:
        eng.close()
