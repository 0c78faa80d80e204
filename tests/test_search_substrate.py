"""Unified search substrate: single-source resolve, strategy parity across
every execution path (including the shard_map mesh-auto path), empty-partition
guards, beam early-out, the visited-table counters."""
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.beam import beam_search_batch
from repro.core.rfann import RNSGIndex
from repro.data.ann import make_attrs, make_vectors, selectivity_ranges
from repro.planner import QueryPlanner
from repro.planner.planner import Partition
from repro.search import SearchRequest, SearchResult, select_entry
from repro.serving.distributed import DistributedRFANN

SRC = Path(__file__).resolve().parent.parent / "src" / "repro"


# ------------------------------------------------------- single-source resolve
def test_resolve_is_single_source():
    """Acceptance: exactly one implementation of rank-interval mapping and
    RMQ entry selection under src/repro — searchsorted / rmq_query_jax are
    *called* only from the substrate's resolve module.  The batched beam's
    bounded frontier merge uses ``searchsorted`` as a sorted-list merge
    primitive (no rank semantics); those lines carry an explicit
    ``sorted-merge`` marker and are the only exemption."""
    call = re.compile(r"\b(?:np|jnp)\.searchsorted\s*\(|rmq_query_jax\s*\(")
    offenders = []
    for py in SRC.rglob("*.py"):
        rel = py.relative_to(SRC).as_posix()
        if rel == "search/resolve.py":          # the one allowed home
            continue
        for ln, line in enumerate(py.read_text().splitlines(), 1):
            if line.lstrip().startswith("#"):
                continue
            if rel == "core/entry.py" and line.lstrip().startswith(
                    "def rmq_query_jax"):       # the definition itself
                continue
            if rel == "core/beam.py" and "sorted-merge" in line:
                continue                        # merge primitive, not resolve
            if call.search(line):
                offenders.append(f"{rel}:{ln}: {line.strip()}")
    assert not offenders, "\n".join(offenders)


# ------------------------------------------------------------- strategy parity
def _corpus(n=256, d=16, seed=0):
    vecs = make_vectors(n, d, seed=seed)
    attrs = make_attrs(n, seed=seed)
    return vecs, attrs


def _degenerate_ranges(attrs, nq, seed):
    """Random selectivities plus the degenerate rows the paper's API must
    handle: empty, single-point, full-span."""
    s = np.sort(attrs)
    rngs = [selectivity_ranges(attrs, nq - 3, 0.2, seed=seed)]
    rngs.append(np.asarray([
        [s[5] + 1e-7, s[5] + 2e-7],     # empty
        [s[17], s[17]],                 # single point
        [s[0], s[-1]],                  # full span
    ], np.float32))
    return np.concatenate(rngs)


def test_strategy_parity_all_paths():
    """With ef >= n every strategy is exact, so plan=graph/auto/scan/beam and
    the sharded DistributedRFANN (graph and per-shard-planned, async and
    sequential) must return identical id sets — including degenerate ranges.
    Cached re-runs of every single-index strategy must additionally be
    **bit-identical** (ids and dists) to the uncached run that populated
    the cache."""
    from repro.search import SearchCache

    n, d, nq, k = 256, 16, 15, 8
    vecs, attrs = _corpus(n, d)
    idx = RNSGIndex.build(vecs, attrs, m=16, ef_spatial=16, ef_attribute=24)
    dist = DistributedRFANN(vecs, attrs, n_shards=4, m=16, ef_spatial=16,
                            ef_attribute=24)
    qv = make_vectors(nq, d, seed=7)
    ranges = _degenerate_ranges(attrs, nq, seed=11)

    runs = {}
    for plan in ("graph", "auto", "scan", "beam"):
        uncached = idx.search(qv, ranges, k=k, ef=n, plan=plan)
        runs[plan] = uncached.ids
        # cached parity: the populating (miss) pass and the all-hit pass
        # must both be bit-identical to the uncached run
        idx.install_cache(SearchCache(1 << 20))
        fill = idx.search(qv, ranges, k=k, ef=n, plan=plan)
        hit = idx.search(qv, ranges, k=k, ef=n, plan=plan)
        idx.install_cache(None)
        assert hit.stats["cache_hits"] == nq
        for res in (fill, hit):
            assert np.array_equal(res.ids, uncached.ids), plan
            assert np.array_equal(res.dists, uncached.dists), plan
    # batched expansion: every strategy at beam_width=4 doubles as a
    # correctness oracle for the bounded-merge + hashed-visited frontier
    for plan in ("graph", "auto", "beam"):
        runs[f"{plan}_bw4"] = idx.search(qv, ranges, k=k, ef=n, plan=plan,
                                         beam_width=4).ids
    runs["dist_graph"] = dist.search(qv, ranges, k=k, ef=n, plan="graph")[0]
    runs["dist_auto"] = dist.search(qv, ranges, k=k, ef=n, plan="auto")[0]
    runs["dist_graph_bw4"] = dist.search(qv, ranges, k=k, ef=n, plan="graph",
                                         beam_width=4)[0]
    dist.async_dispatch = False
    runs["dist_auto_seq"] = dist.search(qv, ranges, k=k, ef=n,
                                        plan="auto")[0]

    base = runs.pop("graph")
    for q in range(nq):
        want = set(base[q][base[q] >= 0].tolist())
        for name, ids in runs.items():
            got = set(ids[q][ids[q] >= 0].tolist())
            assert got == want, (name, q, sorted(got), sorted(want))
    # degenerate rows behave as specified
    assert (base[nq - 3] == -1).all()                       # empty
    assert base[nq - 2][0] >= 0 and (base[nq - 2][1:] == -1).all()  # single
    assert (base[nq - 1] >= 0).all()                        # full span


def test_search_result_is_tuple_compatible():
    vecs, attrs = _corpus(128, 8)
    idx = RNSGIndex.build(vecs, attrs, m=8, ef_spatial=8, ef_attribute=12)
    qv = make_vectors(4, 8, seed=1)
    rg = selectivity_ranges(attrs, 4, 0.3, seed=2)
    res = idx.search(qv, rg, k=3, ef=16)
    assert isinstance(res, SearchResult)
    ids, dists, stats = res                     # legacy unpacking
    assert np.array_equal(ids, res[0]) and np.array_equal(dists, res[1])
    assert stats is res.stats and len(res) == 3
    row = res.row(2)
    assert row.ids.shape == (3,) and row.stats["hops"].shape == ()


# ------------------------------------------------------- mesh strategy parity
def test_mesh_auto_parity_single_device():
    """The mesh-auto machinery (host plan -> replicated strategy vector ->
    branchless per-shard select -> restitch -> merge) on a 1-device mesh:
    every mesh plan must match the mesh graph path's id sets, with both
    strategies exercised in one shard_map call."""
    import jax

    from repro.planner.planner import BEAM, SCAN
    from repro.search import rank_interval

    n, d, nq, k = 256, 16, 15, 8
    vecs, attrs = _corpus(n, d)
    mesh = jax.make_mesh((1,), ("data",))
    dist = DistributedRFANN(vecs, attrs, n_shards=1, mesh=mesh, m=16,
                            ef_spatial=16, ef_attribute=24)
    qv = make_vectors(nq, d, seed=7)
    ranges = _degenerate_ranges(attrs, nq, seed=11)

    lo, hi = rank_interval(dist.attrs_sorted, ranges)
    strat, _ = dist.mesh_substrate.plan_strategies(lo, hi, k=k, mode="auto")
    assert (strat == SCAN).any() and (strat == BEAM).any()   # mixed batch

    base, _ = dist.search(qv, ranges, k=k, ef=n, plan="graph")
    for plan, bw in (("auto", 1), ("scan", 1), ("beam", 1),
                     ("graph", 4), ("auto", 4)):
        ids, dists = dist.search(qv, ranges, k=k, ef=n, plan=plan,
                                 beam_width=bw)
        for q in range(nq):
            want = set(base[q][base[q] >= 0].tolist())
            got = set(ids[q][ids[q] >= 0].tolist())
            assert got == want, (plan, bw, q, sorted(got), sorted(want))
    # degenerate rows behave as specified on the mesh too
    assert (base[nq - 3] == -1).all()                        # empty
    assert base[nq - 2][0] >= 0 and (base[nq - 2][1:] == -1).all()
    assert (base[nq - 1] >= 0).all()                         # full span
    # zero-query mesh request: no dispatch, well-shaped empty result
    e_ids, e_d = dist.search(qv[:0], ranges[:0], k=k, ef=n, plan="auto")
    assert e_ids.shape == (0, k) and e_d.shape == (0, k)


@pytest.mark.slow
def test_mesh_auto_parity_multidevice():
    """Acceptance (subprocess: XLA_FLAGS must precede jax import): on an
    8-device mesh, plan='auto' routes a mixed narrow/wide batch to BOTH
    strategies inside one shard_map call and returns id sets identical to
    the graph-only mesh path — including intervals empty on most shards
    (clipped to a single shard) and globally empty intervals."""
    root = Path(__file__).resolve().parent.parent
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=str(root / "src"))
    code = textwrap.dedent("""
        import numpy as np, jax
        from repro.data.ann import make_vectors, make_attrs, selectivity_ranges
        from repro.planner.planner import BEAM, SCAN
        from repro.search import rank_interval
        from repro.serving.distributed import DistributedRFANN

        vecs = make_vectors(1024, 16, seed=0)
        attrs = make_attrs(1024, seed=0)
        mesh = jax.make_mesh((8,), ("data",))
        qv = make_vectors(24, 16, seed=7)
        s = np.sort(attrs)
        rg = np.concatenate([
            selectivity_ranges(attrs, 10, 0.01, seed=3),     # narrow -> scan
            selectivity_ranges(attrs, 10, 0.5, seed=4),      # wide -> beam
            np.asarray([[s[5] + 1e-7, s[5] + 2e-7],          # globally empty
                        [s[17], s[17]],                      # single point
                        [s[3], s[40]],                       # shard 0 only:
                        [s[0], s[-1]]], np.float32)])        #  7 empty clips
        dist = DistributedRFANN(vecs, attrs, n_shards=8, mesh=mesh, m=16,
                                ef_spatial=16, ef_attribute=24)
        lo, hi = rank_interval(dist.attrs_sorted, rg)
        strat, _ = dist.mesh_substrate.plan_strategies(lo, hi, k=8,
                                                       mode='auto')
        assert (strat == SCAN).any() and (strat == BEAM).any(), strat
        base, _ = dist.search(qv, rg, k=8, ef=1024, plan='graph')
        ids, _ = dist.search(qv, rg, k=8, ef=1024, plan='auto')
        for q in range(len(rg)):
            want = set(base[q][base[q] >= 0].tolist())
            got = set(ids[q][ids[q] >= 0].tolist())
            assert got == want, (q, sorted(got), sorted(want))
        assert (base[20] == -1).all()                        # empty row
        print('OK', strat.tolist())
    """)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "OK" in r.stdout


# ------------------------------------------------------ empty-partition guard
def test_plan_never_emits_empty_partitions():
    pl = QueryPlanner(n=10_000)
    rng = np.random.default_rng(0)
    for mode in ("auto", "scan", "beam"):
        for q in (0, 1, 7, 33):
            lo = rng.integers(0, 10_000, q)
            hi = lo + rng.integers(-5, 5_000, q)     # includes empty ranges
            plan = pl.plan_batch(lo, hi, k=10, ef=64, mode=mode)
            assert all(len(p.indices) > 0 for p in plan.partitions)
            covered = (np.concatenate([p.indices for p in plan.partitions])
                       if plan.partitions else np.zeros(0, np.int64))
            assert sorted(covered.tolist()) == list(range(q))


def test_empty_partition_and_empty_batch_do_not_crash():
    """Regression: dispatching a zero-query partition used to die on
    ``idx[-1:]``; the substrate now guards it and zero-query requests."""
    vecs, attrs = _corpus(128, 8)
    idx = RNSGIndex.build(vecs, attrs, m=8, ef_spatial=8, ef_attribute=12)
    sub = idx.substrate
    ids, d, st = sub._run_beam(np.zeros((0, 8), np.float32),
                               np.zeros(0, np.int64), np.zeros(0, np.int64),
                               np.zeros(0, np.int64), 16, 8, 5)
    assert ids.shape == (0, 5) and st["hops"].shape == (0,)
    for plan in ("graph", "auto", "scan", "beam"):
        res = sub.run(SearchRequest(queries=np.zeros((0, 8), np.float32),
                                    lo=np.zeros(0, np.int64),
                                    hi=np.zeros(0, np.int64),
                                    k=5, ef=16, strategy=plan))
        assert res.ids.shape == (0, 5)


# ------------------------------------------------------------- beam early-out
def test_beam_early_out_same_results_fewer_hops():
    """Narrow range (in-range count << ef): the pool never fills, so the
    legacy condition burns steps_cap; the early-out must return identical
    results in far fewer hops."""
    n, d, ef = 512, 16, 64
    vecs, attrs = _corpus(n, d, seed=3)
    idx = RNSGIndex.build(vecs, attrs, m=16, ef_spatial=16, ef_attribute=24)
    g = idx.g
    nq = 8
    qv = jnp.asarray(make_vectors(nq, d, seed=9))
    lo = jnp.asarray(np.full(nq, 100, np.int32))
    hi = jnp.asarray(np.full(nq, 115, np.int32))     # 16 in-range nodes < ef
    entry = select_entry(jnp.asarray(g.rmq), jnp.asarray(g.dist_c), lo, hi, n)
    args = (jnp.asarray(g.vecs), jnp.asarray(g.nbrs), qv, lo, hi, entry)
    i_new, d_new, st_new = beam_search_batch(*args, k=5, ef=ef,
                                             early_stop=True)
    i_old, d_old, st_old = beam_search_batch(*args, k=5, ef=ef,
                                             early_stop=False)
    assert np.array_equal(np.asarray(i_new), np.asarray(i_old))
    assert np.allclose(np.asarray(d_new), np.asarray(d_old), equal_nan=True)
    steps_cap = 8 * ef + 64
    assert (np.asarray(st_old["hops"]) == steps_cap).all()   # the old burn
    assert (np.asarray(st_new["hops"]) < 64).all()           # early exit


# ------------------------------------------------- visited-table counters
def test_visited_counters_count_real_lanes_only(monkeypatch):
    """``beam_visited_inserts_total`` / ``beam_visited_evictions_total``
    sum the real lanes of a beam partition: the pad lanes that fill it to a
    power of two repeat the last query's work and are not booked.  A
    16-slot table makes evictions certain."""
    from functools import partial
    from repro.obs.metrics import MetricsRegistry
    from repro.search import substrate as sm
    vecs, attrs = _corpus(512, 16, seed=4)
    idx = RNSGIndex.build(vecs, attrs, m=16, ef_spatial=16, ef_attribute=24)
    reg = MetricsRegistry()
    sub = sm.SearchSubstrate.from_graph(idx.g, metrics=reg)
    monkeypatch.setattr(sm, "beam_search_batch",
                        partial(beam_search_batch, _visited_slots=16))
    qv = make_vectors(3, 16, seed=5)
    lo, hi = np.zeros(3, np.int64), np.full(3, 511, np.int64)
    ids, d, st, book = sub._dispatch_beam(qv, lo, hi, np.arange(3), 32, 8,
                                          5)()
    book()
    assert ids.shape == (3, 5) and st["evictions"].shape == (3,)
    assert st["ndist"][-1] > 0 and st["evictions"][-1] > 0   # pads would show
    inserts = reg.counter("beam_visited_inserts_total").value
    evictions = reg.counter("beam_visited_evictions_total").value
    assert inserts == int(st["ndist"].sum())
    assert evictions == int(st["evictions"].sum())
