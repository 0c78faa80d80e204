"""Adaptive query planner: kernel correctness, routing, exactness, ordering,
fixed-shape bucketing, and the bounded engine-stats reservoir."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.rfann import RNSGIndex
from repro.data.ann import (ground_truth, make_attrs, make_vectors,
                            recall_at_k, selectivity_ranges)
from repro.kernels.ops import range_scan
from repro.kernels.range_scan import range_scan_pallas
from repro.kernels.ref import range_scan_ref
from repro.planner import (QueryPlanner, bucket_for_len, ef_bucket,
                           next_pow2, pad_pow2, window_rows)

RNG = np.random.default_rng(0)


def _padded(n, d, tb=128, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    n_pad = -(-n // tb) * tb
    d_pad = -(-d // 128) * 128
    xp = np.zeros((n_pad, d_pad), np.float32)
    xp[:n, :d] = x
    return x, xp, d_pad


# ------------------------------------------------------------- kernel (Pallas)
@pytest.mark.parametrize("bucket", [64, 128, 512])
def test_range_scan_kernel_matches_ref(bucket):
    """Acceptance: Pallas kernel vs jnp reference on masked slices, interpret
    mode on CPU — arbitrary (unaligned) starts, short/empty/clipped lens."""
    n, d, q = 900, 40, 9
    x, xp, d_pad = _padded(n, d)
    starts = RNG.integers(0, n, q).astype(np.int32)
    lens = np.minimum(RNG.integers(0, bucket + 1, q), n - starts).astype(np.int32)
    lens[0] = 0                                    # empty window
    starts[1] = n - 1                              # tail, len clips to 1
    lens[1] = 1
    qv = np.zeros((q, d_pad), np.float32)
    qv[:, :d] = RNG.standard_normal((q, d)).astype(np.float32)
    got_i, got_d = range_scan(jnp.asarray(xp), jnp.asarray(starts),
                              jnp.asarray(lens), jnp.asarray(qv),
                              bucket=bucket, k=5)
    ref_i, ref_d = range_scan_ref(jnp.asarray(xp), jnp.asarray(starts),
                                  jnp.asarray(lens), jnp.asarray(qv),
                                  bucket=bucket, k=5)
    assert np.array_equal(np.asarray(got_i), np.asarray(ref_i))
    gd, rd = np.asarray(got_d), np.asarray(ref_d)
    mask = np.isfinite(rd)
    assert np.array_equal(mask, np.isfinite(gd))
    assert np.allclose(gd[mask], rd[mask], rtol=1e-4, atol=1e-4)


def test_range_scan_is_exact_vs_brute():
    n, d = 700, 24
    x, xp, d_pad = _padded(n, d, seed=3)
    starts = np.asarray([0, 123, 600], np.int32)
    lens = np.asarray([64, 200, 100], np.int32)    # last clips to n
    lens = np.minimum(lens, n - starts)
    qraw = RNG.standard_normal((3, d)).astype(np.float32)
    qv = np.zeros((3, d_pad), np.float32)
    qv[:, :d] = qraw
    ids, _ = range_scan(jnp.asarray(xp), jnp.asarray(starts),
                        jnp.asarray(lens), jnp.asarray(qv), bucket=256, k=7)
    for qi in range(3):
        L, ln = int(starts[qi]), int(lens[qi])
        ex = np.sum((x[L:L + ln] - qraw[qi]) ** 2, axis=1)
        want = set((np.argsort(ex)[:7] + L).tolist())
        got = set(int(i) for i in np.asarray(ids[qi]) if i >= 0)
        assert got == want


# -------------------------------------------------------------------- bucketing
def test_bucketing_helpers():
    assert [next_pow2(v) for v in (1, 2, 3, 64, 65)] == [1, 2, 4, 64, 128]
    assert bucket_for_len(3, min_bucket=64) == 64
    assert bucket_for_len(500) == 512
    assert bucket_for_len(5000, max_bucket=4096) == 4096
    assert window_rows(64) == 256 and window_rows(512) == 640
    assert pad_pow2(1) == 8 and pad_pow2(9) == 16
    assert ef_bucket(length=4, k=10, ef=64) == 16   # floor at next_pow2(k)
    assert ef_bucket(length=40, k=10, ef=64) == 64
    assert ef_bucket(length=10_000, k=10, ef=64) == 64


def test_bucketing_no_recompile_within_signature():
    """Two different batches with the same (bucket, padQ, k) signature must
    hit the compiled kernel cache — no recompilation."""
    n, d = 600, 16
    _, xp, d_pad = _padded(n, d, seed=1)
    xj = jnp.asarray(xp)

    def call(seed):
        rng = np.random.default_rng(seed)
        starts = jnp.asarray(rng.integers(0, n - 80, 8).astype(np.int32))
        lens = jnp.asarray(rng.integers(1, 80, 8).astype(np.int32))
        qv = jnp.asarray(rng.standard_normal((8, d_pad)).astype(np.float32))
        r = range_scan(xj, starts, lens, qv, bucket=128, k=5)
        return np.asarray(r[0])

    call(1)
    size_after_first = range_scan_pallas._cache_size()
    call(2)
    call(3)
    assert range_scan_pallas._cache_size() == size_after_first


# ----------------------------------------------------------------- routing/plan
def test_planner_routes_by_selectivity():
    pl = QueryPlanner(n=100_000)
    lo = np.asarray([10, 0, 50, 2000])
    hi = np.asarray([40, 99_999, 49, 2100])        # narrow, full, empty, small
    plan = pl.plan_batch(lo, hi, k=10, ef=64)
    assert plan.strategy.tolist() == [0, 1, 0, 0]
    sigs = {p.signature for p in plan.partitions}
    assert all(s[2] == next_pow2(max(s[2], 1)) for s in sigs)   # pow2 pads
    covered = np.concatenate([p.indices for p in plan.partitions])
    assert sorted(covered.tolist()) == [0, 1, 2, 3]             # exact cover


def test_planner_forced_modes():
    pl = QueryPlanner(n=10_000)
    lo = np.asarray([0, 100])
    hi = np.asarray([9_999, 200])
    assert (pl.plan_batch(lo, hi, k=10, ef=64, mode="scan").strategy == 0).all()
    assert (pl.plan_batch(lo, hi, k=10, ef=64, mode="beam").strategy == 1).all()


def test_choose_strategy_batch_matches_scalar():
    """The vectorized routing decision (the host half of mesh dispatch) must
    agree element-wise with the scalar reference across the whole regime
    spectrum — empty, tiny, boundary, ceiling, full — for every k, including
    one beyond the scan's lane row."""
    pl = QueryPlanner(n=100_000)
    rng = np.random.default_rng(5)
    lens = np.concatenate([
        np.asarray([0, 1, 5, 10, 11, 64, 65, 12_500, 12_501, 100_000]),
        rng.integers(0, 100_000, 200),
        2 ** rng.integers(0, 17, 50),              # pow2 boundaries
    ])
    for k in (10, 1, 50, 129):
        batch = pl.choose_strategy_batch(lens, k=k)
        scalar = np.asarray([pl.choose_strategy(int(ln), k=k)
                             for ln in lens], np.int8)
        assert np.array_equal(batch, scalar), k
    # and plan_batch routes with the same decisions (lo/hi -> lens)
    batch = pl.choose_strategy_batch(lens, k=10)
    lo = np.zeros(len(lens), np.int64)
    plan = pl.plan_batch(lo, lo + lens - 1, k=10, ef=64)
    assert np.array_equal(plan.strategy, batch)


@pytest.mark.parametrize("n, max_scan_frac, k, lens, want", [
    # the served deployment: 2^20 rows, ceiling 2^-3 — 2^-3 scans, wider beams
    (1 << 20, 0.125, 10, [0, 131_072, 131_073, 262_144, 1 << 20],
     [0, 0, 1, 1, 1]),
    # a raised ceiling moves the one line and nothing else
    (1 << 20, 0.5, 10, [0, 131_072, 131_073, 262_144, 1 << 20],
     [0, 0, 0, 0, 1]),
    # a small corpus: the ceiling is the min bucket (64), yet a slice of at
    # most k rows still scans
    (256, 0.125, 100, [0, 64, 100, 101, 256], [0, 0, 0, 1, 1]),
])
def test_routing_is_one_threshold(n, max_scan_frac, k, lens, want):
    """Routing is the scan ceiling alone: slices up to ``max_scan_len`` (or
    up to ``k`` rows) scan, longer ones beam, whatever either strategy
    costs."""
    pl = QueryPlanner(n=n, max_scan_frac=max_scan_frac)
    assert pl.choose_strategy_batch(np.asarray(lens), k=k).tolist() == want
    assert [pl.choose_strategy(ln, k=k) for ln in lens] == want


# ------------------------------------------------------------------ end to end
def _small_index(n=512, d=16, seed=0):
    vecs = make_vectors(n, d, seed=seed)
    attrs = make_attrs(n, seed=seed)
    return vecs, attrs, RNSGIndex.build(vecs, attrs, m=16, ef_spatial=16,
                                        ef_attribute=24)


def test_scan_and_beam_agree_on_small_n():
    """With ef ≥ n the beam explores the whole in-range component, so the two
    strategies must return the same exact top-k."""
    n = 256
    vecs, attrs, idx = _small_index(n=n)
    qv = make_vectors(12, 16, seed=4)
    ranges = selectivity_ranges(attrs, 12, 0.3, seed=5)
    si, sd, _ = idx.search(qv, ranges, k=8, ef=n, plan="scan")
    bi, bd, _ = idx.search(qv, ranges, k=8, ef=n, plan="beam")
    for q in range(12):
        assert set(si[q][si[q] >= 0].tolist()) == set(bi[q][bi[q] >= 0].tolist())
    fin = np.isfinite(sd)
    assert np.array_equal(fin, np.isfinite(bd))
    assert np.allclose(sd[fin], bd[fin], rtol=1e-3, atol=1e-3)


def test_mixed_strategy_batch_preserves_request_order():
    vecs, attrs, idx = _small_index(n=1024)
    nq = 20
    qv = make_vectors(nq, 16, seed=8)
    narrow = selectivity_ranges(attrs, nq // 2, 0.01, seed=6)
    wide = selectivity_ranges(attrs, nq // 2, 0.9, seed=7)
    ranges = np.empty((nq, 2), np.float32)
    ranges[0::2] = narrow                          # interleave strategies
    ranges[1::2] = wide
    ids, dists, st = idx.search(qv, ranges, k=5, ef=64, plan="auto")
    assert 0.0 < st["scan_frac"] < 1.0             # genuinely mixed batch
    for q in range(nq):                            # each row == its solo run
        one_i, one_d, _ = idx.search(qv[q:q + 1], ranges[q:q + 1], k=5,
                                     ef=64, plan="auto")
        assert np.array_equal(ids[q], one_i[0]), q
    for q in range(nq):                            # and respects its filter
        for i in ids[q]:
            if i >= 0:
                assert ranges[q, 0] <= attrs[i] <= ranges[q, 1]


def test_auto_plan_recall_not_worse_than_graph():
    vecs, attrs, idx = _small_index(n=1024)
    nq = 40
    qv = make_vectors(nq, 16, seed=3)
    ranges = selectivity_ranges(attrs, nq, 0.02, seed=9)
    order = np.argsort(attrs, kind="stable")
    gt_r, _ = ground_truth(vecs[order], attrs[order], qv, ranges, 10)
    gt = np.where(gt_r >= 0, order[np.maximum(gt_r, 0)], -1)
    rg = recall_at_k(idx.search(qv, ranges, k=10, ef=64, plan="graph")[0], gt)
    ra = recall_at_k(idx.search(qv, ranges, k=10, ef=64, plan="auto")[0], gt)
    assert ra >= rg - 1e-9


# ------------------------------------------------------------------ engine
def test_engine_serves_with_planner():
    vecs, attrs, idx = _small_index(n=512)
    from repro.serving.engine import RFANNEngine
    eng = RFANNEngine(idx, k=5, ef=32, max_batch=16, max_wait_ms=5,
                      plan="auto")
    qv = make_vectors(24, 16, seed=6)
    rgs = np.concatenate([selectivity_ranges(attrs, 12, 0.01, seed=1),
                          selectivity_ranges(attrs, 12, 0.9, seed=2)])
    futs = [eng.submit(qv[i], rgs[i]) for i in range(24)]
    res = [f.result(timeout=120) for f in futs]
    eng.close()
    assert len(res) == 24 and all(r[0].shape == (5,) for r in res)
    assert eng.stats.scan_routed > 0


# -------------------------------------------------------- k beyond the scan
def test_range_scan_rejects_k_beyond_lane_row():
    """k > 128 cannot live in the scan's running top-k row: the kernel
    raises instead of silently materializing the jnp oracle."""
    _, xp, d_pad = _padded(300, 8)
    with pytest.raises(ValueError, match="running top-k"):
        range_scan(jnp.asarray(xp), jnp.zeros(2, jnp.int32),
                   jnp.full(2, 200, jnp.int32),
                   jnp.zeros((2, d_pad), jnp.float32), bucket=256, k=200)


def test_planner_routes_k_beyond_scan_to_beam():
    """Every query with k > 128 — narrow, empty or forced-beam — routes to
    beam, and the served auto path answers it end to end."""
    from repro.planner import BEAM, SCAN
    from repro.planner.planner import SCAN_MAX_K
    p = QueryPlanner(4096)
    lo = np.asarray([0, 10, 7])
    hi = np.asarray([3, 400, 5])                   # tiny, narrow, empty
    for mode in ("auto", "beam"):
        plan = p.plan_batch(lo, hi, k=SCAN_MAX_K + 1, ef=256, mode=mode)
        assert (plan.strategy == BEAM).all(), mode
        assert all(part.kind == "beam" for part in plan.partitions)
    assert p.choose_strategy(3, k=SCAN_MAX_K + 1) == BEAM
    assert p.choose_strategy(3, k=SCAN_MAX_K) == SCAN
    vecs, attrs, idx = _small_index(n=512)
    qv = make_vectors(4, 16, seed=11)
    ranges = selectivity_ranges(attrs, 4, 0.5, seed=12)
    ids, dists, st = idx.search(qv, ranges, k=150, ef=150, plan="auto")
    assert ids.shape == (4, 150) and (st["strategy"] == BEAM).all()
    assert (ids >= 0).sum(axis=1).min() > 128      # a genuinely large k


# ------------------------------------------------------- engine failures
class _FailingIndex:
    """An index whose dispatch raises (a device or kernel error)."""

    def search(self, queries, attr_ranges, **kw):
        raise RuntimeError("injected dispatch failure")


def test_engine_dispatch_error_fails_futures():
    """A dispatch that raises must fail that batch's futures with the
    error — never kill the dispatcher and leave callers blocked — and the
    engine keeps serving later batches."""
    from repro.serving.engine import RFANNEngine
    _, attrs, idx = _small_index(n=512)
    eng = RFANNEngine(_FailingIndex(), k=5, ef=32, max_batch=4,
                      max_wait_ms=1)
    try:
        futs = [eng.submit(np.zeros(16, np.float32), (0.0, 1.0))
                for _ in range(6)]
        for f in futs:
            with pytest.raises(RuntimeError, match="injected dispatch"):
                f.result(timeout=60)
        eng.swap_index(idx)                        # dispatcher still alive
        r = eng.submit(make_vectors(1, 16, seed=3)[0],
                       (float(attrs.min()), float(attrs.max())))
        assert r.result(timeout=120).ids.shape == (5,)
    finally:
        eng.close()
