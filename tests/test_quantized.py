"""Quantized scoring path (int8/bf16 + exact f32 rerank): corpus artifacts,
kernel parity vs the jnp oracles (interpret mode), the rerank exactness
contract, end-to-end strategy/mesh/engine parity, per-precision cache keys,
the shared benchmark ``recall_at_k``, and uniform SearchRequest validation
messages."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:           # benchmarks/ is a namespace package
    sys.path.insert(0, str(ROOT))

from benchmarks.common import recall_at_k as bench_recall_at_k  # noqa: E402
from repro.core.beam import rerank_pool  # noqa: E402
from repro.core.rfann import RNSGIndex  # noqa: E402
from repro.data.ann import (make_attrs, make_vectors,  # noqa: E402
                            selectivity_ranges)
from repro.kernels.ops import (gather_dist, gather_rerank,  # noqa: E402
                               gather_topk, range_scan)
from repro.kernels.quantize import (PRECISIONS, RERANK_CAP,  # noqa: E402
                                    dequantize, quantize_corpus,
                                    rerank_depth, sort_candidates)
from repro.kernels.ref import (gather_dist_ref, gather_rerank_ref,  # noqa: E402
                               gather_topk_ref, range_scan_ref)
from repro.search import SearchCache, SearchRequest, query_key  # noqa: E402

RNG = np.random.default_rng(0)
QUANT = ("int8", "bf16")


def _padded(n, d, tb=128, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    n_pad = -(-n // tb) * tb
    d_pad = -(-d // 128) * 128
    xp = np.zeros((n_pad, d_pad), np.float32)
    xp[:n, :d] = x
    return x, xp, d_pad


def _quant_ops(xp, precision):
    """(scoring array, scale-or-None) as the kernels consume them."""
    qc = quantize_corpus(jnp.asarray(xp), precision)
    return qc.data, qc.scale


# ------------------------------------------------------------ corpus artifact
def test_int8_roundtrip_error_bound():
    x = RNG.standard_normal((200, 17)).astype(np.float32) * 3.0
    x[:, 5] = 0.0                                   # all-zero dimension
    qc = quantize_corpus(jnp.asarray(x), "int8")
    assert qc.data.dtype == jnp.int8 and qc.scale.shape == (17,)
    err = np.abs(np.asarray(dequantize(qc)) - x)
    bound = np.asarray(qc.scale)[None, :] * 0.5 + 1e-6
    assert (err <= bound).all()
    assert (np.asarray(dequantize(qc))[:, 5] == 0.0).all()   # exact zeros


def test_bf16_corpus_and_bytes():
    x = RNG.standard_normal((64, 32)).astype(np.float32)
    b = quantize_corpus(jnp.asarray(x), "bf16")
    i = quantize_corpus(jnp.asarray(x), "int8")
    assert b.data.dtype == jnp.bfloat16 and b.scale is None
    assert b.bytes_per_vector == 64 and i.bytes_per_vector == 32   # vs 128
    with pytest.raises(ValueError, match="invalid precision"):
        quantize_corpus(jnp.asarray(x), "f16")


def test_sort_candidates_pads_last():
    ids = jnp.asarray([[7, -1, 3, 9, -1], [0, 2, 1, -1, 5]], jnp.int32)
    got = np.asarray(sort_candidates(ids))
    assert got.tolist() == [[3, 7, 9, -1, -1], [0, 1, 2, 5, -1]]


def test_rerank_depth_clamps():
    assert rerank_depth(10, 64) == RERANK_CAP       # 4*64 hits the lane cap
    assert rerank_depth(10, 8) == 32                # ~4*ef regime
    assert rerank_depth(10, 1) == 10                # never below k
    assert rerank_depth(200, 8) == 200              # k beats the cap
    assert rerank_depth(10, 64, cap=64) == 64       # caller-tightened cap


# ------------------------------------------------- kernel parity (interpret)
@pytest.mark.parametrize("precision", QUANT)
def test_gather_kernels_quantized_match_ref(precision):
    """gather_dist / gather_topk scoring a quantized corpus (with the int8
    scale dequantized in VMEM) must match the jnp oracle bit-for-bit on ids
    and to f32 tolerance on distances — masked ids included."""
    n, m, d, k = 200, 37, 48, 9
    x = RNG.standard_normal((n, d)).astype(np.float32)
    data, scale = _quant_ops(x, precision)
    ids = jnp.asarray(RNG.integers(0, n, m), jnp.int32)
    ids = jnp.where(jnp.asarray(RNG.random(m)) < 0.3, -1, ids)
    q = jnp.asarray(RNG.standard_normal(d), jnp.float32)
    got = gather_dist(data, jnp.maximum(ids, 0), q, scale=scale)
    want = gather_dist_ref(data, jnp.maximum(ids, 0), q, scale=scale)
    assert np.allclose(got, want, rtol=1e-3, atol=1e-3)
    gi, gd = gather_topk(data, ids, q, k=k, scale=scale)
    ri, rd = gather_topk_ref(data, ids, q, k=k, scale=scale)
    assert np.array_equal(np.asarray(gi), np.asarray(ri))
    fin = np.isfinite(np.asarray(rd))
    assert np.allclose(np.asarray(gd)[fin], np.asarray(rd)[fin],
                       rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("precision", QUANT)
def test_range_scan_quantized_matches_ref(precision):
    n, d, q, bucket, k = 900, 40, 9, 256, 7
    _, xp, d_pad = _padded(n, d)
    data, scale = _quant_ops(xp, precision)
    starts = RNG.integers(0, n, q).astype(np.int32)
    lens = np.minimum(RNG.integers(0, bucket + 1, q),
                      n - starts).astype(np.int32)
    lens[0] = 0                                     # empty window
    qv = np.zeros((q, d_pad), np.float32)
    qv[:, :d] = RNG.standard_normal((q, d)).astype(np.float32)
    got_i, got_d = range_scan(data, jnp.asarray(starts), jnp.asarray(lens),
                              jnp.asarray(qv), bucket=bucket, k=k,
                              scale=scale)
    ref_i, ref_d = range_scan_ref(data, jnp.asarray(starts),
                                  jnp.asarray(lens), jnp.asarray(qv),
                                  bucket=bucket, k=k, scale=scale)
    assert np.array_equal(np.asarray(got_i), np.asarray(ref_i))
    gd, rd = np.asarray(got_d), np.asarray(ref_d)
    mask = np.isfinite(rd)
    assert np.array_equal(mask, np.isfinite(gd))
    assert np.allclose(gd[mask], rd[mask], rtol=1e-3, atol=1e-3)


def test_gather_rerank_matches_ref():
    n, d, q, m, k = 300, 24, 11, 40, 8
    x = jnp.asarray(RNG.standard_normal((n, d)), jnp.float32)
    ids = RNG.integers(0, n, (q, m)).astype(np.int32)
    ids[RNG.random((q, m)) < 0.25] = -1             # sparse survivor lists
    ids[3] = -1                                     # one fully-empty pool
    qv = jnp.asarray(RNG.standard_normal((q, d)), jnp.float32)
    gi, gd = gather_rerank(x, jnp.asarray(ids), qv, k=k)
    ri, rd = gather_rerank_ref(x, jnp.asarray(ids), qv, k=k)
    assert np.array_equal(np.asarray(gi), np.asarray(ri))
    fin = np.isfinite(np.asarray(rd))
    assert np.allclose(np.asarray(gd)[fin], np.asarray(rd)[fin],
                       rtol=1e-4, atol=1e-4)


# -------------------------------------------------------- rerank exactness
@pytest.mark.parametrize("precision", QUANT)
@pytest.mark.parametrize("use_kernel", [False, True])
def test_scan_rerank_restores_exact_f32_topk(precision, use_kernel):
    """The tentpole invariant: quantized scan keeping ``rerank_depth``
    survivors + f32 rerank returns the exact f32 top-k id set — empty and
    sub-k slices included."""
    n, d, k, ef, bucket = 700, 24, 7, 16, 256
    _, xp, d_pad = _padded(n, d, seed=3)
    data, scale = _quant_ops(xp, precision)
    starts = np.asarray([0, 123, 600, 42, 42], np.int32)
    lens = np.asarray([64, 200, 100, 0, 3], np.int32)   # empty + sub-k rows
    lens = np.minimum(lens, n - starts)
    qv = np.zeros((len(starts), d_pad), np.float32)
    qv[:, :d] = RNG.standard_normal((len(starts), d)).astype(np.float32)
    f32_i, f32_d = range_scan(jnp.asarray(xp), jnp.asarray(starts),
                              jnp.asarray(lens), jnp.asarray(qv),
                              bucket=bucket, k=k)
    rq = rerank_depth(k, ef)
    q_i, _ = range_scan(data, jnp.asarray(starts), jnp.asarray(lens),
                        jnp.asarray(qv), bucket=bucket, k=rq, scale=scale)
    ids, dists = rerank_pool(jnp.asarray(xp), q_i, jnp.asarray(qv), k,
                             use_kernel=use_kernel)
    assert np.array_equal(np.asarray(ids), np.asarray(f32_i))
    fin = np.isfinite(np.asarray(f32_d))
    assert np.allclose(np.asarray(dists)[fin], np.asarray(f32_d)[fin],
                       rtol=1e-4, atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(5, 40), st.integers(2, 8),
       st.integers(1, 6))
def test_rerank_roundtrip_property(seed, n, d, k):
    """Property (hypothesis via the _hyp shim): for any corpus, quantizing
    to int8, taking every row as the survivor pool, and f32-reranking
    restores the exact f32 top-k id set — quantization error can reorder
    the quantized pass but never the reranked result."""
    k = min(k, n)
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    qv = rng.standard_normal((1, d)).astype(np.float32)
    pool = jnp.asarray(np.arange(n, dtype=np.int32)[None, :])
    ids, dists = rerank_pool(jnp.asarray(x), pool, jnp.asarray(qv), k,
                             use_kernel=False)
    d2 = np.sum((x - qv[0]) ** 2, axis=1)
    want = np.argsort(d2, kind="stable")[:k]
    assert np.array_equal(np.asarray(ids)[0], want)
    assert np.allclose(np.asarray(dists)[0], d2[want], rtol=1e-4, atol=1e-4)


# -------------------------------------------------- end-to-end parity suites
@pytest.fixture(scope="module")
def quant_index():
    n, d = 300, 24
    vecs = make_vectors(n, d, seed=0)
    attrs = make_attrs(n, seed=0)
    ix = RNSGIndex.build(vecs, attrs, m=12)
    for p in QUANT:
        ix.install_quantized(p)
    nq = 10
    qv = make_vectors(nq, d, seed=7)
    ranges = selectivity_ranges(attrs, nq, 0.3, seed=3)
    ranges[0] = [2.0, 1.0]                          # empty attribute range
    return ix, qv, ranges, n


@pytest.mark.parametrize("plan", ["graph", "auto", "scan", "beam"])
def test_strategy_parity_all_precisions(quant_index, plan):
    """Every strategy × precision at covering ef returns the exact f32
    top-k id set, with exact-f32 distances on the quantized rows."""
    ix, qv, ranges, n = quant_index
    k = 5
    base = ix.search(qv, ranges, k=k, ef=n, plan=plan)
    for prec in QUANT:
        res = ix.search(qv, ranges, k=k, ef=n, plan=plan, precision=prec)
        assert np.array_equal(np.sort(res.ids, 1), np.sort(base.ids, 1)), \
            (plan, prec)
        m = res.ids >= 0
        assert np.allclose(res.dists[m], base.dists[m], atol=1e-3), \
            (plan, prec)


def test_mesh_parity_all_precisions():
    from jax.sharding import Mesh

    from repro.serving.distributed import DistributedRFANN
    n, d, nq, k = 256, 24, 8, 5
    vecs = make_vectors(n, d, seed=0)
    attrs = make_attrs(n, seed=0)
    qv = make_vectors(nq, d, seed=7)
    ranges = selectivity_ranges(attrs, nq, 0.4, seed=3)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    dist = DistributedRFANN(vecs, attrs, n_shards=1, mesh=mesh, m=12)
    for p in QUANT:
        dist.install_quantized(p)
    for plan in ("graph", "auto"):
        i0, d0 = dist.search(qv, ranges, k=k, ef=n, plan=plan)
        for prec in QUANT:
            i1, d1 = dist.search(qv, ranges, k=k, ef=n, plan=plan,
                                 precision=prec)
            assert np.array_equal(np.sort(i0, 1), np.sort(i1, 1)), \
                (plan, prec)
            m = np.asarray(i1) >= 0
            assert np.allclose(np.asarray(d1)[m], np.asarray(d0)[m],
                               atol=1e-3), (plan, prec)


def test_quantized_routed_counters(quant_index):
    from repro.obs import MetricsRegistry
    ix, qv, ranges, n = quant_index
    reg = MetricsRegistry()
    ix.install_metrics(reg)
    try:
        ix.search(qv, ranges, k=5, ef=32, plan="scan", precision="int8")
        assert reg.counter("queries_int8_total").value == len(qv)
        assert reg.counter("rerank_rows_total").value > 0
    finally:
        ix.install_metrics(None)


# -------------------------------------------------------------- cache keys
def test_query_key_separates_precision_and_width():
    q = np.ones(8, np.float32)
    base = query_key(q, 0, 10, 5, 64, "auto")
    assert base[-1] == "f32" and base[-2] == 1      # defaults ride the key
    assert query_key(q, 0, 10, 5, 64, "auto", precision="int8") != base
    assert query_key(q, 0, 10, 5, 64, "auto", beam_width=4) != base


def test_auto_rows_hit_on_repeat(quant_index):
    """End to end: a repeated auto-routed batch is served from the cache —
    routing depends only on each interval, so the stored rows are the
    answer — and the hits are bit-identical to the dispatch that stored
    them."""
    ix, qv, ranges, n = quant_index
    cache = SearchCache(1 << 20)
    ix.install_cache(cache)
    try:
        first = ix.search(qv, ranges, k=5, ef=32, plan="auto")  # populate
        again = ix.search(qv, ranges, k=5, ef=32, plan="auto")  # all hits
        assert again.stats["cache_hits"] == len(qv)
        np.testing.assert_array_equal(again.ids, first.ids)
        np.testing.assert_array_equal(again.dists, first.dists)
    finally:
        ix.install_cache(None)


# ------------------------------------------------------ shared recall_at_k
def test_recall_at_k_gt_smaller_than_k():
    found = np.asarray([[3, 7, 9], [1, 2, 4]])
    gt = np.asarray([[3, -1, -1], [-1, -1, -1]])    # sub-k + empty rows
    assert bench_recall_at_k(found, gt) == 1.0      # denominator = valid gt
    assert bench_recall_at_k(np.asarray([[7, 8, 9], [0, 0, 0]]), gt) == 0.0


def test_recall_at_k_tie_handling():
    gt = np.asarray([[0, 1]])
    gt_d = np.asarray([[1.0, 2.0]])
    found = np.asarray([[0, 5]])
    found_d = np.asarray([[1.0, 2.0]])              # id 5 ties the gt worst
    assert bench_recall_at_k(found, gt) == 0.5      # set-only view: a miss
    assert bench_recall_at_k(found, gt, gt_dists=gt_d,
                             found_dists=found_d) == 1.0
    # hits stay capped at |gt-valid| even with many boundary ties
    many = np.asarray([[0, 5, 6, 7]])
    many_d = np.asarray([[1.0, 2.0, 2.0, 2.0]])
    assert bench_recall_at_k(many, gt, gt_dists=gt_d,
                             found_dists=many_d) == 1.0


# --------------------------------------------------- request validation
@pytest.mark.parametrize("kw,msg", [
    (dict(strategy="bogus"), "invalid strategy='bogus'"),
    (dict(precision="f16"), "invalid precision='f16'"),
    (dict(k=0), "invalid k=0"),
    (dict(ef=0), "invalid ef=0"),
    (dict(beam_width=0), "invalid beam_width=0"),
])
def test_request_validation_names_field_and_value(kw, msg):
    base = dict(queries=np.zeros((1, 4), np.float32),
                lo=np.zeros(1, np.int64), hi=np.zeros(1, np.int64))
    with pytest.raises(ValueError) as ei:
        SearchRequest(**{**base, **kw})
    assert f"SearchRequest: {msg}" in str(ei.value)


def test_precisions_exported():
    from repro.search import PRECISIONS as P2
    assert P2 == PRECISIONS == ("f32", "int8", "bf16")
