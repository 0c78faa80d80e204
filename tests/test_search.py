"""Beam search, entry generation, pruning equivalence, end-to-end recall."""
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core.beam import beam_search_batch
from repro.core.construction import RNSGGraph, build_rnsg
from repro.core.entry import (build_rmq, centroid_dists, entry_from_stack,
                              entry_stacks, rmq_query_np)
from repro.core.pruning import prune_all_jax, rrng_prune_np
from repro.core.rfann import RNSGIndex
from repro.data.ann import (ground_truth, make_attrs, make_vectors,
                            mixed_workload, recall_at_k, selectivity_ranges)

import jax.numpy as jnp


# ---------------------------------------------------------------- entry (Alg 3)
@settings(max_examples=30, deadline=None)
@given(st.lists(st.floats(0, 1e6, allow_nan=False), min_size=2, max_size=200),
       st.integers(0, 10_000))
def test_alg3_stack_equals_rmq(dists, seed):
    d = np.asarray(dists, np.float32)
    d += np.arange(len(d)) * 1e-3          # break exact ties deterministically
    stacks = entry_stacks(d)
    rmq = build_rmq(d)
    rng = np.random.default_rng(seed)
    for _ in range(10):
        lo = int(rng.integers(0, len(d)))
        hi = int(rng.integers(lo, len(d)))
        assert entry_from_stack(stacks, d, lo, hi) == rmq_query_np(rmq, d, lo, hi)


def test_alg3_stack_size_logarithmic():
    rng = np.random.default_rng(0)
    d = rng.random(20_000).astype(np.float32)
    sizes = [len(q) for q in entry_stacks(d)]
    # Lemma 4.8: E[|q|] = H_n ≈ ln n ≈ 9.9; generous bound
    assert np.mean(sizes) < 3 * np.log(len(d))


# ---------------------------------------------------------------- pruning (Alg 1)
def test_prune_jax_matches_numpy_reference():
    rng = np.random.default_rng(1)
    n, d, m = 120, 8, 10
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    # candidate sides: full windows (C = D) so both impls see identical input
    from repro.core.construction import _gap_sorted_side
    knn = np.full((n, 1), -1, np.int32)
    cl = _gap_sorted_side(n, knn, n, "l")
    cr = _gap_sorted_side(n, knn, n, "r")
    nbrs = prune_all_jax(vecs, cl, cr, m)
    for x in range(0, n, 7):
        ref = rrng_prune_np(x, np.arange(n), vecs, m)
        got = [int(v) for v in nbrs[x] if v >= 0]
        assert sorted(got) == sorted(ref), x


# ---------------------------------------------------------------- beam search
def _small_index(n=800, d=16, seed=0):
    vecs = make_vectors(n, d, seed=seed)
    attrs = make_attrs(n, seed=seed)
    return vecs, attrs, RNSGIndex.build(vecs, attrs, m=16, ef_spatial=16,
                                        ef_attribute=24)


def test_high_ef_reaches_high_recall():
    vecs, attrs, idx = _small_index()
    nq, k = 60, 10
    qv = make_vectors(nq, 16, seed=5)
    ranges, _ = mixed_workload(attrs, nq, seed=2, levels=6)
    order = np.argsort(attrs, kind="stable")
    gt_r, _ = ground_truth(vecs[order], attrs[order], qv, ranges, k)
    gt = np.where(gt_r >= 0, order[np.maximum(gt_r, 0)], -1)
    ids, _, _ = idx.search(qv, ranges, k=k, ef=128)
    assert recall_at_k(ids, gt) > 0.97


def test_empty_and_singleton_ranges():
    vecs, attrs, idx = _small_index()
    qv = make_vectors(3, 16, seed=9)
    s = np.sort(attrs)
    ranges = np.asarray([
        [s[5] + 1e-7, s[5] + 2e-7],     # empty
        [s[17], s[17]],                 # singleton
        [s[0], s[-1]],                  # full
    ], np.float32)
    ids, dists, _ = idx.search(qv, ranges, k=5, ef=32)
    assert (ids[0] == -1).all()
    assert (ids[1][0] >= 0) and (ids[1][1:] == -1).all()
    assert (ids[2] >= 0).all()


def test_results_respect_range_filter():
    vecs, attrs, idx = _small_index()
    nq = 40
    qv = make_vectors(nq, 16, seed=4)
    ranges = selectivity_ranges(attrs, nq, 0.05, seed=3)
    ids, _, _ = idx.search(qv, ranges, k=10, ef=64)
    for q in range(nq):
        for i in ids[q]:
            if i >= 0:
                assert ranges[q, 0] <= attrs[i] <= ranges[q, 1]


def test_multi_entry_beam():
    vecs, attrs, idx = _small_index()
    g = idx.g
    qv = jnp.asarray(make_vectors(4, 16, seed=11))
    n = g.n
    lo = jnp.zeros(4, jnp.int32)
    hi = jnp.full(4, n - 1, jnp.int32)
    entries = jnp.asarray([[0, n // 2, -1], [5, -1, -1],
                           [n - 1, 1, 2], [7, 8, 9]], jnp.int32)
    ids, d, _ = beam_search_batch(jnp.asarray(g.vecs), jnp.asarray(g.nbrs),
                                  qv, lo, hi, entries, k=5, ef=48)
    assert (np.asarray(ids) >= 0).all()


def test_kernel_backed_beam_matches_default():
    vecs, attrs, idx = _small_index(n=400)
    qv = make_vectors(16, 16, seed=13)
    ranges, _ = mixed_workload(attrs, 16, seed=8, levels=4)
    a, da, _ = idx.search(qv, ranges, k=5, ef=32, use_kernel=False)
    b, db, _ = idx.search(qv, ranges, k=5, ef=32, use_kernel=True)
    assert np.array_equal(a, b)
    assert np.allclose(da, db, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------- save/load
def test_index_save_load_roundtrip(tmp_path):
    vecs, attrs, idx = _small_index(n=300)
    p = str(tmp_path / "idx.npz")
    idx.save(p)
    idx2 = RNSGIndex.load(p)
    qv = make_vectors(8, 16, seed=3)
    ranges = selectivity_ranges(attrs, 8, 0.25, seed=1)
    a, _, _ = idx.search(qv, ranges, k=5, ef=32)
    b, _, _ = idx2.search(qv, ranges, k=5, ef=32)
    assert np.array_equal(a, b)


# ---------------------------------------------------------------- beyond-paper
def test_reverse_edges_preserve_heredity_and_help_recall():
    """Beyond-paper reverse-edge augmentation: with an unsaturated cap
    heredity holds exactly (the reverse of an in-range edge stays in range);
    and at the default cap mixed recall at fixed ef does not get worse."""
    from repro.core.construction import build_rnsg
    n, d = 1024, 16
    vecs = make_vectors(n, d, seed=2)
    attrs = np.arange(n).astype(np.float32)
    from repro.index.knn import exact_knn
    _, knn = exact_knn(vecs, 12)
    g = build_rnsg(vecs, attrs, m=8, ef_attribute=10, knn_ids=knn,
                   reverse_edges=True, reverse_cap=256)   # unsaturated cap
    assert (g.nbrs >= 0).sum(1).max() < 256               # cap never binds
    lo, hi = 200, 800
    ind = np.full((hi - lo, 12), -1, np.int32)
    for i in range(lo, hi):
        js = [j - lo for j in knn[i] if lo <= j < hi]
        ind[i - lo, :len(js)] = js
    g_sub = build_rnsg(vecs[lo:hi], attrs[lo:hi], m=8, ef_attribute=10,
                       knn_ids=ind, reverse_edges=True, reverse_cap=256)
    for i in range(hi - lo):
        glob = {j - lo for j in g.nbrs[lo + i] if lo <= j < hi}
        sub = {int(j) for j in g_sub.nbrs[i] if j >= 0}
        assert glob == sub, i

    vecs2 = make_vectors(2048, 16, seed=5)
    attrs2 = make_attrs(2048, seed=5)
    qv = make_vectors(50, 16, seed=77)
    ranges, _ = mixed_workload(attrs2, 50, seed=3, levels=5)
    order = np.argsort(attrs2, kind="stable")
    gt_r, _ = ground_truth(vecs2[order], attrs2[order], qv, ranges, 10)
    gt = np.where(gt_r >= 0, order[np.maximum(gt_r, 0)], -1)
    base = RNSGIndex(build_rnsg(vecs2, attrs2, m=12, ef_spatial=12, ef_attribute=16))
    aug = RNSGIndex(build_rnsg(vecs2, attrs2, m=12, ef_spatial=12, ef_attribute=16,
                               reverse_edges=True))
    rb = recall_at_k(base.search(qv, ranges, k=10, ef=48)[0], gt)
    ra = recall_at_k(aug.search(qv, ranges, k=10, ef=48)[0], gt)
    assert ra >= rb - 0.01, (rb, ra)


def test_nndescent_build_matches_exact_quality():
    """Paper's construction uses NNDescent; our fixed-iteration variant must
    deliver comparable index quality to the exact-KNN build."""
    from repro.core.construction import build_rnsg
    from repro.index.knn import exact_knn, nndescent, knn_recall
    n, d = 2048, 16
    vecs = make_vectors(n, d, seed=1)
    attrs = make_attrs(n, seed=1)
    order = np.argsort(attrs, kind="stable")
    _, ids_exact = exact_knn(vecs[order], 16)
    _, ids_nnd = nndescent(vecs[order], 16, iters=6)
    assert knn_recall(ids_nnd, ids_exact) > 0.9
    qv = make_vectors(50, d, seed=9)
    ranges, _ = mixed_workload(attrs, 50, seed=4, levels=5)
    gt_r, _ = ground_truth(vecs[order], attrs[order], qv, ranges, 10)
    gt = np.where(gt_r >= 0, order[np.maximum(gt_r, 0)], -1)
    ix_e = RNSGIndex.build(vecs, attrs, m=12, ef_spatial=16, ef_attribute=16,
                           knn_method="exact")
    ix_n = RNSGIndex.build(vecs, attrs, m=12, ef_spatial=16, ef_attribute=16,
                           knn_method="nndescent")
    re_ = recall_at_k(ix_e.search(qv, ranges, k=10, ef=64)[0], gt)
    rn = recall_at_k(ix_n.search(qv, ranges, k=10, ef=64)[0], gt)
    assert rn > re_ - 0.05, (re_, rn)


# ------------------------------------------------------------ exact KNN top-k
@pytest.mark.parametrize("b,n,k", [(7, 512, 32), (5, 4096, 32), (3, 512, 500),
                                   (4, 1024, 1), (6, 2048, 100)])
@pytest.mark.parametrize("ties", [False, True])
def test_topk_smallest_matches_full_row_top_k(b, n, k, ties):
    """The group-minimum top-k of the KNN build returns exactly what
    ``lax.top_k`` over the whole row returns — ids, values and the
    lower-index-first tie order — including massive ties and +inf."""
    import jax
    from repro.index.knn import topk_smallest
    rng = np.random.default_rng(b * n + k)
    d = rng.standard_normal((b, n)).astype(np.float32)
    if ties:
        d = np.round(d * 2) / 2
        d[:, ::7] = np.inf
    want_v, want_i = jax.lax.top_k(-jnp.asarray(d), k)
    got_v, got_i = topk_smallest(jnp.asarray(d), k)
    assert np.array_equal(np.asarray(got_i), np.asarray(want_i))
    assert np.array_equal(np.asarray(got_v), -np.asarray(want_v))
