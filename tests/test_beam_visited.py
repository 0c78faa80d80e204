"""The single-node beam's n-independent visited set: parity with the (n+1,)
bitmap oracle (ids, distances and hops bit-identical, ``ndist`` never
lower), under forced evictions too, and the table's eviction count."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.beam import (_table_insert, _table_lookup, beam_search_batch,
                             visited_table_size)
from repro.core.rfann import RNSGIndex
from repro.data.ann import make_attrs, make_vectors
from repro.kernels.quantize import quantize_corpus
from repro.search import select_entry

from _bitmap_beam import bitmap_beam


@pytest.fixture(scope="module")
def index():
    n, d = 600, 16
    vecs = make_vectors(n, d, seed=0)
    attrs = make_attrs(n, seed=0)
    return RNSGIndex.build(vecs, attrs, m=16, ef_spatial=16, ef_attribute=24)


def _intervals(n, nq, rng):
    """Empty, single-point, full-span, narrow, sub-ef and wide windows."""
    lo = rng.integers(0, n, nq)
    hi = lo + np.concatenate([
        np.full(nq // 6, -3),                          # empty (lo > hi)
        np.zeros(nq // 6, np.int64),                   # single point
        np.full(nq // 6, n),                           # full span
        rng.integers(1, 8, nq // 6),                   # narrow
        rng.integers(8, 60, nq // 6),                  # sub-ef
        rng.integers(n // 4, n, nq - 5 * (nq // 6)),   # wide
    ])
    lo[2 * (nq // 6):3 * (nq // 6)] = 0
    return lo.astype(np.int32), np.clip(hi, -1, n - 1).astype(np.int32)


def _args(ix, nq, seed):
    g = ix.g
    lo, hi = _intervals(g.n, nq, np.random.default_rng(seed))
    lo, hi = jnp.asarray(lo), jnp.asarray(hi)
    entry = select_entry(jnp.asarray(g.rmq), jnp.asarray(g.dist_c), lo, hi,
                         g.n)
    qv = jnp.asarray(make_vectors(nq, g.vecs.shape[1], seed=seed + 1))
    return (jnp.asarray(g.vecs), jnp.asarray(g.nbrs), qv, lo, hi, entry)


def _variant(ix, name, seed):
    if name == "live":
        rng = np.random.default_rng(seed)
        return {"live": jnp.asarray(rng.random(ix.g.n) > 0.3)}
    if name == "int8":
        qc = quantize_corpus(jnp.asarray(ix.g.vecs), "int8")
        return {"quant": (qc.data, qc.scale)}
    return {}


@pytest.mark.parametrize("slots", [0, 16], ids=["served_table", "tiny_table"])
@pytest.mark.parametrize("variant", ["f32", "live", "int8"])
def test_table_matches_bitmap_oracle(index, variant, slots):
    """ids, distances and hops bit-identical to the bitmap oracle; ndist
    never lower.  A 16-slot table forgets most of what it is told, so the
    exactness argument is exercised, not just the common case."""
    args = _args(index, 36, seed=3)
    kw = _variant(index, variant, seed=5)
    for ef in (16, 64):
        want = bitmap_beam(*args, k=10, ef=ef, **kw)
        got = beam_search_batch(*args, k=10, ef=ef, _visited_slots=slots,
                                **kw)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
        np.testing.assert_array_equal(np.asarray(got[2]["hops"]),
                                      np.asarray(want[2]["hops"]))
        nd_got, nd_want = (np.asarray(got[2]["ndist"]),
                           np.asarray(want[2]["ndist"]))
        assert (nd_got >= nd_want).all()
        ev = np.asarray(got[2]["evictions"])
        assert (ev >= 0).all() and (ev <= nd_got).all()
        if slots:
            assert ev.sum() > 0 and (nd_got > nd_want).any()


def test_served_table_keeps_ndist_within_three_percent(index):
    """Fixed routing (every query on the beam) at the table size the search
    picks itself: re-scores add at most 3 % to ndist."""
    g = index.g
    nq = 48
    rng = np.random.default_rng(9)
    lo = rng.integers(0, g.n // 2, nq).astype(np.int32)
    hi = (lo + rng.integers(g.n // 4, g.n // 2, nq)).astype(np.int32)
    lo, hi = jnp.asarray(lo), jnp.asarray(hi)
    entry = select_entry(jnp.asarray(g.rmq), jnp.asarray(g.dist_c), lo, hi,
                         g.n)
    qv = jnp.asarray(make_vectors(nq, g.vecs.shape[1], seed=10))
    args = (jnp.asarray(g.vecs), jnp.asarray(g.nbrs), qv, lo, hi, entry)
    want = bitmap_beam(*args, k=10, ef=64)
    got = beam_search_batch(*args, k=10, ef=64)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    nd_got = float(np.asarray(got[2]["ndist"]).sum())
    nd_want = float(np.asarray(want[2]["ndist"]).sum())
    assert nd_want <= nd_got <= 1.03 * nd_want


def test_table_insert_counts_every_forgotten_id():
    """Ids inserted less ids forgotten is what the table holds, after every
    one of many inserts into a small table (each insert sends distinct ids,
    only those the lookup misses, as the search does; a forgotten id may
    come back)."""
    size = 32
    rng = np.random.default_rng(0)
    table = jnp.full((size + 1,), -1, jnp.int32)
    inserts = lost_total = 0
    for _ in range(40):
        ids = rng.choice(400, 12, replace=False).astype(np.int32)
        ids[rng.random(12) < 0.2] = -1                 # skipped lanes
        ids = jnp.asarray(ids)
        ids = jnp.where(_table_lookup(table, ids, size), -1, ids)
        table, lost = _table_insert(table, ids, size)
        lost_total += int(lost)
        inserts += int((np.asarray(ids) >= 0).sum())
        held = np.asarray(table[:size])
        assert len(set(held[held >= 0].tolist())) == (held >= 0).sum()
        assert inserts - lost_total == (held >= 0).sum()
    assert lost_total > 0


def test_visited_table_size_is_n_independent():
    for ef, m in ((16, 8), (64, 24), (128, 48)):
        for bw in (1, 4):
            s = visited_table_size(ef, m, bw)
            assert s & (s - 1) == 0 and 256 <= s <= (1 << 13)
    # width 1: four slots per expected insert (ef·m), up to the cap
    assert visited_table_size(64, 32) == 1 << 13
    assert visited_table_size(16, 16) == 1024
