"""Batched beam expansion (``beam_width > 1``): parity with the
single-expansion path, bounded-merge/hashed-visited exactness, per-query
state independent of the corpus size, and the blocked gather kernels."""
import numpy as np
import pytest
from _hyp import given, settings, st

import jax
import jax.numpy as jnp

from repro.core.beam import beam_search_batch, visited_table_size
from repro.core.rfann import RNSGIndex
from repro.data.ann import make_attrs, make_vectors, selectivity_ranges
from repro.search import select_entry


@pytest.fixture(scope="module")
def small_index():
    n, d = 600, 16
    vecs = make_vectors(n, d, seed=0)
    attrs = make_attrs(n, seed=0)
    return vecs, attrs, RNSGIndex.build(vecs, attrs, m=16, ef_spatial=16,
                                        ef_attribute=24)


def _run(ix, qv, lo, hi, *, k=10, ef=64, bw=1, use_kernel=False):
    g = ix.g
    loj = jnp.asarray(np.asarray(lo, np.int32))
    hij = jnp.asarray(np.asarray(hi, np.int32))
    entry = select_entry(jnp.asarray(g.rmq), jnp.asarray(g.dist_c),
                         loj, hij, g.n)
    return beam_search_batch(jnp.asarray(g.vecs), jnp.asarray(g.nbrs),
                             jnp.asarray(qv), loj, hij, entry, k=k, ef=ef,
                             beam_width=bw, use_kernel=use_kernel)


def _interval_mix(n, nq, rng):
    """Narrow / wide / empty / sub-ef intervals in one batch."""
    lo = rng.integers(0, n, nq).astype(np.int64)
    width = np.concatenate([
        rng.integers(1, 8, nq // 4),              # narrow
        rng.integers(n // 2, n, nq // 4),         # wide
        np.full(nq // 4, -3),                     # empty (lo > hi)
        rng.integers(8, 60, nq - 3 * (nq // 4)),  # sub-ef
    ])
    hi = np.clip(lo + width[:nq], -1, n - 1)
    return lo, hi


def _id_sets_equal(a, b):
    assert a.shape == b.shape
    for q in range(a.shape[0]):
        sa = set(a[q][a[q] >= 0].tolist())
        sb = set(b[q][b[q] >= 0].tolist())
        if sa != sb:
            return False, (q, sorted(sa), sorted(sb))
    return True, None


# --------------------------------------------------------------- seeded sweep
@pytest.mark.parametrize("bw", [2, 3, 4, 8])
@pytest.mark.parametrize("ef_mode", ["exhaustive", "sub"])
def test_batched_matches_legacy(small_index, bw, ef_mode):
    """Bounded-merge + hashed-visited batched beam returns identical id sets
    to the beam_width=1 legacy beam across narrow/wide/empty/sub-ef
    intervals, in the two regimes where equality is *guaranteed* (not just
    empirical): ``ef >= n`` makes every interval exhaustive over its
    in-range component, and at ``ef=64`` any interval with at most ``ef``
    in-range nodes keeps the pool under-full, so nothing is ever evicted
    and both widths expand the full reachable set.  (A wide interval at
    sub-exhaustive ef may legitimately explore a different frontier — that
    is exactly why ``beam_width`` is part of the cache key.)"""
    vecs, attrs, ix = small_index
    n = ix.g.n
    nq = 24
    rng = np.random.default_rng(7 + bw)
    qv = make_vectors(nq, 16, seed=5)
    ef = n if ef_mode == "exhaustive" else 64
    lo, hi = _interval_mix(n, nq, rng)
    if ef_mode == "sub":                    # keep only guaranteed intervals
        hi = np.minimum(hi, lo + ef - 1)
    base = _run(ix, qv, lo, hi, ef=ef, bw=1)
    got = _run(ix, qv, lo, hi, ef=ef, bw=bw)
    ok, why = _id_sets_equal(np.asarray(base[0]), np.asarray(got[0]))
    assert ok, why
    # batched iterations ≈ expansions / B
    assert float(np.asarray(got[2]["hops"]).mean()) < \
        float(np.asarray(base[2]["hops"]).mean())


_PROP_IX = {}


def _prop_index(n=220, d=8):
    if "ix" not in _PROP_IX:                  # one build for every example
        vecs = make_vectors(n, d, seed=3)
        attrs = make_attrs(n, seed=3)
        _PROP_IX["ix"] = RNSGIndex.build(vecs, attrs, m=8, ef_spatial=8,
                                         ef_attribute=12)
    return _PROP_IX["ix"]


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 8), st.integers(0, 2**31 - 1))
def test_batched_matches_legacy_property(bw, seed):
    """Hypothesis sweep (exhaustive ef): any interval mix, any width."""
    n, d = 220, 8
    ix = _prop_index(n, d)
    rng = np.random.default_rng(seed)
    nq = 8
    qv = make_vectors(nq, d, seed=seed % 1000)
    lo, hi = _interval_mix(n, nq, rng)
    base = _run(ix, qv, lo, hi, k=5, ef=n, bw=1)
    got = _run(ix, qv, lo, hi, k=5, ef=n, bw=bw)
    ok, why = _id_sets_equal(np.asarray(base[0]), np.asarray(got[0]))
    assert ok, why


def test_batched_kernel_path_matches_jnp(small_index):
    """interpret-mode blocked gather/top-k kernels inside the batched beam
    reproduce the jnp gather path exactly."""
    vecs, attrs, ix = small_index
    n = ix.g.n
    nq = 12
    rng = np.random.default_rng(11)
    qv = make_vectors(nq, 16, seed=9)
    lo, hi = _interval_mix(n, nq, rng)
    a = _run(ix, qv, lo, hi, ef=48, bw=4, use_kernel=False)
    b = _run(ix, qv, lo, hi, ef=48, bw=4, use_kernel=True)
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert np.allclose(np.asarray(a[1]), np.asarray(b[1]),
                       rtol=1e-4, atol=1e-4, equal_nan=True)


def test_beam_width_beyond_ef_is_clamped(small_index):
    """A width larger than the pool (e.g. --beam-width 128 at ef=8) clamps
    to ef instead of dying in a reshape deep inside the traced body."""
    vecs, attrs, ix = small_index
    n = ix.g.n
    nq = 6
    rng = np.random.default_rng(13)
    qv = make_vectors(nq, 16, seed=17)
    lo, hi = _interval_mix(n, nq, rng)
    explicit = _run(ix, qv, lo, hi, k=5, ef=8, bw=8)
    clamped = _run(ix, qv, lo, hi, k=5, ef=8, bw=16)    # clamps to 8
    assert np.array_equal(np.asarray(explicit[0]), np.asarray(clamped[0]))
    assert np.asarray(clamped[0]).shape == (nq, 5)


# ----------------------------------------------------- state is n-independent
def test_visited_state_independent_of_corpus_size():
    """Acceptance: neither width carries a (Q, n+1) visited array — both
    visited tables are sized by (ef, m) only.  Checked
    structurally: no traced jaxpr contains an (n+1)-extent value."""
    n, d, m, nq = 5000, 8, 12, 3
    vecs = jnp.zeros((n, d), jnp.float32)
    nbrs = jnp.zeros((n, m), jnp.int32)
    qv = jnp.zeros((nq, d), jnp.float32)
    lo = jnp.zeros((nq,), jnp.int32)
    hi = jnp.full((nq,), n - 1, jnp.int32)
    entry = jnp.zeros((nq,), jnp.int32)

    def trace(bw):
        return repr(jax.make_jaxpr(
            lambda *a: beam_search_batch(*a, k=5, ef=32, beam_width=bw))(
                vecs, nbrs, qv, lo, hi, entry))

    for bw in (1, 4):
        text = trace(bw)
        assert f"{n + 1}" not in text and f"{nq * (n + 1)}" not in text, bw
        assert f"{visited_table_size(32, m, bw) + 1}" in text
    for ef, mm in ((16, 8), (64, 24), (128, 48)):
        s = visited_table_size(ef, mm, 4)
        assert s & (s - 1) == 0 and 256 <= s <= (1 << 13)


# ------------------------------------------------------- substrate-level knob
def test_substrate_beam_width_parity(small_index):
    """RNSGIndex.search(beam_width=...) is exact for every plan at
    exhaustive ef."""
    vecs, attrs, ix = small_index
    nq = 10
    qv = make_vectors(nq, 16, seed=21)
    ranges = selectivity_ranges(attrs, nq, 0.2, seed=4)
    n = ix.g.n
    base = ix.search(qv, ranges, k=8, ef=n, plan="graph")
    for plan in ("graph", "auto", "beam"):
        got = ix.search(qv, ranges, k=8, ef=n, plan=plan, beam_width=4)
        ok, why = _id_sets_equal(base.ids, got.ids)
        assert ok, (plan, why)


@pytest.mark.parametrize("bw", [1, 4])
def test_beam_phases_are_named_in_the_hlo(bw):
    """The beam program's phases carry ``beam.<phase>`` named scopes, which
    reach the lowered program's op metadata (and so a device profile)."""
    import re
    n, d, m, q = 256, 8, 8, 4

    def fn(v, nb, qv, lo, hi, e):
        return beam_search_batch(v, nb, qv, lo, hi, e, k=5, ef=16,
                                 beam_width=bw)
    low = jax.jit(fn).lower(
        jnp.zeros((n, d)), jnp.zeros((n, m), jnp.int32), jnp.zeros((q, d)),
        jnp.zeros(q, jnp.int32), jnp.zeros(q, jnp.int32),
        jnp.zeros(q, jnp.int32))
    text = low.compile().as_text()
    found = set(re.findall(r"beam\.(expand|visited|merge|finish)\b", text))
    assert found == {"expand", "visited", "merge", "finish"}, found
