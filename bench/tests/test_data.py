"""Generators are deterministic per seed, and the deep96 generator keeps
beam-routed recall at the paper's ef = 64."""
import numpy as np
import pytest

from bench import data, harness, reference, traffic


def _cfg(name, n=4096):
    return dict(harness.resolve_cell(harness.load_spec(), name).cfg, n=n)


@pytest.mark.parametrize("n", [512, 5000])
def test_corpus_is_deterministic_per_seed(n):
    cfg = _cfg("deep96.mixed", n)
    a = data.make_corpus(cfg, 2**31 + 17, 64)
    b = data.make_corpus(cfg, 2**31 + 17, 64)
    c = data.make_corpus(cfg, 2**31 + 18, 64)
    for f in ("vecs", "attrs", "queries"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.vecs, c.vecs)
    # more queries drawn never moves the corpus
    d = data.make_corpus(cfg, 2**31 + 17, 5000)
    np.testing.assert_array_equal(a.vecs, d.vecs)
    np.testing.assert_array_equal(a.queries, d.queries[:64])


def test_negative_and_huge_seeds_are_valid():
    for s in (-1, 0, 2**31 + 5, 2**70):
        data.rng(s, 1).random()


def test_generator_shapes_and_latent_rank():
    c = data.make_corpus(_cfg("deep96.mixed", 512), 3, 8)
    assert c.vecs.shape == (512, 96) and c.vecs.dtype == np.float32
    assert c.queries.shape == (8, 96)
    # the rows span only the latent dimensions
    assert np.linalg.matrix_rank(c.vecs.astype(np.float64), tol=1e-3) == 8
    np.testing.assert_array_equal(np.sort(c.attrs), np.arange(512))


def test_stratified_counts_and_gaps():
    c = data.stratified_counts([1 / 7, 1 / 6, 1 / 5, 1 / 4, 1 / 3, 1 / 2, 1],
                               1000)
    assert c.sum() == 1000 and (np.diff(c) > 0).all()
    g1 = data.exponential_gaps(1000, 50.0, data.rng(1, 1))
    g2 = data.exponential_gaps(1000, 50.0, data.rng(2, 1))
    np.testing.assert_allclose(np.sort(g1), np.sort(g2))
    assert abs(g1.mean() - 1 / 50.0) < 0.05 / 50.0


def test_deep96_recall_at_ef64_holds_at_2_14():
    from repro.core.rfann import RNSGIndex
    cfg = _cfg("deep96.mixed", 1 << 14)
    c = data.make_corpus(cfg, 11, 120)
    idx = RNSGIndex.build(c.vecs, c.attrs, **cfg["build"])
    srt = c.attrs_sorted
    r = data.rng(11, 9)
    rg = np.concatenate([data.rank_window(srt, 2.0 ** -lv, r, 40)
                         for lv in (0, 1, 2)])
    ids, dists, st = idx.search(c.queries, rg, k=cfg["k"], ef=cfg["ef"],
                                plan="beam")
    ref = reference.HostReference(c.vecs, c.attrs, np.arange(cfg["n"]))
    nums = reference.compare(
        ref, c.queries, rg, ids, dists, np.asarray(st["strategy"]),
        k=cfg["k"], beam_floor=cfg["guarantees"]["beam_routed_recall_floor"],
        gap_limit=cfg["guarantees"]["scan_gap_limit"])
    assert nums["beam_queries"]["value"] == 120
    assert nums["beam_recall"]["value"] >= 0.9, nums
    assert reference.passes(nums)


def test_schedule_levels_and_gaps_per_block():
    mix = harness.resolve_cell(harness.load_spec(), "deep96.narrow").mix
    a = traffic.make_schedule(mix, 5, 3000)
    b = traffic.make_schedule(mix, 2**31 + 6, 3000)
    for s in (a, b):
        assert (np.diff(s.due) > 0).all()
        assert set(s.level.tolist()) == {7, 8, 9}
    for blk in range(3):
        sl = slice(blk * 1000, (blk + 1) * 1000)
        assert sorted(a.level[sl].tolist()) == sorted(b.level[sl].tolist())
        np.testing.assert_allclose(np.sort(np.diff(a.due[sl])),
                                   np.sort(np.diff(b.due[sl])), rtol=0.5,
                                   atol=0.01)
    assert not np.array_equal(a.level, b.level)
    # the mean gap is the mix's rate
    assert abs(a.due[-1] / 3000 - 1 / mix["rate"]) < 0.05 / mix["rate"]


def test_closed_schedule_draws_its_pool():
    mix = harness.resolve_cell(harness.load_spec(), "deep96.mixed").mix
    s = traffic.make_schedule(mix, 9, traffic.ops_needed(mix, 20))
    assert len(s) == mix["pool"] and not s.due.any()
    counts = np.bincount(s.level[:1000], minlength=10)
    assert (counts == 100).all()
