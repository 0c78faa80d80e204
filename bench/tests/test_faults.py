"""A whole run of each cell at a tiny size on the CPU (the harness's look
for a chip skipped): sound, it is correct; with the timed path broken
underneath it, ``correct`` comes out false."""
import threading

import numpy as np
import pytest

from bench import harness
from bench.tests._tiny import run_tiny, tiny_cell


@pytest.mark.parametrize(
    "cell", [w["name"] for w in harness.load_spec()["workloads"]])
def test_sound_run_is_correct(cell):
    out = run_tiny(tiny_cell(cell))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 10
    assert "setup_s" in out["metrics"]
    assert list(out)[-1] == "checks"


def _shift_ids(fn):
    def altered(*a, **kw):
        ids, d = fn(*a, **kw)
        return ids.at[:, 0].set(ids[:, 0] + 1), d
    return altered


def test_answer_altered_in_the_scan_kernel(monkeypatch):
    import repro.search.substrate as sub
    monkeypatch.setattr(sub, "range_scan", _shift_ids(sub.range_scan))
    out = run_tiny(tiny_cell("deep96.narrow"))
    assert not out["correct"]
    c = out["checks"]
    assert c["scan_gap"]["value"] > c["scan_gap"]["limit"] \
        or c["foreign_ids"]["value"] > 0


def test_answer_altered_in_the_beam(monkeypatch):
    import repro.search.substrate as sub
    real = sub.beam_search_batch

    def altered(*a, **kw):
        ids, d, st = real(*a, **kw)
        return (ids + 3) % a[0].shape[0], d, st
    monkeypatch.setattr(sub, "beam_search_batch", altered)
    out = run_tiny(tiny_cell("deep96.mixed"))
    assert not out["correct"]
    assert out["checks"]["beam_recall"]["value"] < 0.9


def test_half_of_each_batch_left_out():
    def fault(index, engine):
        real = index.search_ranks

        def half(qv, lo, hi, **kw):
            m = max(len(qv) // 2, 1)
            res = real(qv[:m], lo[:m], hi[:m], **kw)
            q = len(qv)
            ids = np.full((q, res.ids.shape[1]), -1, res.ids.dtype)
            d = np.full((q, res.ids.shape[1]), np.inf, np.float32)
            ids[:m], d[:m] = res.ids, res.dists
            st = {k: np.resize(v, q) for k, v in res.stats.items()
                  if isinstance(v, np.ndarray) and v.ndim == 1}
            return type(res)(ids, d, st)
        index.search_ranks = half
    out = run_tiny(tiny_cell("deep96.narrow"), fault=fault)
    assert not out["correct"]
    assert out["checks"]["scan_short"]["value"] > 0


@pytest.mark.parametrize("cell", ["deep96.mixed", "deep96.narrow"])
def test_answers_that_never_come_are_not_correct(cell):
    def fault(index, engine):
        real = index.search_ranks
        calls = [0]

        def every_third_fails(qv, lo, hi, **kw):
            # the engine's batches only: warm-up calls from the main thread
            if threading.current_thread() is not threading.main_thread():
                calls[0] += 1
                if calls[0] % 3 == 0:
                    raise RuntimeError("injected dispatch failure")
            return real(qv, lo, hi, **kw)
        index.search_ranks = every_third_fails
    out = run_tiny(tiny_cell(cell), fault=fault)
    assert not out["correct"]
    assert out["failed"] > 0
    assert out["checks"]["unanswered"]["value"] == out["failed"]
