"""The trace reduction: interval arithmetic on synthetic events, and the
whole reduction on a small trace recorded on a v5e chip."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr

TRACE = Path(__file__).resolve().parents[1] / "testdata" / "small.xplane.pb"


def test_union_clip_and_gaps():
    iv = tr.union([(5, 7), (0, 2), (1, 3), (6, 9), (10, 11)])
    assert iv == [(0, 3), (5, 9), (10, 11)]
    assert tr.clip(iv, 2, 10.5) == [(2, 3), (5, 9), (10, 10.5)]
    assert tr.gaps(tr.clip(iv, 2, 12), 2, 12) == [(3, 5), (9, 10), (11, 12)]


def test_self_time_subtracts_enclosed_ops():
    ops = [(0, 10, "while"), (1, 2, "a"), (4, 3, "b"), (12, 2, "c")]
    own = {name: t for _, t, name in tr._self_times(ops, 0, 100)}
    assert own == {"while": 5, "a": 2, "b": 3, "c": 2}
    # clipped to the window
    own = {name: t for _, t, name in tr._self_times(ops, 5, 13)}
    assert own == {"while": 3, "a": 0, "b": 2, "c": 1}


def test_idle_is_named_after_the_host_span_that_overlaps_it_most():
    host = [(0, 100, "bench.search_ranks"), (40, 45, "rnsg.scan_dispatch"),
            (60, 90, "bench.rank_range")]
    # a gap inside a nested span goes to the innermost one
    out = dict(tr._attribute([(41, 44), (62, 70), (50, 95)], host))
    assert out == {"rnsg.scan_dispatch": 3, "bench.rank_range": 8,
                   "bench.search_ranks": 45}
    assert dict(tr._attribute([(200, 210)], host)) == {"no host span": 10}


@pytest.mark.skipif(not TRACE.exists(), reason="no recorded trace")
def test_reduction_of_a_recorded_trace():
    red = tr.reduce_trace(TRACE)
    assert red.devices == 1
    assert 0 < red.busy_s <= red.window_s
    assert 0 <= red.idle_share < 1
    scan = red.kernel_s("jit_range_scan_pallas")
    beam = red.kernel_s("jit_beam_search_batch")
    assert scan and beam and scan + beam <= red.busy_s * 1.0001
    assert sum(v for _, v in red.top_ops) <= red.busy_s * 1.0001
    idle = sum(v for _, v in red.idle_gaps)
    assert idle == pytest.approx(red.window_s - red.busy_s, rel=1e-6,
                                 abs=1e-9)
    assert all(not n.startswith("%") or "/" in n for n, _ in red.top_ops)
