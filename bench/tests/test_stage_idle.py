"""Device idle by dispatcher stage: interval arithmetic on synthetic spans,
and the reduction of two traces recorded on a v5e chip — one from before
the stage spans (no ``rnsg.await_batch``) and one that holds them."""
from pathlib import Path

import pytest

from bench import stage_idle as si
from bench import trace_reduce

DATA = Path(__file__).resolve().parents[1] / "testdata"
OLD = DATA / "small.xplane.pb"
STAGES = DATA / "stages.xplane.pb"


def test_innermost_labels_self_time_of_nested_spans():
    spans = [(0, 10, "rnsg.outer"), (2, 4, "rnsg.a"), (6, 7, "rnsg.b"),
             (12, 15, "rnsg.c")]
    assert si.innermost(spans) == [
        (0, 2, "rnsg.outer"), (2, 4, "rnsg.a"), (4, 6, "rnsg.outer"),
        (6, 7, "rnsg.b"), (7, 10, "rnsg.outer"), (12, 15, "rnsg.c")]
    # a child that outlives its parent is clipped to it
    assert si.innermost([(0, 5, "rnsg.p"), (3, 9, "rnsg.q")]) == [
        (0, 3, "rnsg.p"), (3, 5, "rnsg.q")]


def test_idle_goes_to_the_innermost_stage_or_to_no_stage():
    segs = si.innermost([(0, 10, "rnsg.outer"), (2, 4, "rnsg.a"),
                         (12, 15, "rnsg.c")])
    got = si.attribute([(1, 3), (9, 13), (16, 18)], segs)
    assert got == {"rnsg.outer": 2, "rnsg.a": 1, "rnsg.c": 1,
                   si.NO_STAGE: 4}


def test_reduce_picks_the_dispatcher_line_and_splits_waiting_from_host():
    dispatcher = [(0, 40, "rnsg.await_batch"), (40, 45, "rnsg.plan"),
                  (45, 50, "rnsg.scan_dispatch"),
                  (50, 60, "rnsg.beam_dispatch"),
                  (60, 90, "rnsg.scan_block"), (90, 100, "rnsg.assemble")]
    resolver = [(30, 35, "rnsg.resolve"), (36, 37, "rnsg.scan_dispatch")]
    busy = [(62, 85), (70, 80)]                  # the kernel, overlapping
    res = si.reduce((0, 100), busy, [resolver, dispatcher])
    assert res.window_s == pytest.approx(100e-9)
    assert res.idle_s == pytest.approx({
        "rnsg.await_batch": 40e-9, "rnsg.plan": 5e-9,
        "rnsg.scan_dispatch": 5e-9, "rnsg.beam_dispatch": 10e-9,
        "rnsg.scan_block": 7e-9, "rnsg.assemble": 10e-9})
    assert res.host_bound_s == pytest.approx(37e-9)
    assert res.host_bound_share == pytest.approx(0.37)
    assert si.reduce((0, 100), busy, [resolver[:1]]) is None


def test_reduce_counts_idle_outside_every_stage():
    res = si.reduce((0, 100), [(10, 20)],
                    [[(0, 30, "rnsg.beam_dispatch"), (50, 60, "rnsg.plan")]])
    assert res.idle_s == pytest.approx({"rnsg.beam_dispatch": 20e-9,
                                        "rnsg.plan": 10e-9,
                                        si.NO_STAGE: 60e-9})
    assert res.host_bound_s == pytest.approx(90e-9)


@pytest.mark.skipif(not OLD.exists(), reason="no recorded trace")
def test_trace_without_stage_spans_counts_all_idle_as_host_bound():
    red = trace_reduce.reduce_trace(OLD)
    res = si.read(OLD)
    idle = red.window_s - red.busy_s
    assert sum(res.idle_s.values()) == pytest.approx(idle, rel=1e-6)
    assert si.WAIT_STAGE not in res.idle_s
    assert res.host_bound_share == pytest.approx(red.idle_share, rel=1e-6)


@pytest.mark.skipif(not STAGES.exists(), reason="no recorded trace")
def test_recorded_stage_trace_puts_idle_on_named_stages():
    """A trimmed traced window of ``deep96.narrow`` on one v5e: the
    dispatcher's stages are all there, nearly all idle lies under one of
    them, and host-bound idle is the idle less the wait for batches."""
    red = trace_reduce.reduce_trace(STAGES)
    res = si.read(STAGES)
    idle = red.window_s - red.busy_s
    assert sum(res.idle_s.values()) == pytest.approx(idle, rel=1e-6)
    for name in ("await_batch", "plan", "scan_prep", "scan_dispatch",
                 "scan_block", "assemble"):
        assert f"rnsg.{name}" in res.idle_s, sorted(res.idle_s)
    assert res.idle_s.get(si.NO_STAGE, 0.0) < 0.05 * idle
    assert res.host_bound_s == pytest.approx(
        idle - res.idle_s[si.WAIT_STAGE], rel=1e-6)
    assert 0 < res.host_bound_share < red.idle_share
