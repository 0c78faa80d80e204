"""``visited_evict_share`` reads the beam's visited-table counters over the
window only, is a finite share of the inserts, and reads nothing from a
program that has no such counters."""
import math

from bench import harness

SPEC = harness.load_spec()
READ = harness.reader("visited_evict_share")


def _ctx(before, after):
    cell = harness.resolve_cell(SPEC, "deep96.mixed")
    return harness.Context(cell=cell, measured=None, setup_s=0.0,
                           before={"counters": before},
                           after={"counters": after})


def test_share_of_the_window_inserts():
    got = READ(_ctx({"beam_visited_inserts_total": 500,
                     "beam_visited_evictions_total": 40},
                    {"beam_visited_inserts_total": 2500,
                     "beam_visited_evictions_total": 67}))
    assert math.isfinite(got) and got == 100.0 * 27 / 2000


def test_no_counters_or_no_beam_reads_nothing():
    # a program without the counters, then a window in which no beam ran
    assert READ(_ctx({}, {})) is None
    assert READ(_ctx({"beam_visited_inserts_total": 9},
                     {"beam_visited_inserts_total": 9})) is None


def test_the_cells_that_list_it_report_qps():
    m = next(m for m in SPEC["per_layer"] if m["name"] == "visited_evict_share")
    for cell in m["workloads"]:
        names = {e["name"] for e in harness.cell_metrics(SPEC, cell,
                                                         "end_to_end")}
        assert m["moves"] in names
