"""The harness finds every cell's files by name, and ``BENCHMARK.json`` keeps
to the shape the benchmark's contract gives it."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench import harness, traffic

SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_by_name(cell):
    c = harness.resolve_cell(SPEC, cell)
    assert c.cfg["name"] == c.entry["config"]
    assert c.mix["loop"] in ("open", "closed")
    e2e = harness.cell_metrics(SPEC, cell, "end_to_end")
    layer = harness.cell_metrics(SPEC, cell, "per_layer")
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    assert layer
    for m in e2e + layer:
        assert callable(harness.reader(m["name"]))
    for m in layer:
        assert m["moves"] in names


def test_metric_readers_exist_for_every_metric():
    for key in ("end_to_end", "per_layer"):
        for m in SPEC[key]:
            assert callable(harness.reader(m["name"]))


def test_spec_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    cfg_names = {c["name"] for c in SPEC["configs"]}
    used = {w["config"] for w in SPEC["workloads"]}
    assert cfg_names == used
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and len(c["source"]) <= 200
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    pairs = set()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        traffic.load_mix(harness.BENCH / "traffic" / f"{w['traffic']}.json")
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        harness.resolve_cell(SPEC, "no.such.cell")


def test_run_refuses_without_a_chip_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(harness.BENCH / "run.py"),
                        "--workload", "deep96.narrow", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=120,
                       cwd=harness.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_traced_run_reads_every_per_layer_metric(cell, monkeypatch):
    """A traced tiny run on the CPU, with the device trace of the window
    replaced by the one recorded on a v5e chip (the CPU's has no device
    operations): every per-layer metric of the cell is read, and the
    device times and breakdown reach the result line."""
    from bench import trace_reduce
    from bench.tests._tiny import tiny_cell
    recorded = harness.BENCH / "testdata" / "small.xplane.pb"
    monkeypatch.setattr(trace_reduce, "find_trace", lambda d: recorded)
    monkeypatch.setattr(harness.Context, "peaks", lambda self: {
        "hbm_bytes_per_s": 8.19e11, "bf16_flops_per_s": 1.97e14})
    c = tiny_cell(cell, rate=200.0) if "rate" in tiny_cell(cell).mix \
        else tiny_cell(cell)
    out = harness.run_cell(c.name, 2**31 + 11, 1.0, True, t_process=0.0,
                           require_chip=False, cell=c)
    assert out["correct"], out["checks"]
    want = {m["name"] for m in harness.cell_metrics(SPEC, cell, "per_layer")}
    assert set(out["metrics"]) == want
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]
