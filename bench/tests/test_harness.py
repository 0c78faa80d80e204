"""The harness finds every cell's files by name, and ``BENCHMARK.json`` keeps
to the shape the benchmark's contract gives it."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness, traffic
from bench.tests._tiny import run_tiny, tiny_cell

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
    dep = harness.deployment(c.kind)
    assert all(callable(getattr(dep, f)) for f in ("build", "engine",
                                                    "warm_up"))
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
def test_traced_run_reads_every_per_layer_metric(cell):
    """A traced tiny run on the CPU, with the device trace of the window
    replaced by the one recorded on a v5e chip (the CPU's has no device
    operations): every per-layer metric of the cell is read, and the
    device times and breakdown reach the result line."""
    c = tiny_cell(cell, rate=200.0) if "rate" in tiny_cell(cell).mix \
        else tiny_cell(cell)
    out = run_tiny(c, 2**31 + 11, 1.0, trace=True)
    assert out["correct"], out["checks"]
    want = {m["name"] for m in harness.cell_metrics(SPEC, cell, "per_layer")}
    assert set(out["metrics"]) == want
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]


def test_a_configuration_without_layout_is_single():
    cfgs = [harness.resolve_cell(SPEC, w["name"]) for w in SPEC["workloads"]]
    bare = [c for c in cfgs if "layout" not in c.cfg]
    assert bare and all(c.kind == "single" for c in bare)
    dep = harness.deployment("single")
    assert Path(dep.__file__) == harness.BENCH / "deploy" / "single.py"


PROBE = """
from pathlib import Path
from bench.deploy import single

CALLS = Path(__file__).with_suffix(".calls")


def _called(name):
    with CALLS.open("a") as f:
        f.write(name + "\\n")


def build(cell, corpus, devices):
    _called("build")
    return single.build(cell, corpus, devices)


def engine(cell, index):
    _called("engine")
    return single.engine(cell, index)


def warm_up(cell, index, tr):
    _called("warm_up")
    single.warm_up(cell, index, tr)
"""


def test_a_new_kind_needs_only_its_file_and_a_configuration(tmp_path):
    """A kind written only under another root is found by its name in the
    configuration's ``layout``, and a tiny cell runs through it correct."""
    kind = tmp_path / "bench" / "deploy" / "probe.py"
    kind.parent.mkdir(parents=True)
    kind.write_text(PROBE)
    c = tiny_cell("deep96.mixed")
    c.cfg = dict(c.cfg, layout={"kind": "probe"})
    c.root = tmp_path
    out = run_tiny(c)
    assert out["correct"], out["checks"]
    assert kind.with_suffix(".calls").read_text().split() == [
        "build", "engine", "warm_up"]


@pytest.mark.parametrize("trace", [False, True])
def test_a_four_chip_cell_runs_in_a_child_process(trace):
    """A tiny ``deep96.mixed`` that asks for four chips runs on four CPU
    devices in a child process (the tests' own has one): correct, with
    every metric of its kind read."""
    c = tiny_cell("deep96.mixed")
    c.cfg = dict(c.cfg, layout={"kind": "single"})
    c.entry = dict(c.entry, chips=4)
    out = run_tiny(c, 2**31 + 13, 1.0, trace=trace)
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4
    key = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in harness.cell_metrics(SPEC, c.name, key)}
    assert set(out["metrics"]) == want


def test_a_cell_with_more_chips_than_devices_is_refused():
    import jax
    c = tiny_cell("deep96.mixed")
    c.entry = dict(c.entry, chips=len(jax.devices()) + 1)
    with pytest.raises(harness.NoChip):
        harness.set_up(c, 1, 1.0, require_chip=False)
