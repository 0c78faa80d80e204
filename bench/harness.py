"""One run of one cell: set-up, warm-up, the measured window, the check
against the plain reference, and the metrics of the cell.

Everything cell-specific is found by name: the cell in ``BENCHMARK.json``
names its configuration (``bench/configs/<config>.json``) and its traffic
mix (``bench/traffic/<mix>.json``); the configuration's ``layout.kind``
(``single`` where it has none) names the deployment that builds, serves and
warms up its index (``bench/deploy/<kind>.py``); each metric is read by
``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, List, Optional

import numpy as np

from bench import data, load, reference, traffic

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
STATE = BENCH / ".state"            # the traced run's profile
CHECK_MAX = 3000                    # answers compared per run, at most
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


class NoChip(RuntimeError):
    """The run cannot be measured here: no accelerator, or too few."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------- lookup
def load_spec(path: Path = ROOT / "BENCHMARK.json") -> dict:
    return json.loads(Path(path).read_text())


@dataclass
class Cell:
    name: str
    entry: dict                 # the workloads entry
    cfg: dict
    mix: dict
    root: Path = ROOT           # where its configuration and deployment lie

    @property
    def kind(self) -> str:
        """The deployment's kind: ``bench/deploy/<kind>.py``."""
        return self.cfg.get("layout", {}).get("kind", "single")


def resolve_cell(spec: dict, name: str, root: Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = json.loads((root / configs[entry["config"]]["file"]).read_text())
    mix = traffic.load_mix(root / "bench" / "traffic"
                           / f"{entry['traffic']}.json")
    return Cell(name, entry, cfg, mix, root)


def cell_metrics(spec: dict, cell: str, key: str) -> List[dict]:
    """The metrics of ``spec[key]`` that this cell reports."""
    return [m for m in spec[key]
            if cell in m.get("workloads", [w["name"]
                                           for w in spec["workloads"]])]


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, root: Path = ROOT) -> Callable:
    """``bench/metrics/<name>.py``'s ``read(ctx)``."""
    return _load(root / "bench" / "metrics" / f"{name}.py",
                 f"bench_metric_{name}").read


def deployment(kind: str, root: Path = ROOT):
    """``bench/deploy/<kind>.py``: its ``build(cell, corpus, devices)``
    returns the index on the cell's chips, ``engine(cell, index)`` the
    engine that serves it, and ``warm_up(cell, index, traffic)`` compiles
    every shape the traffic can reach."""
    return _load(root / "bench" / "deploy" / f"{kind}.py",
                 f"bench_deploy_{kind}")


# ------------------------------------------------------------ context
@dataclass
class Context:
    """What a metric reader may read."""
    cell: Cell
    measured: load.Measured
    setup_s: float
    before: dict                # engine metrics snapshot, window opens
    after: dict                 # ... and after its last answer
    trace: object = None        # trace_reduce.Reduction (traced runs)
    device_kind: str = ""
    scan_rows: int = 0          # rows in range of scan-routed queries

    def counter(self, name: str) -> Optional[float]:
        a = self.after["counters"].get(name)
        if a is None:
            return None
        return a - self.before["counters"].get(name, 0)

    def hist(self, name: str):
        """(sum, count) observed in the window, or ``None``."""
        a = self.after["histograms"].get(name)
        if a is None:
            return None
        b = self.before["histograms"].get(name, {"sum": 0.0, "count": 0})
        return a["sum"] - b["sum"], a["count"] - b["count"]

    def peaks(self) -> dict:
        table = json.loads((BENCH / "peaks.json").read_text())["kinds"]
        if self.device_kind not in table:
            raise KeyError(f"no peaks for device kind {self.device_kind!r}")
        return table[self.device_kind]


# ------------------------------------------------------------ the run
class FullCollections:
    """Pauses of the cyclic collector's full (generation 2) collections,
    read off ``gc.callbacks``: the collector runs as in any deployment, and
    the log says when it stalled the window."""

    def __init__(self):
        self.pauses: List[float] = []
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t)

    def close(self):
        gc.callbacks.remove(self._on)


class CompileCounter:
    """Counts compilations (and persistent-cache loads) JAX reports."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.count += 1


def build(cell: Cell, corpus: data.Corpus):
    """The cell's index alone, built by its deployment on its chips, for
    tools that read the index without serving it."""
    import jax
    devices = jax.devices()[:int(cell.entry["chips"])]
    return deployment(cell.kind, cell.root).build(cell, corpus, devices)


def annotate_calls(index) -> None:
    """Host spans around the engine's calls into the index (traced runs
    only), so device idle gaps can be named after them."""
    import jax

    def wrap(fn, name):
        def call(*a, **kw):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **kw)
        return call
    for name in ("rank_range", "search_ranks", "search"):
        fn = getattr(index, name, None)
        if fn is not None:
            setattr(index, name, wrap(fn, f"bench.{name}"))


@dataclass
class Served:
    """A cell set up and warmed, ready for its window."""
    cell: Cell
    corpus: data.Corpus
    index: object
    engine: object
    traffic: load.Traffic
    sched: traffic.Schedule
    compiles: CompileCounter
    devs: list


def set_up(cell: Cell, seed: int, seconds: float, *,
           require_chip: bool = True,
           fault: Optional[Callable] = None) -> Served:
    """Check the devices, draw the data, then build, start the engine and
    warm up through the cell's deployment.  ``fault`` (tests only) is called
    with the built index and engine, to break the timed path underneath the
    harness."""
    cfg, mix = cell.cfg, cell.mix
    import jax
    devs = jax.devices()
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if require_chip and dev.platform != "tpu":
        raise NoChip(f"no TPU found (platform {dev.platform!r})")
    chips = int(cell.entry["chips"])
    if len(devs) < chips:
        raise NoChip(f"{chips} chips asked, {len(devs)} found")
    if dev.platform == "tpu":
        # every program is cached, so only a checkout's first run compiles
        from repro.runtime.compile_cache import enable_compile_cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        log(f"compile cache: {enable_compile_cache()}")
    compiles = CompileCounter()
    n_ops = traffic.ops_needed(mix, seconds)
    t = time.perf_counter()
    corpus = data.make_corpus(cfg, seed, n_ops)
    log(f"data: n={cfg['n']} d={cfg['d']} queries={n_ops} in "
        f"{time.perf_counter() - t:.3f}s")
    deploy = deployment(cell.kind, cell.root)
    t = time.perf_counter()
    index = deploy.build(cell, corpus, devs[:chips])
    log(f"build: {cell.kind}, {time.perf_counter() - t:.3f}s")
    engine = deploy.engine(cell, index)
    tr = load.Traffic(mix, corpus, seed, cfg["k"])
    sched = traffic.make_schedule(mix, seed, n_ops)
    tr.prepare(sched)
    if fault is not None:
        fault(index, engine)
    t = time.perf_counter()
    deploy.warm_up(cell, index, tr)
    log(f"warm-up: {time.perf_counter() - t:.3f}s, "
        f"{compiles.count} compiles so far")
    return Served(cell, corpus, index, engine, tr, sched, compiles, devs)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, require_chip: bool = True,
             spec: Optional[dict] = None, cell: Optional[Cell] = None,
             fault: Optional[Callable] = None) -> dict:
    """One run; returns the result object the command prints last."""
    spec = spec or load_spec()
    cell = cell or resolve_cell(spec, workload)
    cfg, mix = cell.cfg, cell.mix
    sv = set_up(cell, seed, seconds, require_chip=require_chip, fault=fault)
    import jax
    engine, tr, sched, compiles = sv.engine, sv.traffic, sv.sched, sv.compiles
    dev = sv.devs[0]
    tdir = STATE / "trace"
    if trace:
        annotate_calls(sv.index)
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
    window_span = contextlib.ExitStack()

    def open_window():
        if trace:
            window_span.enter_context(
                jax.profiler.TraceAnnotation("bench.window"))

    # --------------------------------------------------------- window
    gc.collect()                # set-up's garbage, not the window's
    collections = FullCollections()
    settle = float(mix["settle_s"])
    if mix["loop"] == "closed":
        load.run_closed(engine, tr, sched, int(mix["clients"]), settle)
        if trace:
            jax.profiler.start_trace(str(tdir), profiler_options=opts)
        before, c0 = engine.metrics(), compiles.count
        open_window()
        ses = load.run_closed(engine, tr, sched, int(mix["clients"]),
                              seconds, first_op=len(sched) // 2)
        window_span.close()
    else:
        if trace:
            jax.profiler.start_trace(str(tdir), profiler_options=opts)
        snap = {}

        def at_open():
            snap["before"], snap["c0"] = engine.metrics(), compiles.count
            open_window()
        ses = load.run_open(engine, tr, sched, time.perf_counter() + 0.05,
                            settle, seconds, on_open=at_open)
        window_span.close()
        before, c0 = snap["before"], snap["c0"]
    after = engine.metrics()
    collections.close()
    in_window = compiles.count - c0
    if trace:
        jax.profiler.stop_trace()
    setup_s = ses.start - t_process
    chips = sv.devs[:int(cell.entry["chips"])]
    peak = int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in chips))
    engine.close()
    corpus = sv.corpus
    sv = engine = None
    gc.collect()
    log(f"window: {len(ses)} queries sent, {in_window} compiles inside it, "
        f"peak HBM {peak} bytes")
    log(f"gc: {len(collections.pauses)} full collections from settle to the "
        f"window's last answer, longest "
        f"{max(collections.pauses, default=0.0) * 1e3:.3f} ms, "
        f"{len(gc.get_objects())} objects tracked")
    for e in ses.errors:
        log(f"failed request: {e}")
    if mix["loop"] == "open" and len(ses):
        lag = ses.sent - ses.due
        worst = int(np.argmax(lag))
        log(f"generator lag: max {lag.max() * 1e3:.3f} ms at "
            f"{ses.due[worst] - ses.start:.3f}s into the window, "
            f"mean {lag.mean() * 1e3:.3f} ms, "
            f"{int((lag > 0.1).sum())} sends over 100 ms late")
        lat = np.sort((ses.done - ses.due)[ses.ok]) * 1e3
        if len(lat):
            q = {p: lat[min(len(lat) - 1, int(len(lat) * p / 100))]
                 for p in (50, 95, 99)}
            log(f"latency from due: p50 {q[50]:.3f} p95 {q[95]:.3f} "
                f"p99 {q[99]:.3f} max {lat[-1]:.3f} ms, "
                f"{int((lat > 200).sum())} over 200 ms")

    # ---------------------------------------------------------- check
    t = time.perf_counter()
    checks = check(cell, corpus, ses, seed)
    log(f"check: {time.perf_counter() - t:.3f}s")
    correct = reference.passes(checks)

    # -------------------------------------------------------- metrics
    ctx = Context(cell, ses, setup_s, before, after,
                  device_kind=dev.device_kind)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(ses),
           "failed": int(checks["unanswered"]["value"])}
    if trace:
        from bench import trace_reduce
        t = time.perf_counter()
        red = trace_reduce.reduce_trace(trace_reduce.find_trace(tdir))
        ctx.trace = red
        ctx.scan_rows = scan_rows(corpus, ses)
        log(f"trace: {time.perf_counter() - t:.3f}s to reduce; busy "
            f"{red.busy_s:.6f}s of {red.window_s:.6f}s")
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        out["breakdown"] = {"device_ops": [list(t) for t in red.top_ops],
                            "idle_gaps": [list(t) for t in red.idle_gaps]}
    metrics = {}
    key = "per_layer" if trace else "end_to_end"
    for m in cell_metrics(spec, cell.name, key):
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = {k: {kk: v[kk] for kk in ("value", "limit") if kk in v}
                     for k, v in checks.items()}
    for k, v in checks.items():
        lim = f" limit {v['ok']} {v['limit']}" if "limit" in v else ""
        log(f"check {k}: {v['value']}{lim}")
    return out


def check(cell: Cell, corpus: data.Corpus, ses: load.Measured,
          seed: int) -> dict:
    """Compare the window's answers with the plain reference."""
    cfg = cell.cfg
    ok = ses.ok
    q = np.flatnonzero(ok)
    if len(q) > CHECK_MAX:
        q = np.sort(data.rng(seed, 77).choice(q, CHECK_MAX, replace=False))
    ref = reference.HostReference(corpus.vecs, corpus.attrs,
                                  np.arange(cfg["n"]))
    out = {"unanswered": {"value": int((~ok).sum()), "limit": 0, "ok": "<="},
           "checked": {"value": len(q)}}
    out.update(reference.compare(
        ref, corpus.queries[ses.qidx[q]], ses.rng[q], ses.ids[q],
        ses.dists[q], ses.strategy[q], k=cfg["k"],
        beam_floor=cfg["guarantees"]["beam_routed_recall_floor"],
        gap_limit=cfg["guarantees"]["scan_gap_limit"]))
    if cell.mix.get("both_routes"):
        out["scan_queries"].update(limit=1, ok=">=")
        out["beam_queries"].update(limit=1, ok=">=")
    return out


def scan_rows(corpus: data.Corpus, ses: load.Measured) -> int:
    """Rows the window's answered scan-routed queries asked the scan kernel
    to score: the roofline's work, from the workload."""
    srt = corpus.attrs_sorted
    sel = ses.ok & (ses.strategy == 0)
    lo, hi = ses.rng[sel, 0], ses.rng[sel, 1]
    return int((np.searchsorted(srt, hi, "right")
                - np.searchsorted(srt, lo, "left")).sum())
