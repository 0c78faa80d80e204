"""Engine layer: share of the dispatcher thread's staged time spent on
batches rather than waiting for one, in percent; open loop.  100 x (sum of
its leaf stages' wall - ``await_batch``) / sum of its leaf stages' wall,
from the ``stage_<name>_ms`` histograms.  Also logs how a request's mean
end-to-end time splits into queue wait, resolve, hand-off wait and the
dispatcher's serve time per batch."""
from bench import harness

# the program's ``repro.obs.DISPATCHER_STAGES``, listed here: the reader
# also runs against programs that predate the constant
STAGES = ("await_batch", "plan", "scan_prep", "scan_dispatch", "rerank",
          "scan_block", "beam_prep", "beam_dispatch", "graph_beam_dispatch",
          "beam_block", "assemble", "complete")
PARTS = ("engine_queue_wait_ms", "engine_resolve_ms",
         "engine_handoff_wait_ms")


def read(ctx):
    if ctx.cell.mix["loop"] != "open":
        return None
    sums = {s: ctx.hist(f"stage_{s}_ms") for s in STAGES}
    total = sum(h[0] for h in sums.values() if h is not None)
    wait = sums["await_batch"]
    if wait is None or total <= 0:
        return None
    serve = total - wait[0]
    log_split(ctx, serve)
    return 100.0 * serve / total


def log_split(ctx, serve_ms: float) -> None:
    hists = [ctx.hist(n) for n in ("engine_e2e_ms",) + PARTS]
    if any(h is None or h[1] <= 0 for h in hists):
        return
    e2e, *parts = (h[0] / h[1] for h in hists)
    per_batch = serve_ms / hists[-1][1]
    harness.log(
        f"engine: e2e mean {e2e:.3f} ms per request; queue wait "
        f"{parts[0]:.3f} + resolve {parts[1]:.3f} + hand-off {parts[2]:.3f}"
        f" + serve per batch {per_batch:.3f} = "
        f"{sum(parts) + per_batch:.3f} ms")
