#!/usr/bin/env python3
"""Smoke run of the served RFANN path on TPU chips.

  python3 chip_smoke.py               # one chip: 2^20 rows at d=96
  python3 chip_smoke.py --chips 4     # four chips: the range-sharded mesh

One chip.  ``RNSGIndex.build`` over a seeded corpus at the DEEP1M shape
(n = 2^20, d = 96, one uniform attribute), then the paper's mixed
selectivity workload (2^0..2^-9, k=10) served through
``RFANNEngine(plan="auto")`` at ef=2048 in three phases:

  (a) f32 — first one pass at the paper's ef=64 (scan-routed recall
      checked, beam-routed recall logged as a reading), then the checked
      pass at ef=2048: both strategies must be routed (fused scan and
      graph beam);
  (b) int8 — quantized scan/traversal with the exact f32 rerank; queries
      scan-routed in both runs must return the same id sets as (a);
  (c) streaming — a ``StreamingRFANN`` over the phase-(a) graph, a few
      thousand inserts and deletes through the engine with a WAL in
      ``sync="batch"``, one compaction waited on; no tombstoned id may
      come back (128 requests spread over every selectivity, served
      before and after the compaction: an ef=2048 pass of all 512 costs
      minutes on one chip).

Four chips (``--chips 4``, this phase only).  ``DistributedRFANN`` on a
("data",) mesh with 2^20 rows per chip, served through the engine, and
compared with the local per-shard path over the same shards under
``plan="auto"``: both route each shard's clip by the same ceiling, so the
ids must be identical.

Every result is scored against an exact host reference (float32 candidate
pass, float64 rescore): recall@10 must be 1.0 for scan-routed queries
(distance ties allowed) and ≥ 0.9 for beam-routed ones.  The beam pool is
ef=2048 because this mixture (isotropic unit noise around 32 centres, so
the local intrinsic dimension is near d) needs it at 2^20 rows: at ef=64
the beam-routed (widest) ranges stay far below 0.9 there, and the pool a
given recall needs grows with n.  The last line of
stdout is ``{"ok": true, "device": {...}}``; any failure exits non-zero.
Without a TPU the script exits non-zero and prints no result;
``--rehearse`` runs the same phases on CPU at a small ``--n`` (Pallas in
interpret mode) and still exits non-zero, since it is not a chip run.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
K = 10
D = 96                              # the DEEP1M width
BUILD_KW = dict(m=32, ef_spatial=32, ef_attribute=48)     # serve defaults
SCAN_FLOOR, BEAM_FLOOR = 1.0, 0.9
EF = 2048                           # beam pool of every served request
EF_PAPER = 64                       # the paper's pool: a recall reading
CHURN = 2048                        # phase (c) inserts (and base deletes/2)
CHURN_REQUESTS = 128                # requests served in phase (c)
STATE = ROOT / ".smoke_state"       # WAL directory for phase (c)


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ------------------------------------------------------------------ data
def make_data(n: int, nq: int, n_extra: int, d: int, seed: int):
    """Corpus, held-out queries and extra rows (inserts) drawn from one
    seeded mixture, plus a uniform attribute for corpus and extra rows."""
    from repro.data.ann import make_attrs, make_vectors
    v = make_vectors(n + nq + n_extra, d, seed=seed)
    a = make_attrs(n + n_extra, seed=seed)
    return (v[:n], a[:n], v[n:n + nq], v[n + nq:], a[n:])


class HostReference:
    """Exact range-filtered top-k on the host, independent of the
    program's resolve: each query's rows are those whose attribute lies
    in its inclusive range (a plain mask, so attribute ties need no
    tie-break); a float32 distance pass picks 4k candidates among them,
    and float64 distances of those decide the top-k."""

    def __init__(self, vecs: np.ndarray, attrs: np.ndarray,
                 ids: np.ndarray):
        self.x = np.ascontiguousarray(vecs, np.float32)
        self.xn = np.einsum("ij,ij->i", self.x, self.x)
        self.attrs = np.asarray(attrs, np.float32)
        self.ids = np.asarray(ids)                # row -> external id
        self.by_id = {int(e): r for r, e in enumerate(self.ids)}

    def topk(self, qv, ranges, k: int):
        ranges = np.asarray(ranges, np.float32)
        nq = len(qv)
        gt = np.full((nq, k), -1, np.int64)
        gd = np.full((nq, k), np.inf)
        blk = max(1, (1 << 26) // len(self.x))
        for b in range(0, nq, blk):
            q = qv[b:b + blk]
            d32 = self.xn[None, :] - 2.0 * (q @ self.x.T)
            for j in range(len(q)):
                i = b + j
                rows = np.flatnonzero((self.attrs >= ranges[i, 0])
                                      & (self.attrs <= ranges[i, 1]))
                if not len(rows):
                    continue
                row = d32[j, rows]
                c = min(4 * k, len(row))
                cand = rows[np.argpartition(row, c - 1)[:c]]
                d64 = self.dist64(qv[i], cand)
                o = np.lexsort((cand, d64))[:k]
                gt[i, :len(o)] = self.ids[cand[o]]
                gd[i, :len(o)] = d64[o]
        return gt, gd

    def dist64(self, q, rows) -> np.ndarray:
        diff = self.x[rows].astype(np.float64) - q.astype(np.float64)
        return np.einsum("ij,ij->i", diff, diff)

    def found_dists(self, qv, ranges, found) -> np.ndarray:
        """float64 distance of each returned id; +inf for an id whose
        attribute lies outside its query's range, so the tie rule of
        ``recall_at_k`` can never count it as a hit."""
        ranges = np.asarray(ranges, np.float32)
        out = np.full(found.shape, np.inf)
        for i, row in enumerate(found):
            ok = np.flatnonzero(row >= 0)
            if len(ok):
                rows = np.asarray([self.by_id[int(e)] for e in row[ok]])
                a = self.attrs[rows]
                inr = (a >= ranges[i, 0]) & (a <= ranges[i, 1])
                out[i, ok[inr]] = self.dist64(qv[i], rows[inr])
        return out


def recall_by_strategy(ref: HostReference, qv, ranges, ids, strat,
                       tag: str, levels=None,
                       beam_floor: float | None = BEAM_FLOOR) -> dict:
    """Tie-aware recall@k per routed strategy against the host reference;
    fails below the floors (``beam_floor=None``: the beam-routed recall is
    a reading, not checked).  With ``levels`` (the selectivity level of
    each query) the recall of each level is logged too."""
    from benchmarks.common import recall_at_k
    from repro.planner import BEAM, SCAN
    gt, gd = ref.topk(qv, ranges, K)
    fd = ref.found_dists(qv, ranges, ids)
    kth = gd[:, K - 1][np.isfinite(gd[:, K - 1])]
    eps = 1e-5 * float(kth.max()) if len(kth) else 0.0
    if levels is not None:
        per = [recall_at_k(ids[levels == v], gt[levels == v],
                           gt_dists=gd[levels == v],
                           found_dists=fd[levels == v], eps=eps)
               for v in np.unique(levels)]
        log(f"{tag}: recall@{K} by selectivity 2^0..2^-{len(per) - 1}: "
            + " ".join(f"{r:.3f}" for r in per))
    out = {}
    for name, code, floor in (("scan", SCAN, SCAN_FLOOR),
                              ("beam", BEAM, beam_floor)):
        sel = strat == code
        if not sel.any():
            continue
        r = recall_at_k(ids[sel], gt[sel], gt_dists=gd[sel],
                        found_dists=fd[sel], eps=eps)
        out[name] = r
        log(f"{tag}: recall@{K} {name}-routed = {r!r} over {int(sel.sum())} "
            f"queries (floor {floor})")
        check(floor is None or r >= floor,
              f"{tag}: {name}-routed recall {r} < {floor}")
    return out


# --------------------------------------------------------------- serving
class CompileClock:
    """Sums the backend compile time JAX reports (all threads)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration


def serve(index, qv, ranges, clock: CompileClock, tag: str, *,
          engine=None, **engine_kw):
    """Serve every request once through ``RFANNEngine(plan="auto")`` (a
    fresh engine unless one is given).  The pass pays its compiles; the
    compile seconds are logged beside the wall so a reader can take them
    out (a second, warm pass would cost minutes of ef=2048 beam)."""
    from repro.serving.engine import RFANNEngine
    eng = engine or RFANNEngine(index, k=K, plan="auto", max_batch=64,
                                max_wait_ms=2.0, **engine_kw)
    c0 = clock.seconds
    t0 = time.perf_counter()
    futs = [eng.submit(q, r) for q, r in zip(qv, ranges)]
    rows = [f.result(timeout=900) for f in futs]
    wall = time.perf_counter() - t0
    s = eng.summary()
    if engine is None:
        eng.close()
    log(f"{tag}: {len(rows)} requests in {wall:.3f}s "
        f"({len(rows) / wall:.1f} QPS) p50={s['p50_ms']:.3f}ms "
        f"p99={s['p99_ms']:.3f}ms batches={s['batches']} "
        f"compile={clock.seconds - c0:.3f}s")
    log(f"{tag} layers: " + layer_line(eng.metrics()))
    ids = np.stack([r.ids for r in rows]).astype(np.int64)
    strat = np.asarray([int(r.stats["strategy"]) for r in rows])
    return ids, strat


def layer_line(snap: dict) -> str:
    """Wall ms summed per layer over the pass (engine metrics), and the
    routing counters."""
    h, c = snap["histograms"], snap["counters"]
    parts = [f"{name}={h[name]['sum']:.1f}ms/{h[name]['count']}"
             for name in ("engine_queue_wait_ms", "engine_resolve_ms",
                          "engine_handoff_wait_ms", "scan_dispatch_ms",
                          "beam_dispatch_ms", "stage_scan_block_ms",
                          "stage_beam_block_ms", "stage_mesh_prep_ms",
                          "stage_mesh_dispatch_ms", "stage_mesh_block_ms",
                          "stage_assemble_ms",
                          "engine_e2e_ms")
             if name in h]
    parts += [f"{name}={c[name]}" for name in
              ("scan_routed_total", "beam_routed_total", "pad_rows_total")
              if name in c]
    return " ".join(parts)


def hbm(dev) -> str:
    st = dev.memory_stats() or {}
    if "bytes_in_use" not in st:
        return "not reported"
    return (f"{st['bytes_in_use']} bytes in use, peak "
            f"{st.get('peak_bytes_in_use', 'n/a')}")


def check_scan_lowers_to_mosaic(n: int, d: int, platform: str) -> None:
    """The served scan kernel must compile to a Mosaic custom call here,
    never to the Pallas interpreter."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import range_scan
    from repro.planner.bucketing import ROW_TILE
    n_pad = -(-n // ROW_TILE) * ROW_TILE
    d_pad = -(-d // 128) * 128
    fn = jax.jit(functools.partial(range_scan, bucket=2048, k=K))
    txt = fn.lower(jax.ShapeDtypeStruct((n_pad, d_pad), jnp.float32),
                   jax.ShapeDtypeStruct((64,), jnp.int32),
                   jax.ShapeDtypeStruct((64,), jnp.int32),
                   jax.ShapeDtypeStruct((64, d_pad), jnp.float32)).as_text()
    mosaic = "tpu_custom_call" in txt
    log(f"served range_scan lowers to tpu_custom_call: {mosaic}")
    if platform == "tpu":
        check(mosaic, "range_scan did not lower to a Mosaic kernel")


# ---------------------------------------------------------------- phases
def one_chip(args, dev, clock: CompileClock) -> None:
    from repro.core.rfann import RNSGIndex
    from repro.data.ann import mixed_workload
    from repro.planner import BEAM, SCAN
    from repro.streaming import StreamingRFANN

    n, d, nq = args.n, D, args.requests
    n_ins = CHURN
    t0 = time.perf_counter()
    vecs, attrs, qv, ins_v, ins_a = make_data(n, nq, n_ins, d, args.seed)
    ranges, levels = mixed_workload(attrs, nq, seed=args.seed + 3)
    log(f"data: n={n} d={d} requests={nq} in "
        f"{time.perf_counter() - t0:.3f}s")
    ref = HostReference(vecs, attrs, np.arange(n))

    # ------------------------------------------------------- (a) f32
    c0 = clock.seconds
    t0 = time.perf_counter()
    idx = RNSGIndex.build(vecs, attrs, **BUILD_KW)
    st = idx.g.meta["stage_seconds"]
    log(f"build: {time.perf_counter() - t0:.3f}s "
        + " ".join(f"{k}={v:.3f}s" for k, v in st.items())
        + f" (compile {clock.seconds - c0:.3f}s) {idx.stats()}")
    log(f"HBM after build: {hbm(dev)}")
    ids_p, strat_p = serve(idx, qv, ranges, clock, f"(a) f32 ef={EF_PAPER}",
                           ef=EF_PAPER)
    recall_by_strategy(ref, qv, ranges, ids_p, strat_p,
                       f"(a) f32 ef={EF_PAPER}", levels, beam_floor=None)
    ids_a, strat_a = serve(idx, qv, ranges, clock, "(a) f32", ef=EF)
    n_scan, n_beam = int((strat_a == SCAN).sum()), int((strat_a == BEAM).sum())
    log(f"(a) routed: scan={n_scan} beam={n_beam}")
    check(n_scan > 0 and n_beam > 0, "(a) both strategies must be routed")
    recall_by_strategy(ref, qv, ranges, ids_a, strat_a, "(a) f32", levels)
    check_scan_lowers_to_mosaic(n, d, dev.platform)

    # ------------------------------------------------------ (b) int8
    ids_b, strat_b = serve(idx, qv, ranges, clock, "(b) int8",
                           ef=EF, precision="int8")
    both = (strat_a == SCAN) & (strat_b == SCAN)
    same = [set(ids_a[i].tolist()) == set(ids_b[i].tolist())
            for i in np.flatnonzero(both)]
    log(f"(b) int8 scan ids equal f32 on {sum(same)}/{len(same)} queries "
        f"scan-routed in both runs")
    check(all(same), "(b) int8 scan ids differ from f32")
    # the planner prices int8 on its own measured walls, so the overlap
    # above may be small: every query (a) scan-routed is also scanned in
    # int8 (scaled range_scan + f32 gather_rerank) and must give (a)'s ids
    sel = np.flatnonzero(strat_a == SCAN)
    ids_s, _, _ = idx.search(qv[sel], ranges[sel], k=K, ef=EF,
                             plan="scan", precision="int8")
    same = [set(ids_a[i].tolist()) == set(r.tolist())
            for i, r in zip(sel, np.asarray(ids_s))]
    log(f"(b) forced int8 scan ids equal f32 on {sum(same)}/{len(same)} "
        f"queries (a) scan-routed")
    check(len(same) and all(same), "(b) forced int8 scan ids differ from f32")
    recall_by_strategy(ref, qv, ranges, ids_b, strat_b, "(b) int8")
    log(f"HBM after int8: {hbm(dev)}")

    # ------------------------------------------------- (c) streaming
    g = idx.g
    del idx
    stream = StreamingRFANN.from_state(
        base_vecs=g.vecs, base_attrs=g.attrs, base_ids=g.order,
        base_live=np.ones(n, bool), base_nbrs=g.nbrs, base_rmq=g.rmq,
        base_dist_c=g.dist_c, delta_vecs=np.zeros((0, d), np.float32),
        delta_attrs=np.zeros(0, np.float32),
        delta_ids=np.zeros(0, np.int32), next_id=n, max_delta=1 << 30,
        build_kw=BUILD_KW)
    shutil.rmtree(STATE, ignore_errors=True)
    from repro.serving.engine import RFANNEngine
    eng = RFANNEngine(stream, k=K, ef=EF, plan="auto", max_batch=64,
                      max_wait_ms=2.0, wal_dir=str(STATE / "wal"),
                      wal_sync="batch")
    rng = np.random.default_rng(args.seed + 5)
    base_dead = rng.choice(n, n_ins // 2, replace=False)
    dead = set()
    t0 = time.perf_counter()
    for j in range(n_ins):
        eng.insert(ins_v[j], float(ins_a[j]), ext_id=n + j)
        if j % 2:
            e = int(base_dead[j // 2])
            eng.delete(e)
            dead.add(e)
        if j % 8 == 7:                      # and some fresh delta rows
            eng.delete(n + j - 3)
            dead.add(n + j - 3)
    dt = time.perf_counter() - t0
    log(f"(c) churn: {n_ins} inserts + {len(dead)} deletes in {dt:.3f}s "
        f"({(n_ins + len(dead)) / dt:.1f} mutations/s) {stream.stats()}")
    sub = slice(None, None, max(nq // CHURN_REQUESTS, 1))   # every level
    qv, ranges = qv[sub], ranges[sub]
    ids_c, _ = serve(stream, qv, ranges, clock, "(c) churned",
                     engine=eng)
    hit = dead & set(ids_c.ravel().tolist())
    check(not hit, f"(c) tombstoned ids returned before compaction: {hit}")
    t0 = time.perf_counter()
    check(stream.compact(wait=True), "(c) compaction did not run")
    log(f"(c) compaction: {time.perf_counter() - t0:.3f}s {stream.stats()}")
    ids_c, strat_c = serve(stream, qv, ranges, clock, "(c) compacted",
                           engine=eng)
    eng.close()
    stream.close()
    hit = dead & set(ids_c.ravel().tolist())
    check(not hit, f"(c) tombstoned ids returned after compaction: {hit}")
    lv, la, li = stream.live_items()
    check(len(li) == n + n_ins - len(dead) and not (set(li.tolist()) & dead),
          "(c) live set after compaction is wrong")
    recall_by_strategy(HostReference(lv, la, li), qv, ranges, ids_c,
                       strat_c, "(c) compacted")
    shutil.rmtree(STATE, ignore_errors=True)
    log(f"(c) no tombstoned id returned; HBM: {hbm(dev)}")


def four_chips(args, dev, clock: CompileClock) -> None:
    import jax
    from jax.sharding import AxisType

    from repro.data.ann import mixed_workload
    from repro.planner import BEAM, SCAN
    from repro.serving.distributed import DistributedRFANN

    s = args.chips
    n, d, nq = s * args.n, D, args.requests
    t0 = time.perf_counter()
    vecs, attrs, qv, _, _ = make_data(n, nq, 0, d, args.seed)
    ranges, _ = mixed_workload(attrs, nq, seed=args.seed + 3)
    log(f"data: n={n} ({s} x {args.n}) d={d} requests={nq} in "
        f"{time.perf_counter() - t0:.3f}s")
    mesh = jax.make_mesh((s,), ("data",), axis_types=(AxisType.Auto,))
    c0 = clock.seconds
    t0 = time.perf_counter()
    dist = DistributedRFANN(vecs, attrs, n_shards=s, mesh=mesh, **BUILD_KW)
    log(f"build ({s} shards, one per chip): "
        f"{time.perf_counter() - t0:.3f}s (compile "
        f"{clock.seconds - c0:.3f}s)")
    ids_m, strat_m = serve(dist, qv, ranges, clock, "mesh", ef=EF)
    log(f"mesh routed: scan={int((strat_m == SCAN).sum())} "
        f"beam={int((strat_m == BEAM).sum())}")
    recall_by_strategy(HostReference(vecs, attrs, np.arange(n)), qv,
                       ranges, ids_m, strat_m, "mesh")
    # the same shards through the local path under plan="auto": each
    # shard's planner there routes its clip by the ceiling the mesh uses
    # (and at 2^20 rows a shard every beamed clip is over 2^17 rows, so its
    # selectivity-capped ef is the mesh's ef)
    mesh_path, dist.mesh = dist.mesh, None
    try:
        ids_l, _ = dist.search(qv, ranges, k=K, ef=EF, plan="auto")
    finally:
        dist.mesh = mesh_path
    same = (ids_l == ids_m).all(axis=1)
    log(f"mesh ids equal local-path ids on {int(same.sum())}/{nq} queries")
    check(same.all(), "mesh and local paths disagree")
    for i, dv in enumerate(jax.devices()[:s]):
        log(f"HBM chip {i}: {hbm(dv)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--n", type=int, default=1 << 20,
                    help="corpus rows (per chip with --chips 4)")
    ap.add_argument("--requests", type=int, default=512)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="allow a CPU run (small --n); exits non-zero")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU found (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(devs)} found",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.runtime.compile_cache import enable_compile_cache
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}; compile cache {enable_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        (four_chips if args.chips > 1 else one_chip)(args, dev, clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"total {time.perf_counter() - t0:.3f}s, backend compile "
        f"{clock.seconds:.3f}s")
    if dev.platform != "tpu":
        print("chip_smoke: rehearsal passed (not a chip run)",
              file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
