"""Dispatch + stitch stages: one strategy-routed execution layer.

``SearchSubstrate`` owns the entire query path for one attribute-sorted
corpus slice (a whole index, or one shard of a distributed one):

* ``resolve``  — attribute ranges -> rank intervals (``repro.search.resolve``);
* cache        — when a ``SearchCache`` is installed, each request is split
                 into hit rows (served from memory, no device work), unique
                 miss rows (executed), and intra-batch duplicates of a miss
                 (executed once, fanned back out), stitched in request
                 order;
* dispatch     — ``graph`` runs the paper's beam search over the full batch;
                 ``auto``/``scan``/``beam`` go through the adaptive planner,
                 which partitions the batch into fixed-shape jit dispatches
                 (fused Pallas ``range_scan`` | bucketed beam search);
* stitch       — partition results land back in request order, rank ids are
                 remapped to original corpus ids, and per-query stats
                 (hops / ndist / strategy) are assembled.

Dispatch is **asynchronous at the substrate boundary**: ``dispatch(req)``
enqueues all device work (jax async dispatch) and returns a
``PendingSearch`` whose ``result()`` blocks and stitches.  ``run`` is the
synchronous spelling (``dispatch(..., defer=False).result()``); the
distributed local path dispatches every shard before blocking any of them,
overlapping the per-shard device queues.  Deferred dispatches skip
wall-time calibration (their block time includes sibling shards' work),
while ndist-based beam calibration still applies.

Scan partitions pad with empty windows (masked, ~free); beam partitions pad
by duplicating the last real query (a duplicate lane adds no extra
``while_loop`` iterations under vmap).  After every planned synchronous
dispatch the substrate feeds the cost model: observed ``ndist`` from beam
stats and warm-call wall times per work unit (the first call of each jit
signature is excluded so compile time never enters calibration).

``MeshSubstrate`` is the ``shard_map`` twin for multi-device serving: the
planner runs **host-side** over the globally resolved rank intervals (clipped
per shard), and the resulting strategy vector partitions the batch into
scan/beam sub-batches that enter the traced per-device body as replicated
operands — a branchless select in which each shard executes the ``range_scan``
kernel and the beam search at most once per call, scatters both groups back
into request order, and finishes with the cross-shard ``all_gather`` + top-k
merge.  Warm-call wall times of the traced dispatches feed the cost model
(mixed scan+beam calls are attributed proportionally to predicted unit
costs — ``CostModel.observe_wall_mixed``), so mesh routing converges to
measured hardware ratios instead of serving from the prior forever.  See
docs/distributed.md.
"""
from __future__ import annotations

import time
from functools import partial
from typing import Callable, Dict, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.beam import beam_search_batch, rerank_pool
from repro.kernels.ops import range_scan
from repro.kernels.quantize import (QuantizedCorpus, quantize_corpus,
                                    rerank_depth)
from repro.obs.metrics import MetricsRegistry
from repro.obs.profiler import annotate
from repro.obs.trace import maybe_span
from repro.planner.bucketing import (ROW_TILE, bucket_for_len, next_pow2,
                                     pad_pow2, window_rows)
from repro.planner.planner import BEAM, QueryPlanner, SCAN
from repro.search import resolve
from repro.search.cache import SearchCache
from repro.search.request import SearchRequest, SearchResult

INF = np.float32(np.inf)


def merge_topk(ids: jax.Array, dists: jax.Array, k: int):
    """(S,Q,k) per-shard results -> (Q,k) global top-k.  Shared by the local
    path, the mesh bodies, and the dry-run — identical merges by
    construction (same flatten order, same ``lax.top_k`` tie-breaking)."""
    s, q, kk = ids.shape
    flat_i = jnp.moveaxis(ids, 0, 1).reshape(q, s * kk)
    flat_d = jnp.moveaxis(dists, 0, 1).reshape(q, s * kk)
    nd, sel = jax.lax.top_k(-flat_d, k)
    out_i = jnp.take_along_axis(flat_i, sel, axis=1)
    return jnp.where(jnp.isfinite(-nd), out_i, -1), -nd


class PendingSearch:
    """Handle for an in-flight substrate dispatch.

    The device work is already enqueued when this object exists (jax async
    dispatch); ``result()`` blocks on the outputs, stitches, feeds the cost
    model, and returns the ``SearchResult``.  Idempotent — repeated calls
    return the same object."""
    __slots__ = ("_finalize", "_result")

    def __init__(self, finalize: Callable[[], SearchResult]):
        self._finalize: Optional[Callable[[], SearchResult]] = finalize
        self._result: Optional[SearchResult] = None

    def result(self) -> SearchResult:
        if self._finalize is not None:
            self._result = self._finalize()
            self._finalize = None
        return self._result


class SearchSubstrate:
    def __init__(self, vecs, nbrs, rmq, dist_c, order, attrs, *,
                 planner: Optional[QueryPlanner] = None,
                 use_kernel: bool = False,
                 cache: Optional[SearchCache] = None,
                 cache_ns=None,
                 metrics: Optional[MetricsRegistry] = None):
        self._vecs = jnp.asarray(vecs, jnp.float32)
        self._nbrs = jnp.asarray(nbrs)
        self._rmq = jnp.asarray(rmq)
        self._dist_c = jnp.asarray(dist_c)
        self.order = np.asarray(order)
        self.attrs = np.asarray(attrs)
        self.use_kernel = use_kernel
        self.cache = cache
        self.cache_ns = cache_ns    # distinguishes shards sharing one cache
        self.metrics = metrics      # optional MetricsRegistry (obs layer)
        n, d = self._vecs.shape
        self.n, self.d = n, d
        self.tb = ROW_TILE          # must match the range_scan kernel tile
        self.d_pad = -(-d // 128) * 128
        if planner is None:
            deg = float((np.asarray(nbrs) >= 0).sum(1).mean()) if n else 1.0
            planner = QueryPlanner(max(n, 1), deg)
        self.planner = planner
        self._x_pad = None          # padded scan copy, built on first scan
        self._quant: Dict[str, dict] = {}   # precision -> quantized slots
        self._live_memo = None      # (mask, (n,) bool dev, (1,n_pad) i32 dev)
        self._warm: Set[Tuple] = set()

    @classmethod
    def from_graph(cls, g, **kw) -> "SearchSubstrate":
        """Build over one ``RNSGGraph`` (single node or one shard)."""
        return cls(g.vecs, g.nbrs, g.rmq, g.dist_c, g.order, g.attrs, **kw)

    # ------------------------------------------------------------ resolve
    def resolve(self, attr_ranges: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Attribute ranges (Q,2) -> inclusive rank intervals (lo, hi)."""
        return resolve.rank_interval(self.attrs, attr_ranges)

    # ---------------------------------------------------------------- run
    def run(self, req: SearchRequest) -> SearchResult:
        """Dispatch one request synchronously and stitch the result."""
        return self.dispatch(req, defer=False).result()

    def dispatch(self, req: SearchRequest, *, defer: bool = True,
                 q_digests=None) -> PendingSearch:
        """Enqueue one request's device work and return a ``PendingSearch``.

        ``defer=True`` (the async path) enqueues every partition before any
        block and skips wall-time calibration; ``defer=False`` reproduces
        the synchronous per-partition dispatch+block loop, whose wall times
        are clean enough to calibrate on.  Cache hits are resolved here —
        a fully-hit request performs no device work at all.  ``q_digests``
        are optional precomputed ``hash_query`` values (the distributed
        local path hashes each query once, not once per shard).

        A ``req.trace`` collects plan / dispatch / stitch spans (the stitch
        span on a deferred dispatch includes the device block); the
        installed ``MetricsRegistry`` (when any) counts routed queries,
        cache outcomes and pad waste, and observes dispatch wall
        histograms."""
        qv = np.asarray(req.queries, np.float32)
        lo = np.asarray(req.lo, np.int64)
        hi = np.asarray(req.hi, np.int64)
        k, ef, bw = int(req.k), int(req.ef), int(req.beam_width)
        prec = req.precision
        tr = req.trace
        met = self.metrics
        nq = len(qv)
        if met is not None and nq:
            met.counter("queries_total").inc(nq)
            met.counter(f"queries_{prec}_total").inc(nq)
        live = req.live
        cache = self.cache
        cache_info = dict(cache_enabled=cache is not None,
                          cache_hits=0, cache_misses=nq, batch_dedup=0)
        if cache is None or nq == 0:
            fin = self._dispatch_all(qv, lo, hi, k, ef, req.strategy,
                                     req.use_kernel, defer, bw, prec,
                                     trace=tr, cache_info=cache_info,
                                     live=live)
            return PendingSearch(self._stitched(fin, tr))
        # (global, segment) epoch pair: fences stores vs both invalidate()
        # and invalidate_segment(self.cache_ns) — the streaming layer bumps
        # the segment epoch on every tombstone change / compaction
        epoch = cache.epoch_for(self.cache_ns)
        cal_epoch = (self.planner.calibration_epoch
                     if req.strategy == "auto" else None)
        keys, hit_rows, miss, dups = cache.split(
            qv, lo, hi, k, ef, req.strategy, req.use_kernel,
            ns=self.cache_ns, digests=q_digests, beam_width=bw,
            precision=prec, cal_epoch=cal_epoch)
        cache_info.update(cache_hits=len(hit_rows), cache_misses=len(miss),
                          batch_dedup=len(dups))
        if met is not None:
            met.counter("cache_hit_rows_total").inc(len(hit_rows))
            met.counter("cache_miss_rows_total").inc(len(miss))
            if dups:
                met.counter("cache_dedup_rows_total").inc(len(dups))
        if len(miss) == 0:
            if tr is not None:          # fully hit: no device work at all
                tr.add_span("dispatch", dispatched=0, ns=self.cache_ns,
                            **cache_info)
            return PendingSearch(self._stitched(
                lambda: cache.assemble(nq, k, hit_rows, None, miss), tr))
        fin = self._dispatch_all(qv[miss], lo[miss], hi[miss], k, ef,
                                 req.strategy, req.use_kernel, defer, bw,
                                 prec, trace=tr, cache_info=cache_info,
                                 live=live)
        miss_keys = [keys[i] for i in miss]

        def finalize() -> SearchResult:
            miss_res = fin()
            cache.store_batch(miss_keys, miss_res, epoch=epoch,
                              cal_epoch=cal_epoch)
            if not hit_rows and not dups:
                miss_res.stats["cache_hits"] = 0
                return miss_res
            return cache.assemble(nq, k, hit_rows, miss_res, miss,
                                  dups)
        return PendingSearch(self._stitched(finalize, tr))

    def _stitched(self, fin: Callable[[], SearchResult],
                  tr) -> Callable[[], SearchResult]:
        """Wrap a finalize closure with the stitch span (block + assembly +
        id remap; on deferred dispatches the block time includes sibling
        device work) and attach the trace to the result.  Identity when
        neither tracing nor metrics are on — the hot path is unchanged."""
        met = self.metrics
        if tr is None and met is None:
            return fin

        def finalize() -> SearchResult:
            t0 = time.perf_counter()
            with maybe_span(tr, "stitch", ns=self.cache_ns):
                res = fin()
            if met is not None:
                met.histogram("stitch_ms").observe(
                    (time.perf_counter() - t0) * 1e3)
            if tr is not None:
                res.trace = tr
            return res
        return finalize

    # ----------------------------------------------------------- dispatch
    def _dispatch_all(self, qv, lo, hi, k, ef, strategy, use_kernel,
                      defer: bool, beam_width: int = 1,
                      precision: str = "f32", trace=None,
                      cache_info=None, live=None) -> Callable[[], SearchResult]:
        """Enqueue the uncached work for one (sub-)batch; the returned
        closure blocks, stitches, and remaps rank ids to original ids.
        The dispatch span covers the enqueue (plus, on the ``defer=False``
        path, the per-partition blocks); the plan span is recorded inside
        it, so spans land in resolve -> plan -> dispatch -> stitch order."""
        met = self.metrics
        with maybe_span(trace, "dispatch") as sp:
            sp.attrs.update(cache_info or {})
            sp.attrs.update(strategy_mode=strategy, use_kernel=use_kernel,
                            beam_width=beam_width, ns=self.cache_ns,
                            precision=precision,
                            dispatched=len(qv), deferred=defer)
            if strategy == "graph":
                if trace is not None:
                    trace.add_span("plan", strategy_mode="graph",
                                   chosen="graph", beam_width=beam_width)
                if met is not None and len(qv):
                    met.counter("graph_queries_total").inc(len(qv))
                fin = self._dispatch_graph(qv, lo, hi, k, ef, use_kernel,
                                           beam_width, precision, live=live)
            else:
                fin = self._dispatch_planned(qv, lo, hi, k, ef, strategy,
                                             use_kernel, defer, beam_width,
                                             precision,
                                             trace=trace, span=sp, live=live)

        def finalize() -> SearchResult:
            ids, dists, stats = fin()
            return SearchResult(resolve.remap_ids(self.order, ids), dists,
                                stats)
        return finalize

    # ------------------------------------------------------ graph strategy
    def _dispatch_graph(self, qv, lo, hi, k, ef, use_kernel, beam_width=1,
                        precision="f32", live=None):
        """The paper's path: one beam-search dispatch over the full batch.
        Non-f32 precisions score the traversal against the quantized corpus
        and rerank the final pool in f32 inside ``beam_search_batch``."""
        qj = jnp.asarray(qv, jnp.float32)
        lo_j = jnp.asarray(lo)
        hi_j = jnp.asarray(hi)
        entry = resolve.select_entry(self._rmq, self._dist_c, lo_j, hi_j,
                                     self.n)
        slot = self._quant_for(precision)
        quant = None if slot is None else (slot["data"], slot["scale"])
        live_b, _ = self._live_ops(live)
        t0 = time.perf_counter()
        with annotate("rnsg.graph_beam_dispatch"):
            ids, dists, st = beam_search_batch(
                self._vecs, self._nbrs, qj, lo_j, hi_j, entry,
                k=k, ef=max(ef, k), use_kernel=use_kernel,
                beam_width=beam_width, quant=quant, live=live_b)
        met = self.metrics

        def finalize():
            st_h = jax.tree.map(np.asarray, st)
            st_h["strategy"] = np.ones(len(qv), np.int8)     # all graph/beam
            st_h["scan_frac"] = 0.0
            if met is not None:
                met.histogram("graph_dispatch_ms").observe(
                    (time.perf_counter() - t0) * 1e3)
            return np.asarray(ids), np.asarray(dists), st_h
        return finalize

    # ---------------------------------------------------- planned strategies
    def _dispatch_planned(self, qv, lo, hi, k, ef, mode, use_kernel,
                          defer: bool, beam_width: int = 1,
                          precision: str = "f32", trace=None,
                          span=None, live=None):
        """Routing policy: plan the batch, dispatch each fixed-shape
        partition, stitch back in request order.  ``defer=False`` blocks
        each partition before dispatching the next (today's calibrated
        loop); ``defer=True`` enqueues them all and blocks only in the
        returned closure."""
        q = len(qv)
        met = self.metrics
        if trace is None:
            plan = self.planner.plan_batch(lo, hi, k=k, ef=ef, mode=mode,
                                           beam_width=beam_width,
                                           precision=precision)
        else:
            with trace.span("plan") as psp:
                plan = self.planner.plan_batch(lo, hi, k=k, ef=ef,
                                               mode=mode,
                                               beam_width=beam_width,
                                               precision=precision)
                lens = np.clip(hi - lo + 1, 0, None)
                sc, bc = self.planner.predict_costs(lens, k=k, ef=ef,
                                                    beam_width=beam_width,
                                                    precision=precision)
                psp.attrs.update(
                    strategy_mode=mode, strategy=plan.strategy.copy(),
                    scan_frac=plan.scan_frac, beam_width=beam_width,
                    precision=precision,
                    partitions=[p.signature for p in plan.partitions],
                    predicted_scan_units=sc, predicted_beam_units=bc)
        pad_rows = sum(p.pad_q - len(p.indices) for p in plan.partitions)
        if met is not None and q:
            n_scan = int((plan.strategy == SCAN).sum())
            met.counter("scan_routed_total").inc(n_scan)
            met.counter("beam_routed_total").inc(q - n_scan)
            if pad_rows:
                met.counter("pad_rows_total").inc(pad_rows)
        if span is not None:
            span.attrs["pad_rows"] = pad_rows
        fins = []
        for part in plan.partitions:
            if part.kind == "scan":
                fin = self._dispatch_scan(qv, lo, hi, part.indices,
                                          part.param, part.pad_q, k, ef,
                                          calibrate_wall=not defer,
                                          precision=precision, trace=trace,
                                          live=live)
            else:
                fin = self._dispatch_beam(qv, lo, hi, part.indices,
                                          part.param, part.pad_q, k,
                                          calibrate=(mode == "auto"),
                                          calibrate_wall=not defer,
                                          use_kernel=use_kernel,
                                          beam_width=beam_width,
                                          precision=precision, live=live)
            if not defer:
                val = fin()
                fin = (lambda v: lambda: v)(val)
            fins.append(fin)

        def finalize():
            out_ids = np.full((q, k), -1, np.int32)
            out_d = np.full((q, k), INF, np.float32)
            hops = np.zeros(q, np.int32)
            ndist = np.zeros(q, np.int32)
            for part, fin in zip(plan.partitions, fins):
                idx = part.indices  # never empty (guarded at plan time)
                if part.kind == "scan":
                    ids_p, d_p, units = fin()
                    ndist[idx] = units
                else:
                    ids_p, d_p, st = fin()
                    hops[idx] = st["hops"]
                    ndist[idx] = st["ndist"]
                out_ids[idx] = ids_p
                out_d[idx] = d_p
            stats = {"hops": hops, "ndist": ndist,
                     "strategy": plan.strategy, "scan_frac": plan.scan_frac}
            return out_ids, out_d, stats
        return finalize

    # ------------------------------------------------------------------
    def _scan_corpus(self):
        """Row/lane-padded corpus copy for the scan kernel (lazy: shards
        that never route to scan skip the duplicate)."""
        if self._x_pad is None:
            n_pad = -(-self.n // self.tb) * self.tb
            self._x_pad = jnp.pad(
                self._vecs, ((0, n_pad - self.n), (0, self.d_pad - self.d)))
        return self._x_pad

    # ------------------------------------------------------- liveness mask
    def _live_ops(self, live):
        """Device forms of a per-rank liveness mask: ((n,) bool for the beam
        paths, (1, n_pad) i32 row for the scan kernel).  Memoized by object
        identity — the streaming layer publishes one immutable mask array
        per corpus version, so ``is`` is a sound cache key and mask reuse
        costs no re-upload."""
        if live is None:
            return None, None
        memo = self._live_memo
        if memo is not None and memo[0] is live:
            return memo[1], memo[2]
        lv = np.asarray(live, bool)
        if lv.shape != (self.n,):
            raise ValueError(
                f"live mask shape {lv.shape} does not match corpus ({self.n},)")
        n_pad = -(-self.n // self.tb) * self.tb
        row = np.zeros((1, n_pad), np.int32)
        row[0, :self.n] = lv
        out = (jnp.asarray(lv), jnp.asarray(row))
        self._live_memo = (live,) + out
        return out

    # --------------------------------------------------- quantized corpus
    def install_quantized(self, precision: str) -> None:
        """Build (or rebuild) the quantized corpus copies for one precision
        ahead of serving, so the first quantized request pays no build cost.
        Lazy build happens anyway on first use (``_quant_for``).

        Rebuilding the quantized slots changes what a non-f32 request scores
        against, so any installed cache must go cold for this substrate:
        rows stored before the switch would otherwise stay servable under
        unchanged keys."""
        if precision != "f32":
            self._quant.pop(precision, None)
            self._quant_for(precision)
            if self.cache is not None:
                self.cache.invalidate_segment(self.cache_ns)

    def _quant_for(self, precision: str) -> Optional[dict]:
        """Quantized scoring slots for one precision (lazy, cached):
        ``data`` (n,d) for the beam's gathered rows, ``data_pad``
        (n_pad,d_pad) rank-ordered for the scan kernel (interval slicing is
        unchanged — quantization is per-element), ``scale``/``scale_pad``
        ((d,)/(d_pad,) f32, int8 only; padding scale with 1.0 is inert
        because padded query/corpus lanes are zero)."""
        if precision == "f32":
            return None
        slot = self._quant.get(precision)
        if slot is None:
            slot = self._slot_of(quantize_corpus(self._vecs, precision))
            self._quant[precision] = slot
        return slot

    def _slot_of(self, qc: QuantizedCorpus) -> dict:
        """Scoring slots from one quantized corpus copy (shared between the
        lazy quantize path and the restore preload path)."""
        n_pad = -(-self.n // self.tb) * self.tb
        data_pad = jnp.pad(qc.data, ((0, n_pad - self.n),
                                     (0, self.d_pad - self.d)))
        scale_pad = (None if qc.scale is None else
                     jnp.pad(qc.scale, (0, self.d_pad - self.d),
                             constant_values=1.0))
        return dict(data=qc.data, data_pad=data_pad,
                    scale=qc.scale, scale_pad=scale_pad,
                    bytes_per_vector=qc.bytes_per_vector)

    def preload_quantized(self, precision: str, data, scale=None) -> None:
        """Attach a prebuilt quantized corpus copy (the index-restore path,
        ``repro.index.io``) without re-quantizing.  ``data`` may arrive as
        the checkpoint's exact f32 upcast — it is narrowed back to the
        precision's dtype here, which round-trips bit-exactly.  Same cache
        rule as :meth:`install_quantized`: the scored corpus changed, so
        this substrate's cache segment goes cold."""
        if precision == "f32":
            return
        dt = jnp.bfloat16 if precision == "bf16" else jnp.int8
        qc = QuantizedCorpus(precision, jnp.asarray(data).astype(dt),
                             None if scale is None
                             else jnp.asarray(scale, jnp.float32))
        self._quant[precision] = self._slot_of(qc)
        if self.cache is not None:
            self.cache.invalidate_segment(self.cache_ns)

    def _dispatch_scan(self, qv, lo, hi, idx, bucket: int, pad_q: int,
                       k: int, ef: int, *, calibrate_wall: bool,
                       precision: str = "f32", trace=None, live=None):
        nq = len(idx)
        starts = np.zeros(pad_q, np.int32)
        lens = np.zeros(pad_q, np.int32)
        starts[:nq] = lo[idx]
        lens[:nq] = np.clip(hi[idx] - lo[idx] + 1, 0, bucket)
        qp = np.zeros((pad_q, self.d_pad), np.float32)
        qp[:nq, :self.d] = qv[idx]
        slot = self._quant_for(precision)
        _, live_row = self._live_ops(live)
        sig = ("scan", bucket, pad_q, k, precision, live is not None)
        warm = sig in self._warm
        self._warm.add(sig)
        t0 = time.perf_counter()
        rq = 0
        with annotate("rnsg.scan_dispatch"):
            if slot is None:
                ids, d = range_scan(self._scan_corpus(), jnp.asarray(starts),
                                    jnp.asarray(lens), jnp.asarray(qp),
                                    bucket=bucket, k=k, live=live_row)
            else:
                # quantized scan keeps rerank_depth survivors (clamped to
                # the slice via lens ≤ bucket masking; tombstoned rows are
                # masked here, so the survivor pool is live-only) ...
                rq = rerank_depth(k, ef, cap=self.tb)
                ids_q, _ = range_scan(slot["data_pad"], jnp.asarray(starts),
                                      jnp.asarray(lens), jnp.asarray(qp),
                                      bucket=bucket, k=rq,
                                      scale=slot["scale_pad"],
                                      live=live_row)
                # ... then a fused f32 rescore of those ids restores the
                # exact top-k (candidates rank-sorted so ties break exactly
                # as the oracle's)
                with maybe_span(trace, "rerank", precision=precision,
                                rows=pad_q * rq, k=k):
                    ids, d = rerank_pool(self._vecs, ids_q,
                                         jnp.asarray(qp[:, :self.d]), k,
                                         use_kernel=True)
        units = window_rows(bucket, self.tb)
        met = self.metrics

        def finalize():
            ids_h = np.asarray(ids)[:nq]
            d_h = np.asarray(d)[:nq]
            dt = time.perf_counter() - t0
            if met is not None:
                met.histogram("scan_dispatch_ms").observe(dt * 1e3)
                if rq:
                    met.counter("rerank_rows_total").inc(pad_q * rq)
            if calibrate_wall and warm:
                # the dispatch did pad_q windows of work, not nq: normalize
                # by pad_q so calibration measures the kernel, not the
                # padding ratio
                self.planner.cost.observe_wall("scan", units, dt, pad_q,
                                               precision=precision)
            return ids_h, d_h, units
        return finalize

    def _dispatch_beam(self, qv, lo, hi, idx, ef: int, pad_q: int, k: int, *,
                       calibrate: bool, calibrate_wall: bool = True,
                       use_kernel: bool = False, beam_width: int = 1,
                       precision: str = "f32", live=None):
        nq = len(idx)
        if nq == 0:                 # empty partition: nothing to dispatch
            empty = np.zeros(0, np.int32)
            return lambda: (np.zeros((0, k), np.int32),
                            np.zeros((0, k), np.float32),
                            {"hops": empty, "ndist": empty})
        pad = np.concatenate([idx, np.repeat(idx[-1:], pad_q - nq)])
        lo_j = jnp.asarray(np.clip(lo[pad], 0, self.n - 1).astype(np.int32))
        hi_j = jnp.asarray(np.clip(hi[pad], 0, self.n - 1).astype(np.int32))
        entry = resolve.select_entry(self._rmq, self._dist_c, lo_j, hi_j,
                                     self.n)
        qp = jnp.asarray(qv[pad])
        slot = self._quant_for(precision)
        quant = None if slot is None else (slot["data"], slot["scale"])
        live_b, _ = self._live_ops(live)
        sig = ("beam", ef, pad_q, k, beam_width, precision, live is not None)
        warm = sig in self._warm
        self._warm.add(sig)
        t0 = time.perf_counter()
        with annotate("rnsg.beam_dispatch"):
            ids, d, st = beam_search_batch(
                self._vecs, self._nbrs, qp,
                jnp.asarray(lo[pad].astype(np.int32)),
                jnp.asarray(hi[pad].astype(np.int32)),
                entry, k=k, ef=max(ef, k), use_kernel=use_kernel,
                beam_width=beam_width, quant=quant, live=live_b)
        met = self.metrics

        def finalize():
            ids_h = np.asarray(ids)[:nq]
            d_h = np.asarray(d)[:nq]
            st_h = {kk: np.asarray(vv)[:nq] for kk, vv in st.items()}
            dt = time.perf_counter() - t0
            if met is not None:
                met.histogram("beam_dispatch_ms").observe(dt * 1e3)
            if calibrate:
                self.planner.cost.update_beam(float(st_h["ndist"].mean()), ef,
                                              beam_width=beam_width)
                if calibrate_wall and warm:
                    # pad lanes duplicate the last real query, so pad_q lanes
                    # of ~ndist work each were executed — normalize by pad_q
                    self.planner.cost.observe_wall(
                        "beam", max(float(st_h["ndist"].mean()), 1.0), dt,
                        pad_q, precision=precision)
            return ids_h, d_h, st_h
        return finalize

    # ------------------------------------------------- legacy sync wrapper
    def _run_beam(self, qv, lo, hi, idx, ef: int, pad_q: int, k: int, *,
                  calibrate: bool, use_kernel: bool = False):
        """Synchronous beam partition dispatch (kept for the empty-partition
        regression test and any external caller of the pre-async API)."""
        return self._dispatch_beam(qv, lo, hi, np.asarray(idx, np.int64),
                                   ef, pad_q, k, calibrate=calibrate,
                                   use_kernel=use_kernel)()


# ======================================================================
# Mesh path: traced per-device bodies + the host-planned mesh substrate.
# ======================================================================
def _shard_graph(vecs, nbrs, rmq, dist_c, order, rank0, xq, scale, live, qv,
                 lo, hi, *, k: int, ef: int, axis: str, beam_width: int = 1,
                 precision: str = "f32", use_live: bool = False):
    """Per-device graph body (the paper's mesh path): clip the replicated
    global rank interval to this shard, one beam dispatch over the full
    batch, then the cross-shard merge.  Leading shard dim of size 1.

    ``xq``/``scale`` are the quantized scoring operands (``xq`` sharded like
    ``vecs``; ``scale`` a replicated (d_pad,) f32 row, sliced to d here).
    Under ``precision="f32"`` the caller passes ``vecs`` itself as ``xq``
    (no copy) and both are ignored — the operand list stays uniform so one
    body shape serves every precision.  Quantized traversals rerank their
    final pool in f32 inside ``beam_search_batch``, so the merged id set
    matches the f32 body's.

    ``live`` is the sharded (1, per) shard-local liveness mask, same uniform
    -operand idiom: under ``use_live=False`` the caller passes an all-ones
    array and the trace never touches it; under ``use_live=True`` the beam
    filters tombstoned candidates out of its final pool.

    Besides the merged top-k, the body all-gathers each shard's **summed
    ndist** (one scalar per shard) so the host can feed the cost model's
    ``ndist_per_ef`` EMA — without it the mesh path would never move the
    beam-cost estimate (traced bodies return no per-query stats)."""
    vecs, nbrs = vecs[0], nbrs[0]
    rmq, dist_c, order = rmq[0], dist_c[0], order[0]
    n, d = vecs.shape
    if precision == "f32":
        quant = None
    else:
        quant = (xq[0], scale[:d] if precision == "int8" else None)
    slo, shi = resolve.clip_interval_jax(lo, hi, rank0[0], n)
    entry = resolve.select_entry(rmq, dist_c, slo, shi, n)
    ids, dists, st = beam_search_batch(vecs, nbrs, qv, slo, shi, entry,
                                       k=k, ef=ef, beam_width=beam_width,
                                       quant=quant,
                                       live=live[0] if use_live else None)
    orig = resolve.remap_ids_jax(order, ids)
    dists = jnp.where(ids >= 0, dists, jnp.inf)
    ids_g = jax.lax.all_gather(orig, axis)               # (S, Q, k)
    ds_g = jax.lax.all_gather(dists, axis)
    nd_g = jax.lax.all_gather(jnp.sum(st["ndist"]), axis)    # (S,)
    out_i, out_d = merge_topk(ids_g, ds_g, k)
    return out_i, out_d, nd_g


def _shard_planned(x_scan, vecs, nbrs, rmq, dist_c, order, rank0, xq, scale,
                   live, scan_q, scan_lo, scan_hi, scan_dst,
                   beam_q, beam_lo, beam_hi, beam_dst, *,
                   k: int, ef: int, bucket: int, nq: int,
                   has_beam: bool, axis: str, beam_width: int = 1,
                   precision: str = "f32", use_live: bool = False):
    """Per-device planned body: branchless strategy dispatch.

    The host already split the batch into scan/beam sub-batches (replicated
    operands, padded to pow2 with empty windows), so the trace runs the
    ``range_scan`` kernel and the beam search **at most once each** — no
    ``lax.cond`` on traced values, no per-query branching.  Each group's
    results scatter into an ``(nq+1, k)`` buffer at its original request
    positions (pads land in the sink row ``nq``, dropped before the merge),
    restoring request order *before* the cross-shard top-k merge so the merge
    is identical to the graph body's.

    Quantized precisions: ``x_scan`` holds the *quantized* padded scan
    corpus (the caller swaps it per precision — same rank order, narrower
    DMA), ``xq`` the unpadded quantized rows for the beam's gathers, and
    ``scale`` the replicated (d_pad,) dequant row.  The scan keeps
    ``rerank_depth`` survivors and rescores them against the f32 ``vecs``
    in-trace, so scan rows leave this body exact; the beam reranks inside
    ``beam_search_batch``.  Under f32 the extra operands alias ``vecs`` /
    ones and are ignored.

    The scan group is always non-empty here — uniform-beam batches dispatch
    the graph body instead (``MeshSubstrate.run`` fast path).

    ``live`` is the sharded (1, per) shard-local liveness mask (all-ones and
    untouched under ``use_live=False``): the scan masks dead rows in-kernel
    (a (1, per_pad) i32 row built in-trace), the beam filters its final
    pool."""
    x_scan, vecs, nbrs = x_scan[0], vecs[0], nbrs[0]
    rmq, dist_c, order = rmq[0], dist_c[0], order[0]
    n, d = vecs.shape
    if use_live:
        live_sh = live[0]                                # (per,) shard-local
        live_row = jnp.pad(live_sh.astype(jnp.int32),
                           (0, x_scan.shape[0] - n))[None, :]
        live_beam = live_sh.astype(bool)
    else:
        live_row = live_beam = None
    out_i = jnp.full((nq + 1, k), -1, jnp.int32)
    out_d = jnp.full((nq + 1, k), jnp.inf, jnp.float32)
    slo, shi = resolve.clip_interval_jax(scan_lo, scan_hi, rank0[0], n)
    lens = jnp.clip(shi - slo + 1, 0, bucket)            # shard-local window
    starts = jnp.clip(slo, 0, n - 1)                     # (len 0 when empty)
    if precision == "f32":
        ids_s, d_s = range_scan(x_scan, starts, lens, scan_q,
                                bucket=bucket, k=k, n_valid=n, live=live_row)
    else:
        rq = rerank_depth(k, ef, cap=ROW_TILE)
        ids_q, _ = range_scan(x_scan, starts, lens, scan_q,
                              bucket=bucket, k=rq, n_valid=n,
                              scale=scale if precision == "int8" else None,
                              live=live_row)
        ids_s, d_s = rerank_pool(vecs, ids_q, scan_q[:, :d], k,
                                 use_kernel=False)
    d_s = jnp.where(ids_s >= 0, d_s, jnp.inf)
    out_i = out_i.at[scan_dst].set(resolve.remap_ids_jax(order, ids_s))
    out_d = out_d.at[scan_dst].set(d_s)
    nd = jnp.zeros((), jnp.int32)
    if has_beam:
        if precision == "f32":
            quant = None
        else:
            quant = (xq[0], scale[:d] if precision == "int8" else None)
        slo, shi = resolve.clip_interval_jax(beam_lo, beam_hi, rank0[0], n)
        entry = resolve.select_entry(rmq, dist_c, slo, shi, n)
        ids_b, d_b, st = beam_search_batch(vecs, nbrs, beam_q, slo, shi,
                                           entry, k=k, ef=ef,
                                           beam_width=beam_width,
                                           quant=quant, live=live_beam)
        d_b = jnp.where(ids_b >= 0, d_b, jnp.inf)
        out_i = out_i.at[beam_dst].set(resolve.remap_ids_jax(order, ids_b))
        out_d = out_d.at[beam_dst].set(d_b)
        nd = jnp.sum(st["ndist"])       # pad lanes: empty windows, ndist 0
    ids_g = jax.lax.all_gather(out_i[:nq], axis)         # (S, Q, k)
    ds_g = jax.lax.all_gather(out_d[:nq], axis)
    nd_g = jax.lax.all_gather(nd, axis)                  # (S,) beam-group sum
    out_ii, out_dd = merge_topk(ids_g, ds_g, k)
    return out_ii, out_dd, nd_g


class MeshSubstrate:
    """Mesh-path twin of ``SearchSubstrate``: host planning, traced dispatch.

    The cost router is host-side policy and cannot run inside a traced
    ``shard_map`` body, so the strategy split happens **before** tracing:

    * plan     — ``QueryPlanner.choose_strategy_batch`` over each query's
                 widest shard-local clip of the globally resolved rank
                 interval (one replicated decision per query — every shard
                 must agree so the traced shapes stay uniform);
    * dispatch — the strategy vector partitions the batch host-side into a
                 scan sub-batch (one shared pow2 ``bucket``) and a beam
                 sub-batch, entering ``shard_map`` as replicated operands;
                 ``_shard_planned`` runs each kernel at most once per shard;
    * stitch   — in-trace scatter back to request order, ``all_gather`` +
                 ``merge_topk`` across shards, replicated result.

    Compiled signatures are bounded the same way as the local planner's:
    ``(k, ef, bucket, pad_pow2(|scan|), pad_pow2(|beam|), Q)``.

    Calibration feedback: routed dispatches (``auto``/``scan``/``beam``)
    whose jit signature is already warm feed their wall time back into the
    planner's cost model — pure-beam calls observe the beam unit cost
    (work per lane ≈ ``ndist_per_ef · ef``), and mixed scan+beam calls are
    attributed proportionally to predicted unit costs
    (``observe_wall_mixed``).  The traced bodies additionally **all-gather
    a per-shard ndist scalar**, so warm routed dispatches also move the
    ``ndist_per_ef`` EMA itself — the mesh path calibrates the same two
    quantities the local path does.  ``req.strategy == "graph"`` — the
    paper's pure path — never calibrates.
    """

    def __init__(self, mesh, axis: str, vecs, nbrs, rmq, dist_c, order,
                 rank0, *, planner: Optional[QueryPlanner] = None,
                 cache: Optional[SearchCache] = None,
                 calibrate: bool = True,
                 metrics: Optional[MetricsRegistry] = None):
        self.mesh, self.axis = mesh, axis
        self._vecs = jnp.asarray(vecs, jnp.float32)      # (S, per, d)
        self._nbrs = jnp.asarray(nbrs)
        self._rmq = jnp.asarray(rmq)
        self._dist_c = jnp.asarray(dist_c)
        self._order = jnp.asarray(order)
        self._rank0 = jnp.asarray(rank0)                 # (S, 1) int32
        s, per, d = self._vecs.shape
        self.n_shards, self.per, self.d = s, per, d
        self.tb = ROW_TILE
        self.d_pad = -(-d // 128) * 128
        if planner is None:
            deg = float((np.asarray(nbrs) >= 0).sum(-1).mean()) if per else 1.0
            planner = QueryPlanner(max(per, 1), deg)
        self.planner = planner
        self.cache = cache
        self.calibrate = calibrate
        self.metrics = metrics      # optional MetricsRegistry (obs layer)
        self._x_pad = None          # padded scan corpus, built on first scan
        self._quant: Dict[str, dict] = {}   # precision -> quantized slots
        self._ones = None           # dummy replicated scale row (f32/bf16)
        self._live_memo = None      # (mask, (S, per) bool device copy)
        self._live_ones = None      # dummy all-live mask (uniform operands)
        self._fns: Dict[Tuple, object] = {}

    @property
    def index_bytes(self) -> int:
        return self._nbrs.nbytes + self._rmq.nbytes + self._dist_c.nbytes

    # --------------------------------------------------- quantized corpus
    def install_quantized(self, precision: str) -> None:
        """Eagerly build the per-shard quantized corpus copies (lazy build
        on first quantized request otherwise).  Rebuilding changes what
        non-f32 requests score against, so the mesh cache segment goes
        cold (same invariant as ``SearchSubstrate.install_quantized``)."""
        if precision != "f32":
            self._quant.pop(precision, None)
            self._quant_for(precision)
            if self.cache is not None:
                self.cache.invalidate_segment("mesh")

    # ------------------------------------------------------- liveness mask
    def _live_shards(self, live):
        """(n,) global rank-space mask -> (S, per) sharded device copy,
        memoized by object identity (one immutable array per corpus
        version)."""
        if live is None:
            if self._live_ones is None:
                self._live_ones = jnp.ones((self.n_shards, self.per), bool)
            return self._live_ones
        memo = self._live_memo
        if memo is not None and memo[0] is live:
            return memo[1]
        lv = np.asarray(live, bool)
        if lv.shape != (self.n_shards * self.per,):
            raise ValueError(
                f"live mask shape {lv.shape} does not match corpus "
                f"({self.n_shards * self.per},)")
        dev = jnp.asarray(lv.reshape(self.n_shards, self.per))
        self._live_memo = (live, dev)
        return dev

    def _ones_scale(self):
        """Replicated dummy scale row for precisions without one — keeps
        the traced bodies' operand list uniform across precisions."""
        if self._ones is None:
            self._ones = jnp.ones((self.d_pad,), jnp.float32)
        return self._ones

    def _quant_for(self, precision: str) -> Optional[dict]:
        """Per-shard quantized slots (lazy, cached).  The int8 scale is
        computed over the **whole** corpus (all shards jointly), so every
        shard dequantizes with the same replicated (d_pad,) row and merged
        distances are comparable across shards."""
        if precision == "f32":
            return None
        slot = self._quant.get(precision)
        if slot is None:
            s, per, d = self.n_shards, self.per, self.d
            qc = quantize_corpus(self._vecs.reshape(s * per, d), precision)
            data = qc.data.reshape(s, per, d)
            per_pad = -(-per // self.tb) * self.tb
            data_pad = jnp.pad(data, ((0, 0), (0, per_pad - per),
                                      (0, self.d_pad - d)))
            scale_pad = (self._ones_scale() if qc.scale is None else
                         jnp.pad(qc.scale, (0, self.d_pad - d),
                                 constant_values=1.0))
            slot = dict(data=data, data_pad=data_pad, scale_pad=scale_pad,
                        bytes_per_vector=qc.bytes_per_vector)
            self._quant[precision] = slot
        return slot

    # ------------------------------------------------------------- planning
    def plan_strategies(self, lo: np.ndarray, hi: np.ndarray, *, k: int,
                        ef: int, mode: str, beam_width: int = 1,
                        precision: str = "f32"
                        ) -> Tuple[np.ndarray, np.ndarray]:
        """Host half of mesh dispatch: (strategy (Q,) int8, lens_eff (Q,)).

        ``lens_eff`` is each query's **widest shard-local clip** of its
        global rank interval — the decision must be one replicated scalar
        per query, and the widest shard is the one whose scan cost the
        traced dispatch actually pays (shards execute in lockstep)."""
        lo = np.asarray(lo, np.int64)
        hi = np.asarray(hi, np.int64)
        lens_eff = np.zeros(len(lo), np.int64)
        for s in range(self.n_shards):
            slo, shi = resolve.clip_interval(lo, hi, s * self.per, self.per)
            lens_eff = np.maximum(lens_eff, np.clip(
                shi.astype(np.int64) - slo + 1, 0, None))
        if mode == "scan":
            return np.full(len(lo), SCAN, np.int8), lens_eff
        if mode == "beam":
            return np.full(len(lo), BEAM, np.int8), lens_eff
        return (self.planner.choose_strategy_batch(lens_eff, k=k, ef=ef,
                                                   beam_width=beam_width,
                                                   precision=precision),
                lens_eff)

    # ---------------------------------------------------------------- run
    def run(self, req: SearchRequest) -> SearchResult:
        """Dispatch one request on the mesh; result ids are original corpus
        ids, already merged across shards (replicated).  With a cache
        installed, hit rows skip the mesh dispatch entirely.  A ``req.trace``
        collects plan / dispatch / stitch spans (the cross-shard scatter +
        merge run *inside* the traced body, so the host-side stitch span
        covers output conversion and cache assembly)."""
        qv = np.asarray(req.queries, np.float32)
        lo = np.asarray(req.lo, np.int64)
        hi = np.asarray(req.hi, np.int64)
        k, ef = int(req.k), max(int(req.ef), int(req.k))
        bw = int(req.beam_width)
        prec = req.precision
        tr = req.trace
        met = self.metrics
        nq = len(qv)
        if nq == 0:
            return SearchResult(np.zeros((0, k), np.int32),
                                np.zeros((0, k), np.float32),
                                {"strategy": np.zeros(0, np.int8),
                                 "scan_frac": 0.0}, trace=tr)
        if met is not None:
            met.counter("queries_total").inc(nq)
            met.counter("mesh_queries_total").inc(nq)
            met.counter(f"queries_{prec}_total").inc(nq)
        live = req.live
        cache = self.cache
        cache_info = dict(cache_enabled=cache is not None,
                          cache_hits=0, cache_misses=nq, batch_dedup=0)
        if cache is None:
            res = self._run_uncached(qv, lo, hi, k, ef, req.strategy, bw,
                                     prec, trace=tr, cache_info=cache_info,
                                     live=live)
            res.trace = tr
            return res
        # fences stores vs invalidate() / invalidate_segment("mesh")
        epoch = cache.epoch_for("mesh")
        cal_epoch = (self.planner.calibration_epoch
                     if req.strategy == "auto" else None)
        keys, hit_rows, miss, dups = cache.split(qv, lo, hi, k, ef,
                                                 req.strategy, ns="mesh",
                                                 beam_width=bw,
                                                 precision=prec,
                                                 cal_epoch=cal_epoch)
        cache_info.update(cache_hits=len(hit_rows), cache_misses=len(miss),
                          batch_dedup=len(dups))
        if met is not None:
            met.counter("cache_hit_rows_total").inc(len(hit_rows))
            met.counter("cache_miss_rows_total").inc(len(miss))
            if dups:
                met.counter("cache_dedup_rows_total").inc(len(dups))
        if len(miss) == 0:
            if tr is not None:          # fully hit: no mesh dispatch at all
                tr.add_span("dispatch", dispatched=0, ns="mesh",
                            **cache_info)
            with maybe_span(tr, "stitch", ns="mesh"):
                res = cache.assemble(nq, k, hit_rows, None, miss)
            res.trace = tr
            return res
        miss_res = self._run_uncached(qv[miss], lo[miss], hi[miss], k, ef,
                                      req.strategy, bw, prec, trace=tr,
                                      cache_info=cache_info, live=live)
        cache.store_batch([keys[i] for i in miss], miss_res, epoch=epoch,
                          cal_epoch=cal_epoch)
        if not hit_rows and not dups:
            miss_res.stats["cache_hits"] = 0
            miss_res.trace = tr
            return miss_res
        with maybe_span(tr, "stitch", ns="mesh"):
            res = cache.assemble(nq, k, hit_rows, miss_res, miss, dups)
        res.trace = tr
        return res

    def _shard_clip_widths(self, lo, hi) -> np.ndarray:
        """(S, Q) shard-local clipped interval widths — the dispatch-span
        view of how each query's global interval lands on the mesh."""
        w = []
        for s in range(self.n_shards):
            slo, shi = resolve.clip_interval(lo, hi, s * self.per, self.per)
            w.append(np.clip(shi.astype(np.int64) - slo + 1, 0, None))
        return np.stack(w)

    def _run_uncached(self, qv, lo, hi, k: int, ef: int, mode: str,
                      beam_width: int = 1, precision: str = "f32",
                      trace=None, cache_info=None, live=None) -> SearchResult:
        nq = len(qv)
        met = self.metrics
        if mode == "graph":
            if trace is not None:
                trace.add_span("plan", strategy_mode="graph", chosen="graph",
                               beam_width=beam_width)
            if met is not None:
                met.counter("graph_queries_total").inc(nq)
            with maybe_span(trace, "dispatch") as sp:
                sp.attrs.update(cache_info or {})
                sp.attrs.update(strategy_mode=mode, ns="mesh",
                                dispatched=nq, beam_width=beam_width,
                                precision=precision,
                                shard_clip_widths=self._shard_clip_widths(
                                    lo, hi) if trace is not None else None)
                ids, dists = self._call_graph(qv, lo, hi, k, ef,
                                              calibrate=False,
                                              beam_width=beam_width,
                                              precision=precision, live=live)
            with maybe_span(trace, "stitch", ns="mesh"):
                res = SearchResult(ids, dists,
                                   {"strategy": np.ones(nq, np.int8),
                                    "scan_frac": 0.0})
            return res
        if trace is None:
            strategy, lens_eff = self.plan_strategies(lo, hi, k=k, ef=ef,
                                                      mode=mode,
                                                      beam_width=beam_width,
                                                      precision=precision)
        else:
            with trace.span("plan") as psp:
                strategy, lens_eff = self.plan_strategies(
                    lo, hi, k=k, ef=ef, mode=mode, beam_width=beam_width,
                    precision=precision)
                sc, bc = self.planner.predict_costs(lens_eff, k=k, ef=ef,
                                                    beam_width=beam_width,
                                                    precision=precision)
                psp.attrs.update(strategy_mode=mode,
                                 strategy=strategy.copy(),
                                 lens_eff=lens_eff.copy(),
                                 beam_width=beam_width,
                                 precision=precision,
                                 scan_frac=float((strategy == SCAN).mean()),
                                 predicted_scan_units=sc,
                                 predicted_beam_units=bc)
        scan_idx = np.flatnonzero(strategy == SCAN)
        beam_idx = np.flatnonzero(strategy == BEAM)
        if met is not None:
            met.counter("scan_routed_total").inc(len(scan_idx))
            met.counter("beam_routed_total").inc(len(beam_idx))
        if len(scan_idx) == 0:
            # uniform-beam batch: the planned body would degenerate to the
            # graph body plus pow2 padding and a scatter — dispatch the graph
            # fn directly (same ef, same merge, bit-identical results)
            with maybe_span(trace, "dispatch") as sp:
                sp.attrs.update(cache_info or {})
                sp.attrs.update(strategy_mode=mode, ns="mesh",
                                dispatched=nq, beam_width=beam_width,
                                precision=precision,
                                uniform_beam_fast_path=True,
                                shard_clip_widths=self._shard_clip_widths(
                                    lo, hi) if trace is not None else None)
                ids, dists = self._call_graph(qv, lo, hi, k, ef,
                                              calibrate=self.calibrate,
                                              beam_width=beam_width,
                                              precision=precision, live=live)
            with maybe_span(trace, "stitch", ns="mesh"):
                res = SearchResult(ids, dists,
                                   {"strategy": strategy, "scan_frac": 0.0})
            return res
        # scan_idx is non-empty past the fast path; one shared bucket covers
        # every scan query's widest shard-local clip (never truncates)
        cap = next_pow2(self.per)
        bucket = max(bucket_for_len(
            int(ln), min_bucket=self.planner.min_bucket, max_bucket=cap)
            for ln in lens_eff[scan_idx])
        pad_s = pad_pow2(len(scan_idx))
        pad_b = pad_pow2(len(beam_idx)) if len(beam_idx) else 0
        use_live = live is not None
        key = ("planned", k, ef, bucket, pad_s, pad_b, nq, beam_width,
               precision, use_live)
        warm = key in self._fns
        fn = self._planned_fn(k=k, ef=ef, bucket=bucket, pad_s=pad_s,
                              pad_b=pad_b, nq=nq, beam_width=beam_width,
                              precision=precision, use_live=use_live)
        slot = self._quant_for(precision)
        if slot is None:
            x_scan, xq, scale = (self._scan_corpus(), self._vecs,
                                 self._ones_scale())
        else:
            x_scan, xq, scale = (slot["data_pad"], slot["data"],
                                 slot["scale_pad"])
        scan_ops = self._group_operands(qv, lo, hi, scan_idx, pad_s, nq,
                                        lane_pad=True)
        beam_ops = self._group_operands(qv, lo, hi, beam_idx, pad_b, nq,
                                        lane_pad=False)
        pad_rows = (pad_s - len(scan_idx)) + (pad_b - len(beam_idx))
        if met is not None and pad_rows:
            met.counter("pad_rows_total").inc(pad_rows)
        t0 = time.perf_counter()
        with maybe_span(trace, "dispatch") as sp:
            sp.attrs.update(cache_info or {})
            sp.attrs.update(strategy_mode=mode, ns="mesh", dispatched=nq,
                            beam_width=beam_width, warm=warm, bucket=bucket,
                            precision=precision,
                            pad_scan=pad_s, pad_beam=pad_b,
                            pad_rows=pad_rows,
                            shard_clip_widths=self._shard_clip_widths(
                                lo, hi) if trace is not None else None)
            with annotate("rnsg.mesh_planned_dispatch"):
                ids, dists, nd_g = fn(x_scan, self._vecs,
                                      self._nbrs, self._rmq, self._dist_c,
                                      self._order, self._rank0, xq, scale,
                                      self._live_shards(live),
                                      *scan_ops, *beam_ops)
                ids = np.asarray(ids)
                dists = np.asarray(dists)
        if met is not None:
            met.histogram("mesh_dispatch_ms").observe(
                (time.perf_counter() - t0) * 1e3)
        if self.calibrate and warm:
            # one fused traced step: attribute the wall time across the two
            # groups proportionally to their predicted unit costs.  Scan
            # lanes count the pow2 padding (empty windows still scan their
            # fixed-shape blocks — real work); beam lanes count only the
            # real queries (pad lanes carry empty windows and exit the
            # while_loop immediately)
            dt = time.perf_counter() - t0
            n_beam = len(beam_idx)
            self.planner.cost.observe_wall_mixed(
                window_rows(bucket, self.tb) * pad_s,
                self.planner.cost.ndist_per_ef_at(beam_width) * ef * n_beam,
                dt, pad_s, n_beam, precision=precision)
            if len(beam_idx):
                # all-gathered per-shard ndist sums: pad lanes carry empty
                # windows (ndist 0), so normalize by the real beam count —
                # this is the signal that moves the mesh path's ndist EMA
                nd_mean = float(np.asarray(nd_g).mean()) / len(beam_idx)
                self.planner.cost.update_beam(nd_mean, ef,
                                              beam_width=beam_width)
        scan_frac = len(scan_idx) / nq
        with maybe_span(trace, "stitch", ns="mesh"):
            res = SearchResult(ids, dists,
                               {"strategy": strategy,
                                "scan_frac": scan_frac})
        return res

    def _call_graph(self, qv, lo, hi, k: int, ef: int, *, calibrate: bool,
                    beam_width: int = 1, precision: str = "f32", live=None):
        """One graph-body mesh dispatch (+ optional warm-call beam
        calibration for routed uniform-beam batches: wall time and the
        all-gathered per-shard ndist feed the cost model)."""
        use_live = live is not None
        warm = ("graph", k, max(ef, k), beam_width, precision,
                use_live) in self._fns
        fn = self.graph_fn(k, ef, beam_width, precision, use_live=use_live)
        slot = self._quant_for(precision)
        xq = self._vecs if slot is None else slot["data"]
        scale = self._ones_scale() if slot is None else slot["scale_pad"]
        t0 = time.perf_counter()
        with annotate("rnsg.mesh_graph_dispatch"):
            ids, dists, nd_g = fn(self._vecs, self._nbrs, self._rmq,
                                  self._dist_c, self._order, self._rank0,
                                  xq, scale, self._live_shards(live),
                                  jnp.asarray(qv),
                                  jnp.asarray(np.asarray(lo).astype(np.int32)),
                                  jnp.asarray(np.asarray(hi).astype(np.int32)))
            ids = np.asarray(ids)
            dists = np.asarray(dists)
        if self.metrics is not None:
            self.metrics.histogram("mesh_dispatch_ms").observe(
                (time.perf_counter() - t0) * 1e3)
        if calibrate and warm:
            # both feeds normalize by the NON-EMPTY row count: forced-beam
            # batches may carry empty intervals (the local path routes
            # those to scan), which exit the while_loop immediately and
            # would bias both the wall-per-unit estimate and the ndist EMA
            # toward free
            n_real = int((np.asarray(lo) <= np.asarray(hi)).sum())
            if n_real:
                dt = time.perf_counter() - t0
                self.planner.cost.observe_wall(
                    "beam",
                    max(self.planner.cost.ndist_per_ef_at(beam_width) * ef,
                        1.0),
                    dt, n_real, precision=precision)
                nd_mean = float(np.asarray(nd_g).mean()) / n_real
                self.planner.cost.update_beam(nd_mean, ef,
                                              beam_width=beam_width)
        return ids, dists

    # ------------------------------------------------------------ operands
    def _group_operands(self, qv, lo, hi, idx, pad: int, nq: int, *,
                        lane_pad: bool):
        """One strategy group's replicated operands: queries (pow2-padded),
        global rank interval, and scatter destinations.  Pads carry empty
        windows (lo=1 > hi=0 — masked in scan, immediate exit in beam) and
        scatter into the sink row ``nq``."""
        m = len(idx)
        qd = self.d_pad if lane_pad else self.d
        g_q = np.zeros((pad, qd), np.float32)
        g_lo = np.ones(pad, np.int32)
        g_hi = np.zeros(pad, np.int32)
        dst = np.full(pad, nq, np.int32)
        if m:
            g_q[:m, :self.d] = qv[idx]
            g_lo[:m] = lo[idx]
            g_hi[:m] = hi[idx]
            dst[:m] = idx
        return (jnp.asarray(g_q), jnp.asarray(g_lo), jnp.asarray(g_hi),
                jnp.asarray(dst))

    def _scan_corpus(self):
        """Row/lane-padded per-shard corpus for the scan kernel (lazy: a
        mesh that never routes to scan skips the duplicate)."""
        if self._x_pad is None:
            per_pad = -(-self.per // self.tb) * self.tb
            self._x_pad = jnp.pad(
                self._vecs, ((0, 0), (0, per_pad - self.per),
                             (0, self.d_pad - self.d)))
        return self._x_pad

    # ---------------------------------------------------------- traced fns
    def graph_fn(self, k: int, ef: int, beam_width: int = 1,
                 precision: str = "f32", use_live: bool = False):
        """Jitted graph-strategy mesh fn (also the dry-run lowering target).
        Operands: 6 sharded index arrays + sharded ``xq`` + replicated
        ``scale`` + sharded ``live`` + replicated ``(qv, lo, hi)`` — under
        f32 pass ``vecs`` again as ``xq`` and any (d_pad,) f32 row as
        ``scale``; under ``use_live=False`` pass any (S, per) array as
        ``live`` (all ignored).  Returns (ids, dists, ndist_per_shard)."""
        key = ("graph", k, max(ef, k), beam_width, precision, use_live)
        fn = self._fns.get(key)
        if fn is None:
            body = partial(_shard_graph, k=k, ef=max(ef, k), axis=self.axis,
                           beam_width=beam_width, precision=precision,
                           use_live=use_live)
            shard, rep = P(self.axis), P()
            fn = jax.jit(jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(shard,) * 7 + (rep,) + (shard,) + (rep,) * 3,
                out_specs=(rep, rep, rep), check_vma=False))
            self._fns[key] = fn
        return fn

    def _planned_fn(self, *, k, ef, bucket, pad_s, pad_b, nq,
                    beam_width: int = 1, precision: str = "f32",
                    use_live: bool = False):
        key = ("planned", k, ef, bucket, pad_s, pad_b, nq, beam_width,
               precision, use_live)
        fn = self._fns.get(key)
        if fn is None:
            body = partial(_shard_planned, k=k, ef=ef, bucket=bucket, nq=nq,
                           has_beam=pad_b > 0, axis=self.axis,
                           beam_width=beam_width, precision=precision,
                           use_live=use_live)
            shard, rep = P(self.axis), P()
            fn = jax.jit(jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(shard,) * 8 + (rep,) + (shard,) + (rep,) * 8,
                out_specs=(rep, rep, rep), check_vma=False))
            self._fns[key] = fn
        return fn
