"""Plan, dispatch and assembly: one strategy-routed execution layer.

``SearchSubstrate`` owns the entire query path for one attribute-sorted
corpus slice (a whole index, or one shard of a distributed one), as a run
of leaf stages (``repro.obs.stage``; each lands in ``stage_<name>_ms`` and,
under a profiler session, on the device trace as ``rnsg.<name>``):

* ``resolve``  — attribute ranges -> rank intervals (``repro.search.resolve``,
                 run by the caller);
* ``plan``     — when a ``SearchCache`` is installed, each request is split
                 into hit rows (served from memory, no device work), unique
                 miss rows (executed), and intra-batch duplicates of a miss
                 (executed once, fanned back out); ``graph`` runs the
                 paper's beam search over the full batch, while
                 ``auto``/``scan``/``beam`` go through the adaptive planner,
                 which partitions the batch into fixed-shape jit dispatches
                 (fused Pallas ``range_scan`` | bucketed beam search);
* per partition, ``scan_prep``/``beam_prep`` (host arrays, padding, device
  copies, entry selection), ``scan_dispatch``/``beam_dispatch``/
  ``graph_beam_dispatch`` (the enqueue) and ``scan_block``/``beam_block``
  (the wait for the device and the copy back);
* ``assemble`` — partition results land back in request order, rank ids
                 are remapped to original corpus ids, per-query stats
                 (hops / ndist / strategy) are assembled, the cache stores
                 and assembles, and the dispatch histograms and counters
                 are fed.

Dispatch is **asynchronous at the substrate boundary**: ``dispatch(req)``
enqueues all device work (jax async dispatch) and returns a
``PendingSearch`` whose ``result()`` blocks and assembles.  ``run`` is the
synchronous spelling (``dispatch(..., defer=False).result()``); the
distributed local path dispatches every shard before blocking any of them,
overlapping the per-shard device queues.

Scan partitions pad with empty windows (masked, ~free); beam partitions pad
by duplicating the last real query (a duplicate lane adds no extra
``while_loop`` iterations under vmap).

``MeshSubstrate`` is the ``shard_map`` twin for multi-device serving: the
router runs **host-side** over the globally resolved rank intervals, clipped
per shard, and each shard routes its own clip (scan up to the planner's
ceiling, beam beyond).  The (shard, query) pairs of a batch, padded to
``mesh_rows`` rows, form one program per scan bucket and one beam program,
each scattering its shard's results into a per-shard request-order buffer;
one last program ``all_gather``s the buffers and takes the cross-shard
top-k.  See docs/distributed.md.
"""
from __future__ import annotations

import time
from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.beam import beam_search_batch, rerank_pool
from repro.kernels.ops import range_scan
from repro.kernels.quantize import (QuantizedCorpus, quantize_corpus,
                                    rerank_depth)
from repro.obs.metrics import MetricsRegistry
from repro.obs.stages import stage
from repro.planner.bucketing import (ROW_TILE, bucket_for_len, buckets_np,
                                     next_pow2, pad_pow2, window_rows)
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
    dispatch); ``result()`` blocks on the outputs, assembles, and returns
    the ``SearchResult``.  Idempotent — repeated calls
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
        self.planner = planner or QueryPlanner(max(n, 1))
        self._x_pad = None          # padded scan copy, built on first scan
        self._quant: Dict[str, dict] = {}   # precision -> quantized slots
        self._live_memo = None      # (mask, (n,) bool dev, (1,n_pad) i32 dev)

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
        """Dispatch one request synchronously and assemble the result."""
        return self.dispatch(req, defer=False).result()

    def dispatch(self, req: SearchRequest, *, defer: bool = True,
                 q_digests=None) -> PendingSearch:
        """Enqueue one request's device work and return a ``PendingSearch``.

        ``defer=True`` (the async path) enqueues every partition before any
        block; ``defer=False`` blocks each partition before dispatching the
        next.  Cache hits are resolved here — a
        fully-hit request performs no device work at all.  ``q_digests``
        are optional precomputed ``hash_query`` values (the distributed
        local path hashes each query once, not once per shard).

        Every step runs in a leaf stage (``repro.obs.stage``): ``plan``
        (counters, cache split, planner), then per partition ``*_prep``
        (host arrays, padding, copies), ``*_dispatch`` (the enqueue) and
        ``*_block`` (device wait and copy back), then ``assemble``
        (request-order scatter, id remap, cache store and assembly, the
        dispatch histograms and counters).  A ``req.trace``
        collects the same spans; the installed ``MetricsRegistry`` (when
        any) receives the stage histograms, routed counts, cache outcomes
        and pad waste."""
        tr = req.trace
        met = self.metrics
        split = None
        plan = None
        with stage("plan", met, tr) as sp:
            qv = np.asarray(req.queries, np.float32)
            lo = np.asarray(req.lo, np.int64)
            hi = np.asarray(req.hi, np.int64)
            k, ef, bw = int(req.k), int(req.ef), int(req.beam_width)
            prec, mode = req.precision, req.strategy
            nq = len(qv)
            cache = self.cache
            if met is not None and nq:
                met.counter("queries_total").inc(nq)
                met.counter(f"queries_{prec}_total").inc(nq)
            cache_info = dict(cache_enabled=cache is not None,
                              cache_hits=0, cache_misses=nq, batch_dedup=0)
            if cache is not None and nq:
                # (global, segment) epoch pair: fences stores vs both
                # invalidate() and invalidate_segment(self.cache_ns) — the
                # streaming layer bumps the segment epoch on every
                # tombstone change / compaction
                epoch = cache.epoch_for(self.cache_ns)
                keys, hit_rows, miss, dups = cache.split(
                    qv, lo, hi, k, ef, mode, req.use_kernel,
                    ns=self.cache_ns, digests=q_digests, beam_width=bw,
                    precision=prec)
                cache_info.update(cache_hits=len(hit_rows),
                                  cache_misses=len(miss),
                                  batch_dedup=len(dups))
                if met is not None:
                    met.counter("cache_hit_rows_total").inc(len(hit_rows))
                    met.counter("cache_miss_rows_total").inc(len(miss))
                    if dups:
                        met.counter("cache_dedup_rows_total").inc(len(dups))
                split = (epoch, keys, hit_rows, miss, dups)
                qv, lo, hi = qv[miss], lo[miss], hi[miss]
            work = split is None or len(qv) > 0
            sp.attrs.update(cache_info, strategy_mode=mode,
                            use_kernel=req.use_kernel, beam_width=bw,
                            ns=self.cache_ns, precision=prec,
                            dispatched=len(qv) if work else 0,
                            deferred=defer)
            if work and mode == "graph":
                sp.attrs["chosen"] = "graph"
                if met is not None and len(qv):
                    met.counter("graph_queries_total").inc(len(qv))
            elif work:
                plan = self._plan(lo, hi, k, ef, mode, tr, sp)
        if not work:                    # fully hit: no device work at all
            blocks = []
        elif plan is None:
            blocks = [self._dispatch_graph(qv, lo, hi, k, ef,
                                           req.use_kernel, bw, prec,
                                           live=req.live, trace=tr)]
        else:
            blocks = self._dispatch_planned(qv, lo, hi, plan, k, ef, mode,
                                            req.use_kernel, defer, bw, prec,
                                            trace=tr, live=req.live)

        def finalize() -> SearchResult:
            outs = [blk() for blk in blocks]    # blocks not yet taken
            with stage("assemble", met, tr):
                res = None
                if work:
                    ids, dists, stats = (self._scatter(plan, outs, len(qv), k)
                                         if plan is not None
                                         else self._graph_out(outs[0]))
                    res = SearchResult(resolve.remap_ids(self.order, ids),
                                       dists, stats)
                if split is not None:
                    epoch, keys, hit_rows, miss, dups = split
                    if res is not None:
                        cache.store_batch([keys[i] for i in miss], res,
                                          epoch=epoch)
                    if hit_rows or dups or res is None:
                        res = cache.assemble(nq, k, hit_rows, res, miss,
                                             dups)
                    else:
                        res.stats["cache_hits"] = 0
                res.trace = tr
            return res
        return PendingSearch(finalize)

    def _plan(self, lo, hi, k: int, ef: int, mode: str, trace, sp):
        """Planner call of the ``plan`` stage: partitions the batch and
        counts routed rows and pad waste."""
        plan = self.planner.plan_batch(lo, hi, k=k, ef=ef, mode=mode)
        if trace is not None:
            sp.attrs.update(strategy=plan.strategy.copy(),
                            scan_frac=plan.scan_frac,
                            partitions=[p.signature for p in plan.partitions])
        q = len(lo)
        pad_rows = sum(p.pad_q - len(p.indices) for p in plan.partitions)
        met = self.metrics
        if met is not None and q:
            n_scan = int((plan.strategy == SCAN).sum())
            met.counter("scan_routed_total").inc(n_scan)
            met.counter("beam_routed_total").inc(q - n_scan)
            if pad_rows:
                met.counter("pad_rows_total").inc(pad_rows)
        sp.attrs["pad_rows"] = pad_rows
        return plan

    @staticmethod
    def _scatter(plan, outs, q: int, k: int):
        """Partition outputs back into request order (``assemble``), after
        each partition's histograms and counters."""
        out_ids = np.full((q, k), -1, np.int32)
        out_d = np.full((q, k), INF, np.float32)
        hops = np.zeros(q, np.int32)
        ndist = np.zeros(q, np.int32)
        for part, (ids_p, d_p, extra, book) in zip(plan.partitions, outs):
            book()
            idx = part.indices  # never empty (guarded at plan time)
            if part.kind == "scan":
                ndist[idx] = extra
            else:
                hops[idx] = extra["hops"]
                ndist[idx] = extra["ndist"]
            out_ids[idx] = ids_p
            out_d[idx] = d_p
        stats = {"hops": hops, "ndist": ndist,
                 "strategy": plan.strategy, "scan_frac": plan.scan_frac}
        return out_ids, out_d, stats

    @staticmethod
    def _graph_out(out):
        ids, dists, st, book = out
        book()
        st["strategy"] = np.ones(len(ids), np.int8)       # all graph/beam
        st["scan_frac"] = 0.0
        return ids, dists, st

    # ------------------------------------------------------ graph strategy
    def _dispatch_graph(self, qv, lo, hi, k, ef, use_kernel, beam_width=1,
                        precision="f32", live=None, trace=None):
        """The paper's path: one beam-search dispatch over the full batch.
        Non-f32 precisions score the traversal against the quantized corpus
        and rerank the final pool in f32 inside ``beam_search_batch``.
        Returns the partition's block closure (see ``_dispatch_scan``)."""
        met = self.metrics
        with stage("beam_prep", met, trace):
            qj = jnp.asarray(qv, jnp.float32)
            lo_j = jnp.asarray(lo)
            hi_j = jnp.asarray(hi)
            entry = resolve.select_entry(self._rmq, self._dist_c, lo_j, hi_j,
                                         self.n)
            slot = self._quant_for(precision)
            quant = None if slot is None else (slot["data"], slot["scale"])
            live_b, _ = self._live_ops(live)
            t0 = time.perf_counter()
        with stage("graph_beam_dispatch", met, trace):
            out = list(beam_search_batch(
                self._vecs, self._nbrs, qj, lo_j, hi_j, entry,
                k=k, ef=max(ef, k), use_kernel=use_kernel,
                beam_width=beam_width, quant=quant, live=live_b))
            del qj, lo_j, hi_j, entry           # see _dispatch_scan

        def block():
            with stage("beam_block", met, trace):
                ids, dists, st = out
                out.clear()
                st_h = jax.tree.map(np.asarray, st)
                ids_h, d_h = np.asarray(ids), np.asarray(dists)
                del ids, dists, st
            dt = time.perf_counter() - t0

            def book():
                if met is not None:
                    met.histogram("graph_dispatch_ms").observe(dt * 1e3)
                    _book_visited(met, st_h)
            return ids_h, d_h, st_h, book
        return block

    # ---------------------------------------------------- planned strategies
    def _dispatch_planned(self, qv, lo, hi, plan, k, ef, mode, use_kernel,
                          defer: bool, beam_width: int = 1,
                          precision: str = "f32", trace=None, live=None):
        """Dispatch each fixed-shape partition of a plan.  ``defer=False``
        blocks each partition before dispatching the next; ``defer=True``
        enqueues them all.  Returns one block closure per partition, already
        taken (memoized) when not deferred."""
        blocks = []
        for part in plan.partitions:
            if part.kind == "scan":
                blk = self._dispatch_scan(qv, lo, hi, part.indices,
                                          part.param, part.pad_q, k, ef,
                                          precision=precision, trace=trace,
                                          live=live)
            else:
                blk = self._dispatch_beam(qv, lo, hi, part.indices,
                                          part.param, part.pad_q, k,
                                          use_kernel=use_kernel,
                                          beam_width=beam_width,
                                          precision=precision, live=live,
                                          trace=trace)
            if not defer:
                blk = (lambda v: lambda: v)(blk())
            blocks.append(blk)
        return blocks

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
                       k: int, ef: int, *, precision: str = "f32",
                       trace=None, live=None):
        """Enqueue one scan partition; returns its block closure.  The
        closure waits for the outputs (``scan_block``) and returns
        ``(ids, dists, units, book)``, where ``book`` feeds the
        ``scan_dispatch_ms`` histogram (enqueue to host result) — run in
        ``assemble``."""
        met = self.metrics
        with stage("scan_prep", met, trace):
            nq = len(idx)
            starts = np.zeros(pad_q, np.int32)
            lens = np.zeros(pad_q, np.int32)
            starts[:nq] = lo[idx]
            lens[:nq] = np.clip(hi[idx] - lo[idx] + 1, 0, bucket)
            qp = np.zeros((pad_q, self.d_pad), np.float32)
            qp[:nq, :self.d] = qv[idx]
            slot = self._quant_for(precision)
            _, live_row = self._live_ops(live)
            t0 = time.perf_counter()
            x = (self._scan_corpus() if slot is None
                 else slot["data_pad"])
            starts_j, lens_j, qp_j = (jnp.asarray(starts), jnp.asarray(lens),
                                      jnp.asarray(qp))
        rq = 0
        with stage("scan_dispatch", met, trace):
            if slot is None:
                out = list(range_scan(x, starts_j, lens_j, qp_j,
                                      bucket=bucket, k=k, live=live_row))
            else:
                # quantized scan keeps rerank_depth survivors (clamped to
                # the slice via lens ≤ bucket masking; tombstoned rows are
                # masked here, so the survivor pool is live-only) ...
                rq = rerank_depth(k, ef, cap=self.tb)
                ids_q, _ = range_scan(x, starts_j, lens_j, qp_j,
                                      bucket=bucket, k=rq,
                                      scale=slot["scale_pad"],
                                      live=live_row)
            # device arrays are released inside the stage that used them:
            # their teardown is host work too, and must not fall between
            # stages
            del starts_j, lens_j, qp_j
        if rq:
            # ... then a fused f32 rescore of those ids restores the exact
            # top-k (candidates rank-sorted so ties break exactly as the
            # oracle's)
            with stage("rerank", met, trace, precision=precision,
                       rows=pad_q * rq, k=k):
                out = list(rerank_pool(self._vecs, ids_q,
                                       jnp.asarray(qp[:, :self.d]), k,
                                       use_kernel=True))
                del ids_q
        units = window_rows(bucket, self.tb)

        def block():
            with stage("scan_block", met, trace):
                ids, d = out
                out.clear()
                ids_h = np.asarray(ids)[:nq]
                d_h = np.asarray(d)[:nq]
                del ids, d
            dt = time.perf_counter() - t0

            def book():
                if met is not None:
                    met.histogram("scan_dispatch_ms").observe(dt * 1e3)
                    if rq:
                        met.counter("rerank_rows_total").inc(pad_q * rq)
            return ids_h, d_h, units, book
        return block

    def _dispatch_beam(self, qv, lo, hi, idx, ef: int, pad_q: int, k: int, *,
                       use_kernel: bool = False, beam_width: int = 1,
                       precision: str = "f32", live=None, trace=None):
        """Enqueue one beam partition; returns its block closure
        (``beam_block``), as ``_dispatch_scan`` does."""
        nq = len(idx)
        if nq == 0:                 # empty partition: nothing to dispatch
            empty = np.zeros(0, np.int32)
            return lambda: (np.zeros((0, k), np.int32),
                            np.zeros((0, k), np.float32),
                            {"hops": empty, "ndist": empty,
                             "evictions": empty}, _no_book)
        met = self.metrics
        with stage("beam_prep", met, trace):
            pad = np.concatenate([idx, np.repeat(idx[-1:], pad_q - nq)])
            lo_j = jnp.asarray(
                np.clip(lo[pad], 0, self.n - 1).astype(np.int32))
            hi_j = jnp.asarray(
                np.clip(hi[pad], 0, self.n - 1).astype(np.int32))
            entry = resolve.select_entry(self._rmq, self._dist_c, lo_j, hi_j,
                                         self.n)
            qp = jnp.asarray(qv[pad])
            slot = self._quant_for(precision)
            quant = None if slot is None else (slot["data"], slot["scale"])
            live_b, _ = self._live_ops(live)
            t0 = time.perf_counter()
            lo_p = jnp.asarray(lo[pad].astype(np.int32))
            hi_p = jnp.asarray(hi[pad].astype(np.int32))
        with stage("beam_dispatch", met, trace):
            out = list(beam_search_batch(
                self._vecs, self._nbrs, qp, lo_p, hi_p,
                entry, k=k, ef=max(ef, k), use_kernel=use_kernel,
                beam_width=beam_width, quant=quant, live=live_b))
            del qp, lo_p, hi_p, entry, lo_j, hi_j   # see _dispatch_scan

        def block():
            with stage("beam_block", met, trace):
                ids, d, st = out
                out.clear()
                ids_h = np.asarray(ids)[:nq]
                d_h = np.asarray(d)[:nq]
                st_h = {kk: np.asarray(vv)[:nq] for kk, vv in st.items()}
                del ids, d, st
            dt = time.perf_counter() - t0

            def book():
                if met is not None:
                    met.histogram("beam_dispatch_ms").observe(dt * 1e3)
                    _book_visited(met, st_h)      # real lanes only
            return ids_h, d_h, st_h, book
        return block

    # ------------------------------------------------- legacy sync wrapper
    def _run_beam(self, qv, lo, hi, idx, ef: int, pad_q: int, k: int, *,
                  use_kernel: bool = False):
        """Synchronous beam partition dispatch (kept for the empty-partition
        regression test and any external caller of the pre-async API)."""
        ids, d, st, book = self._dispatch_beam(
            qv, lo, hi, np.asarray(idx, np.int64), ef, pad_q, k,
            use_kernel=use_kernel)()
        book()
        return ids, d, st


def _no_book():
    """Bookkeeping of a partition that dispatched nothing."""


def _book_visited(met: MetricsRegistry, st: dict) -> None:
    """The beam's visited-table counters: ids inserted (every scored
    neighbor is inserted once) and ids the table forgot."""
    met.counter("beam_visited_inserts_total").inc(int(st["ndist"].sum()))
    met.counter("beam_visited_evictions_total").inc(
        int(st["evictions"].sum()))


# ======================================================================
# Mesh path: traced per-device bodies + the host-planned mesh substrate.
# ======================================================================
#: floor of a mesh call's padded batch: every batch of up to this many rows
#: pads to the same row count, so the batch size enters no compile key
MESH_ROWS = 64
#: route of a (shard, query) pair whose shard-local clip is empty
NO_CLIP = -1


def mesh_rows(nq: int) -> int:
    """Rows of a mesh call's padded batch: a power of two, at least
    ``MESH_ROWS``."""
    return max(next_pow2(nq), MESH_ROWS)


def shard_routes(lo, hi, *, per: int, n_shards: int, planner: QueryPlanner,
                 k: int, mode: str):
    """Per-shard routing of a batch of global rank intervals.

    Returns ``(routes, slo, shi)``, each (S, Q): ``routes`` holds ``SCAN``,
    ``BEAM`` or ``NO_CLIP`` per (shard, query) pair, ``slo``/``shi`` the
    shard-local clip.  Under ``auto`` each shard routes its own clip by the
    planner's ceiling (scan at most ``planner.max_scan_len`` rows, beam
    longer ones), so a window that straddles two shards may be scanned on
    one and beamed on the other; ``scan``/``beam`` force every non-empty
    clip.  An empty clip dispatches nothing."""
    rank0 = np.arange(n_shards, dtype=np.int64)[:, None] * per
    slo, shi = resolve.clip_interval(np.asarray(lo, np.int64)[None, :],
                                     np.asarray(hi, np.int64)[None, :],
                                     rank0, per)
    lens = np.clip(shi.astype(np.int64) - slo + 1, 0, None)
    if mode == "scan":
        scan = np.ones(lens.shape, bool)
    elif mode == "beam":
        scan = np.zeros(lens.shape, bool)
    else:
        scan = planner.choose_strategy_batch(
            lens.ravel(), k=k).reshape(lens.shape) == SCAN
    routes = np.where(lens > 0, np.where(scan, SCAN, BEAM), NO_CLIP)
    return routes.astype(np.int8), slo, shi


def reported_strategy(routes: np.ndarray) -> np.ndarray:
    """(Q,) strategy of each merged answer: ``SCAN`` (exact) when every
    shard with a non-empty clip scanned it, else ``BEAM``."""
    return np.where((routes == BEAM).any(axis=0), BEAM, SCAN).astype(np.int8)


def _group_rows(sel: np.ndarray, a: np.ndarray, b: np.ndarray, rows: int,
                fill: Tuple[int, int]) -> np.ndarray:
    """(S, 3, pad) int32 operand of one group program: per shard, the
    request positions of its selected pairs (``dst``) and their (a, b)
    window, padded to ``pad_pow2`` of the largest shard's count with
    ``fill`` windows that scatter into the sink row ``rows``."""
    pad = pad_pow2(int(sel.sum(axis=1).max()))
    ops = np.empty((len(sel), 3, pad), np.int32)
    ops[:, 0], ops[:, 1], ops[:, 2] = rows, fill[0], fill[1]
    for s in range(len(sel)):
        q = np.flatnonzero(sel[s])
        ops[s, 0, :len(q)] = q
        ops[s, 1, :len(q)] = a[s, q]
        ops[s, 2, :len(q)] = b[s, q]
    return ops


def _scatter(buf_i, buf_d, dst, order, ids, dists):
    """One group's shard-local results into this shard's request-order
    buffer (pad rows land in the sink row, the last)."""
    dists = jnp.where(ids >= 0, dists, jnp.inf)
    return (buf_i.at[0, dst].set(resolve.remap_ids_jax(order, ids)),
            buf_d.at[0, dst].set(dists))


def _shard_scan(x_scan, vecs, order, scale, live, qall, ops, buf_i, buf_d,
                *, k: int, ef: int, bucket: int, precision: str,
                use_live: bool):
    """Per-device body of one scan group: this shard's clips of one pow2
    bucket through the ``range_scan`` kernel, scattered into its buffer.

    ``x_scan`` is the row/lane-padded shard corpus, quantized under a
    non-f32 ``precision`` (then ``scale`` is the replicated (d_pad,)
    dequant row and the ``rerank_depth`` survivors are rescored against the
    f32 ``vecs``, so scan rows leave exact).  ``live`` is the sharded
    (1, per) liveness mask, untouched under ``use_live=False``."""
    x_scan, vecs, order = x_scan[0], vecs[0], order[0]
    n, d = vecs.shape
    dst, starts, lens = ops[0, 0], ops[0, 1], ops[0, 2]
    q = qall[dst]
    live_row = None
    if use_live:
        live_row = jnp.pad(live[0].astype(jnp.int32),
                           (0, x_scan.shape[0] - n))[None, :]
    if precision == "f32":
        ids, dists = range_scan(x_scan, starts, lens, q, bucket=bucket, k=k,
                                n_valid=n, live=live_row)
    else:
        ids_q, _ = range_scan(x_scan, starts, lens, q, bucket=bucket,
                              k=rerank_depth(k, ef, cap=ROW_TILE), n_valid=n,
                              scale=scale if precision == "int8" else None,
                              live=live_row)
        ids, dists = rerank_pool(vecs, ids_q, q[:, :d], k, use_kernel=False)
    return _scatter(buf_i, buf_d, dst, order, ids, dists)


def _shard_beam(vecs, nbrs, rmq, dist_c, order, xq, scale, live, qall, ops,
                buf_i, buf_d, *, k: int, ef: int, beam_width: int,
                precision: str, use_live: bool):
    """Per-device body of the beam group: this shard's beam-routed clips
    through ``beam_search_batch`` (entry by RMQ over the clip), scattered
    into its buffer.  ``xq``/``scale`` are the quantized scoring operands
    (ignored under f32); quantized traversals rerank their pool in f32."""
    vecs, nbrs = vecs[0], nbrs[0]
    rmq, dist_c, order = rmq[0], dist_c[0], order[0]
    n, d = vecs.shape
    dst, lo, hi = ops[0, 0], ops[0, 1], ops[0, 2]
    quant = None if precision == "f32" else (
        xq[0], scale[:d] if precision == "int8" else None)
    entry = resolve.select_entry(rmq, dist_c, lo, hi, n)
    ids, dists, _ = beam_search_batch(vecs, nbrs, qall[dst, :d], lo, hi,
                                      entry, k=k, ef=ef,
                                      beam_width=beam_width, quant=quant,
                                      live=live[0] if use_live else None)
    return _scatter(buf_i, buf_d, dst, order, ids, dists)


def _shard_merge(buf_i, buf_d, *, k: int, axis: str):
    """Per-device merge: every shard's request-order buffer (sink row
    dropped) gathered across the mesh, then the cross-shard top-k."""
    ids = jax.lax.all_gather(buf_i[0, :-1], axis)          # (S, rows, k)
    dists = jax.lax.all_gather(buf_d[0, :-1], axis)
    return merge_topk(ids, dists, k)


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
    filters tombstoned candidates out of its final pool."""
    vecs, nbrs = vecs[0], nbrs[0]
    rmq, dist_c, order = rmq[0], dist_c[0], order[0]
    n, d = vecs.shape
    if precision == "f32":
        quant = None
    else:
        quant = (xq[0], scale[:d] if precision == "int8" else None)
    slo, shi = resolve.clip_interval_jax(lo, hi, rank0[0], n)
    entry = resolve.select_entry(rmq, dist_c, slo, shi, n)
    ids, dists, _ = beam_search_batch(vecs, nbrs, qv, slo, shi, entry,
                                      k=k, ef=ef, beam_width=beam_width,
                                      quant=quant,
                                      live=live[0] if use_live else None)
    orig = resolve.remap_ids_jax(order, ids)
    dists = jnp.where(ids >= 0, dists, jnp.inf)
    ids_g = jax.lax.all_gather(orig, axis)               # (S, Q, k)
    ds_g = jax.lax.all_gather(dists, axis)
    return merge_topk(ids_g, ds_g, k)


#: each mesh program's per-device body and the layout of its operands and
#: of its outputs: ``s`` sharded along the mesh axis, ``r`` replicated
_PROGRAMS = {
    "scan": (_shard_scan, "sssrsrsss", "ss"),
    "beam": (_shard_beam, "ssssssrsrsss", "ss"),
    "merge": (_shard_merge, "ss", "rr"),
    "graph": (_shard_graph, "sssssssrsrrr", "rr"),
}


def mesh_program(mesh, axis: str, kind: str, **static):
    """The jitted ``shard_map`` program of one body of ``_PROGRAMS``, with
    its static arguments (bodies that gather across the mesh get
    ``axis``)."""
    body, ins, outs = _PROGRAMS[kind]
    spec = {"s": P(axis), "r": P()}
    if kind in ("merge", "graph"):
        static["axis"] = axis
    return jax.jit(jax.shard_map(
        partial(body, **static), mesh=mesh,
        in_specs=tuple(spec[c] for c in ins),
        out_specs=tuple(spec[c] for c in outs), check_vma=False))


class MeshSubstrate:
    """Mesh-path twin of ``SearchSubstrate``: host planning, traced dispatch.

    The router is host-side policy and cannot run inside a traced
    ``shard_map`` body, so each batch is planned before anything is traced.
    Every step is a leaf stage on the installed registry:

    * ``plan``          — ``shard_routes``: each global rank interval is
                          clipped to every shard, and each shard routes its
                          own clip by the planner's ceiling (scan up to
                          ``max_scan_len`` rows, beam longer ones; an empty
                          clip dispatches nothing).  An answer reports
                          ``SCAN`` only when every shard with a non-empty
                          clip scanned it;
    * ``mesh_prep``     — the batch pads to ``mesh_rows(Q)`` rows; scan
                          pairs group by pow2 bucket, beam pairs form one
                          group, each padded per shard to ``pad_pow2`` of
                          its largest shard's count (pad rows carry empty
                          windows and scatter into a sink row); one
                          ``device_put`` uploads the replicated queries and
                          every group's sharded (dst, lo, hi) rows;
    * ``mesh_dispatch`` — one program per group scatters its shard's results
                          into a per-shard request-order buffer, then one
                          program ``all_gather``s the buffers and
                          ``merge_topk``s them: a query scanned on one shard
                          and beamed on another is merged, never
                          overwritten;
    * ``mesh_block``    — the wait for the merged result and its copy back;
    * ``assemble``      — result and cache assembly.

    Programs are keyed by ``(bucket, pad, rows)`` (scan), ``(pad, rows)``
    (beam) and ``rows`` (merge), with k, ef, width, precision and liveness.
    Every batch of up to ``MESH_ROWS`` queries pads to the same ``rows``, so
    the batch size enters no key, and ``warm_up`` compiles every program a
    batch can reach.  The ``graph`` strategy runs the paper's one-program
    beam over the padded batch (``graph_fn``).
    """

    def __init__(self, mesh, axis: str, vecs, nbrs, rmq, dist_c, order,
                 rank0, *, planner: Optional[QueryPlanner] = None,
                 cache: Optional[SearchCache] = None,
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
        self.planner = planner or QueryPlanner(max(per, 1))
        self.cache = cache
        self.metrics = metrics      # optional MetricsRegistry (obs layer)
        self._x_pad = None          # padded scan corpus, built on first scan
        self._quant: Dict[str, dict] = {}   # precision -> quantized slots
        self._ones = None           # dummy replicated scale row (f32/bf16)
        self._live_memo = None      # (mask, (S, per) bool device copy)
        self._live_ones = None      # dummy all-live mask (uniform operands)
        self._empty: Dict[Tuple[int, int], tuple] = {}   # (rows, k) buffers
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
                        mode: str) -> Tuple[np.ndarray, np.ndarray]:
        """Host half of mesh dispatch: (reported strategy (Q,) int8,
        per-shard routes (S, Q) int8) — see ``shard_routes``."""
        routes, _, _ = shard_routes(lo, hi, per=self.per,
                                    n_shards=self.n_shards,
                                    planner=self.planner, k=k, mode=mode)
        return reported_strategy(routes), routes

    # ---------------------------------------------------------------- run
    def run(self, req: SearchRequest) -> SearchResult:
        """Dispatch one request on the mesh; result ids are original corpus
        ids, already merged across shards (replicated).  With a cache
        installed, hit rows skip the mesh dispatch entirely.  Stages: see
        the class docstring."""
        qv = np.asarray(req.queries, np.float32)
        lo = np.asarray(req.lo, np.int64)
        hi = np.asarray(req.hi, np.int64)
        k, ef = int(req.k), max(int(req.ef), int(req.k))
        bw = int(req.beam_width)
        prec, mode = req.precision, req.strategy
        tr = req.trace
        met = self.metrics
        nq = len(qv)
        if nq == 0:
            return SearchResult(np.zeros((0, k), np.int32),
                                np.zeros((0, k), np.float32),
                                {"strategy": np.zeros(0, np.int8),
                                 "scan_frac": 0.0}, trace=tr)
        cache = self.cache
        split = None
        route = None
        with stage("plan", met, tr) as sp:
            if met is not None:
                met.counter("queries_total").inc(nq)
                met.counter("mesh_queries_total").inc(nq)
                met.counter(f"queries_{prec}_total").inc(nq)
            cache_info = dict(cache_enabled=cache is not None,
                              cache_hits=0, cache_misses=nq, batch_dedup=0)
            if cache is not None:
                # fences stores vs invalidate() / invalidate_segment("mesh")
                epoch = cache.epoch_for("mesh")
                keys, hit_rows, miss, dups = cache.split(
                    qv, lo, hi, k, ef, mode, ns="mesh", beam_width=bw,
                    precision=prec)
                cache_info.update(cache_hits=len(hit_rows),
                                  cache_misses=len(miss),
                                  batch_dedup=len(dups))
                if met is not None:
                    met.counter("cache_hit_rows_total").inc(len(hit_rows))
                    met.counter("cache_miss_rows_total").inc(len(miss))
                    if dups:
                        met.counter("cache_dedup_rows_total").inc(len(dups))
                split = (epoch, keys, hit_rows, miss, dups)
                qv, lo, hi = qv[miss], lo[miss], hi[miss]
            sp.attrs.update(cache_info, strategy_mode=mode, ns="mesh",
                            dispatched=len(qv), beam_width=bw,
                            precision=prec)
            if len(qv):
                route = self._route(lo, hi, k, mode, tr, sp)
        if route is not None:
            ids, dists, stats = self._execute(qv, lo, hi, k, ef, bw, prec,
                                              route, tr, req.live)
        with stage("assemble", met, tr):
            res = None if route is None else SearchResult(ids, dists, stats)
            if split is not None:
                epoch, keys, hit_rows, miss, dups = split
                if res is not None:
                    cache.store_batch([keys[i] for i in miss], res,
                                      epoch=epoch)
                if hit_rows or dups or res is None:
                    res = cache.assemble(nq, k, hit_rows, res, miss, dups)
                else:
                    res.stats["cache_hits"] = 0
            res.trace = tr
        return res

    def _route(self, lo, hi, k: int, mode: str, trace, sp):
        """Routing of the ``plan`` stage: ``"graph"`` for the graph
        strategy, else (reported strategy, routes, slo, shi) from
        ``shard_routes``; counts queries by reported strategy and
        (query, shard) pairs by route."""
        met = self.metrics
        if mode == "graph":
            sp.attrs["chosen"] = "graph"
            if met is not None:
                met.counter("graph_queries_total").inc(len(lo))
            return "graph"
        routes, slo, shi = shard_routes(lo, hi, per=self.per,
                                        n_shards=self.n_shards,
                                        planner=self.planner, k=k, mode=mode)
        strategy = reported_strategy(routes)
        if trace is not None:
            sp.attrs.update(strategy=strategy.copy(), shard_routes=routes,
                            shard_clip_widths=np.clip(
                                shi.astype(np.int64) - slo + 1, 0, None),
                            scan_frac=float((strategy == SCAN).mean()))
        if met is not None:
            n_scan = int((strategy == SCAN).sum())
            met.counter("scan_routed_total").inc(n_scan)
            met.counter("beam_routed_total").inc(len(lo) - n_scan)
            for name, code in (("scan", SCAN), ("beam", BEAM)):
                met.counter(f"mesh_shard_{name}_total").inc(
                    int((routes == code).sum()))
        return strategy, routes, slo, shi

    def _execute(self, qv, lo, hi, k: int, ef: int, beam_width: int,
                 precision: str, route, trace, live):
        """Run one routed batch on the mesh (``mesh_prep``,
        ``mesh_dispatch``, ``mesh_block``): (ids, dists, stats)."""
        nq = len(qv)
        rows = mesh_rows(nq)
        met = self.metrics
        use_live = live is not None
        shard = NamedSharding(self.mesh, P(self.axis))
        rep = NamedSharding(self.mesh, P())
        with stage("mesh_prep", met, trace) as sp:
            if route == "graph":
                qp = np.zeros((rows, self.d), np.float32)
                g_lo = np.ones(rows, np.int32)      # pad rows: empty windows
                g_hi = np.zeros(rows, np.int32)
                qp[:nq], g_lo[:nq], g_hi[:nq] = qv, lo, hi
                host, groups = [qp, g_lo, g_hi], []
                dispatched = self.n_shards * rows
                real = self.n_shards * nq
            else:
                strategy, routes, slo, shi = route
                qall = np.zeros((rows + 1, self.d_pad), np.float32)
                qall[:nq, :self.d] = qv
                groups = self._groups(routes, slo, shi, rows)
                host = [qall] + [ops for _, ops in groups]
                # every group program runs its pad rows on every shard, and
                # the merge gathers ``rows`` from each
                dispatched = self.n_shards * (
                    sum(ops.shape[2] for _, ops in groups) + rows)
                real = int((routes != NO_CLIP).sum()) + self.n_shards * nq
            if met is not None:
                met.counter("mesh_rows_total").inc(dispatched)
                met.counter("mesh_pad_rows_total").inc(dispatched - real)
            sp.attrs.update(rows=rows, pad_rows=dispatched - real,
                            groups=[(b, ops.shape[2]) for b, ops in groups])
            dev = jax.device_put(host, [rep] * (len(host) - len(groups))
                                 + [shard] * len(groups))
            live_d = self._live_shards(live)
            slot = self._quant_for(precision)
        with stage("mesh_dispatch", met, trace):
            if route == "graph":
                xq = self._vecs if slot is None else slot["data"]
                scale = (self._ones_scale() if slot is None
                         else slot["scale_pad"])
                out = self.graph_fn(k, ef, beam_width, precision,
                                    use_live=use_live)(
                    self._vecs, self._nbrs, self._rmq, self._dist_c,
                    self._order, self._rank0, xq, scale, live_d, *dev)
            else:
                out = self._dispatch_groups(groups, dev, rows, k, ef,
                                            beam_width, precision, slot,
                                            live_d, use_live)
            del dev
        with stage("mesh_block", met, trace):
            ids, dists = jax.device_get(out)
            ids, dists = ids[:nq], dists[:nq]
            del out
        if route == "graph":
            return ids, dists, {"strategy": np.ones(nq, np.int8),
                                "scan_frac": 0.0}
        return ids, dists, {"strategy": strategy,
                            "scan_frac": float((strategy == SCAN).mean())}

    def _groups(self, routes, slo, shi, rows: int):
        """The group programs of one batch: ``(bucket, ops)`` for each pow2
        scan bucket any shard reaches, then ``(0, ops)`` for the beam
        (``_group_rows``: scan rows carry (start, len), beam rows (lo, hi),
        all shard-local)."""
        lens = np.clip(shi.astype(np.int64) - slo + 1, 0, None)
        out = []
        scan = routes == SCAN
        if scan.any():
            buckets = buckets_np(lens, min_bucket=self.planner.min_bucket,
                                 max_bucket=next_pow2(self.per))
            for b in np.unique(buckets[scan]):
                out.append((int(b), _group_rows(scan & (buckets == b), slo,
                                                lens, rows, (0, 0))))
        beam = routes == BEAM
        if beam.any():
            out.append((0, _group_rows(beam, slo, shi, rows, (1, 0))))
        return out

    def _dispatch_groups(self, groups, dev, rows: int, k: int, ef: int,
                         beam_width: int, precision: str, slot, live_d,
                         use_live: bool):
        """Enqueue each group program over the per-shard buffers, then the
        merge; returns the replicated (rows, k) (ids, dists)."""
        scale = self._ones_scale() if slot is None else slot["scale_pad"]
        buf = self._empty_buffers(rows, k)
        qall = dev[0]
        for (bucket, ops), ops_d in zip(groups, dev[1:]):
            shape = (ops.shape[2], rows)
            if bucket:
                x_scan = (self._scan_corpus() if slot is None
                          else slot["data_pad"])
                fn = self._program("scan", shape, k=k, ef=ef, bucket=bucket,
                                   precision=precision, use_live=use_live)
                buf = fn(x_scan, self._vecs, self._order, scale, live_d,
                         qall, ops_d, *buf)
            else:
                xq = self._vecs if slot is None else slot["data"]
                fn = self._program("beam", shape, k=k, ef=ef,
                                   beam_width=beam_width,
                                   precision=precision, use_live=use_live)
                buf = fn(self._vecs, self._nbrs, self._rmq, self._dist_c,
                         self._order, xq, scale, live_d, qall, ops_d, *buf)
        return self._program("merge", (rows,), k=k)(*buf)

    def _empty_buffers(self, rows: int, k: int):
        """The per-shard (S, rows + 1, k) request-order buffers a batch
        starts from (ids -1, dists inf; kept, never written in place)."""
        buf = self._empty.get((rows, k))
        if buf is None:
            shape = (self.n_shards, rows + 1, k)
            sh = NamedSharding(self.mesh, P(self.axis))
            buf = tuple(jax.device_put(
                [np.full(shape, -1, np.int32),
                 np.full(shape, np.inf, np.float32)], [sh, sh]))
            self._empty[(rows, k)] = buf
        return buf

    def _scan_corpus(self):
        """Row/lane-padded per-shard corpus for the scan kernel (lazy: a
        mesh that never routes to scan skips the duplicate)."""
        if self._x_pad is None:
            per_pad = -(-self.per // self.tb) * self.tb
            self._x_pad = jnp.pad(
                self._vecs, ((0, 0), (0, per_pad - self.per),
                             (0, self.d_pad - self.d)))
        return self._x_pad

    # ---------------------------------------------------------- programs
    def _program(self, kind: str, shape=(), **static):
        """``mesh_program`` of ``kind``, built once per operand ``shape``
        (group pad and batch rows) and static arguments: ``_fns`` holds
        every program this substrate compiled."""
        key = (kind, *shape, *sorted(static.items()))
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = mesh_program(self.mesh, self.axis, kind,
                                               **static)
        return fn

    def graph_fn(self, k: int, ef: int, beam_width: int = 1,
                 precision: str = "f32", use_live: bool = False):
        """Jitted graph-strategy mesh fn (also the dry-run lowering target).
        Operands: 6 sharded index arrays + sharded ``xq`` + replicated
        ``scale`` + sharded ``live`` + replicated ``(qv, lo, hi)`` — under
        f32 pass ``vecs`` again as ``xq`` and any (d_pad,) f32 row as
        ``scale``; under ``use_live=False`` pass any (S, per) array as
        ``live`` (all ignored).  Returns (ids, dists)."""
        return self._program("graph", k=k, ef=max(ef, k),
                             beam_width=beam_width, precision=precision,
                             use_live=use_live)

    def warm_up(self, *, k: int, ef: int, max_batch: int,
                beam_width: int = 1, precision: str = "f32") -> None:
        """Compile every program the planned path reaches on batches of up
        to ``max_batch`` queries: the scan at each pow2 bucket up to the
        planner's ceiling and the beam, each at every group pad and batch
        row count, and the merge at every row count.  Windows in shard 0
        steer each call to one program; the batch's other rows are
        empty."""
        pl = self.planner
        top = bucket_for_len(pl.max_scan_len, min_bucket=pl.min_bucket,
                             max_bucket=next_pow2(self.per))
        buckets = sorted({bucket_for_len(1 << i, min_bucket=pl.min_bucket,
                                         max_bucket=top)
                          for i in range(top.bit_length())})
        sizes = range(1, max_batch + 1)
        pads = sorted({pad_pow2(c) for c in sizes})
        qv = np.zeros((max_batch, self.d), np.float32)
        for rows in sorted({mesh_rows(c) for c in sizes}):
            nb = min(rows, max_batch)
            for pad in pads:
                c = min(pad, nb)
                lo, hi = np.ones(nb, np.int64), np.zeros(nb, np.int64)
                lo[:c] = 0
                for mode, widths in (("scan", buckets), ("beam", [self.per])):
                    for w in widths:
                        hi[:c] = min(w, self.per) - 1
                        self.run(SearchRequest(
                            queries=qv[:nb], lo=lo, hi=hi, k=k, ef=ef,
                            strategy=mode, beam_width=beam_width,
                            precision=precision))
