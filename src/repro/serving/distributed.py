"""Distributed RFANN serving: range-partitioned shards via ``shard_map``.

The scale-out design falls directly out of Theorem 4.7 (structural heredity):
an attribute-contiguous shard's induced subgraph *is* the RNSG built on that
shard, so

  * shards can be **constructed independently in parallel** (provably
    equivalent to slicing a global build, up to KNN approximation noise), and
  * a query with range ``q.I`` only needs the shards whose attribute span
    intersects ``q.I``; per-shard searches are exact RNSG searches on their
    sub-ranges, and a top-k merge of shard results equals the global search.

Resolution happens **once**, globally: the query's attribute range maps to a
global rank interval (``repro.search.resolve``), which each shard *clips* to
its contiguous rank slice — no per-shard ``searchsorted``.  Execution then
routes through the unified search substrate, and ``plan="auto"`` works on
**both** paths:

  * local path (``mesh=None``): one ``SearchSubstrate`` per shard, so each
    shard runs the full strategy router (fused range-scan | beam per
    query), followed by a host top-k merge.  By
    default the per-shard dispatches are **asynchronous**: every shard's
    device work is enqueued (``SearchSubstrate.dispatch``, jax async
    dispatch) before any shard's result is blocked on, so shard N+1's
    planning and upload overlap shard N's kernels; ``async_dispatch=False``
    restores the sequential dispatch+block loop;
  * mesh path: one shard per device along the ``data`` axis via
    ``MeshSubstrate`` — each shard routes its own clip of every global
    interval host-side, exactly as the local path's per-shard planner does,
    and the batch runs as one ``shard_map`` program per scan bucket and one
    beam program, each writing into per-shard request-order buffers, then
    the cross-shard ``all_gather`` + top-k merge.  Batches pad to
    ``mesh_rows``, so the compiled programs are bounded and
    ``MeshSubstrate.warm_up`` compiles them all.  See docs/distributed.md
    for the full dispatch flow.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from contextlib import nullcontext
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.construction import build_rnsg
from repro.search import (MeshSubstrate, SearchCache, SearchRequest,
                          SearchResult, SearchSubstrate, clip_interval,
                          merge_topk, rank_interval)


class DistributedRFANN:
    """Attribute-range-partitioned RNSG serving across the 'data' mesh axis."""

    def __init__(self, vectors: np.ndarray, attrs: np.ndarray, *,
                 n_shards: int, mesh=None, axis: str = "data",
                 async_dispatch: bool = True, **build_kw):
        order = np.argsort(attrs, kind="stable")
        vs = np.asarray(vectors, np.float32)[order]
        as_ = np.asarray(attrs, np.float32)[order]
        n = len(as_)
        per = n // n_shards
        assert per * n_shards == n, "pad the corpus to a shard multiple"
        self.mesh = mesh
        self.axis = axis
        self.n_shards = n_shards
        self.per = per
        self.attrs_sorted = as_       # global resolve happens over this
        # each shard is independently buildable (heredity); with a mesh,
        # shard s is built on its own device, all shards at once
        devs = None if mesh is None else list(mesh.devices.flat)

        def build(s):
            sl = slice(s * per, (s + 1) * per)
            with (nullcontext() if devs is None
                  else jax.default_device(devs[s])):
                return build_rnsg(vs[sl], as_[sl], **build_kw), order[sl]
        if devs is None:
            graphs = [build(s) for s in range(n_shards)]
        else:
            with ThreadPoolExecutor(n_shards) as ex:
                graphs = list(ex.map(build, range(n_shards)))
        self.shard_span = np.asarray(
            [[g.attrs[0], g.attrs[-1]] for g, _ in graphs], np.float32)
        # with a mesh, every stacked array is placed shard-per-device along
        # the data axis (never stacked onto one device first)
        place = (jnp.asarray if mesh is None else
                 lambda a: jax.device_put(a, NamedSharding(mesh, P(axis))))
        stack = lambda f: place(np.stack([f(g, o) for g, o in graphs]))  # noqa: E731
        self.vecs = stack(lambda g, o: g.vecs)
        self.nbrs = stack(lambda g, o: g.nbrs)
        self.attrs = stack(lambda g, o: g.attrs)
        self.rmq = stack(lambda g, o: g.rmq)
        self.dist_c = stack(lambda g, o: g.dist_c)
        self.order = stack(lambda g, o: o[g.order].astype(np.int32))
        self.rank0 = place(
            np.arange(n_shards, dtype=np.int32)[:, None] * per)   # (S, 1)
        self.build_seconds = sum(g.build_seconds for g, _ in graphs)
        self.async_dispatch = async_dispatch
        self._subs: Optional[list] = None
        self._mesh_sub: Optional[MeshSubstrate] = None
        self._cache: Optional[SearchCache] = None
        self._metrics = None

    @property
    def index_bytes(self) -> int:
        return (self.nbrs.nbytes + self.rmq.nbytes + self.dist_c.nbytes)

    # ------------------------------------------------------------------
    @property
    def substrates(self):
        """One unified search substrate per shard (local execution path)."""
        if self._subs is None:
            sh = self._shard
            self._subs = [
                SearchSubstrate(sh(self.vecs, s), sh(self.nbrs, s),
                                sh(self.rmq, s), sh(self.dist_c, s),
                                np.asarray(sh(self.order, s)),
                                np.asarray(sh(self.attrs, s)),
                                cache=self._cache, cache_ns=s,
                                metrics=self._metrics)
                for s in range(self.n_shards)]
        return self._subs

    @staticmethod
    def _shard(a: jax.Array, s: int) -> jax.Array:
        """Shard ``s`` of a stacked array — on its own device when the
        stack is placed across a mesh (so each shard's substrate runs
        there), else a slice of the one-device stack."""
        if isinstance(a.sharding, NamedSharding):
            for piece in a.addressable_shards:
                if (piece.index[0].start or 0) == s:
                    return piece.data[0]
        return a[s]

    @property
    def mesh_substrate(self) -> MeshSubstrate:
        """The shard_map execution path (lazy; requires ``mesh``)."""
        if self._mesh_sub is None:
            assert self.mesh is not None, "mesh execution needs mesh="
            self._mesh_sub = MeshSubstrate(
                self.mesh, self.axis, self.vecs, self.nbrs, self.rmq,
                self.dist_c, self.order, self.rank0, cache=self._cache,
                metrics=self._metrics)
        return self._mesh_sub

    def install_cache(self, cache: Optional[SearchCache]) -> None:
        """Install one shared result cache on every execution path.  On the
        local path each shard substrate keys its own shard-clipped interval,
        so shards share the byte budget without colliding."""
        self._cache = cache
        if self._subs is not None:
            for sub in self._subs:
                sub.cache = cache
        if self._mesh_sub is not None:
            self._mesh_sub.cache = cache

    def install_metrics(self, metrics) -> None:
        """Install (or remove, with ``None``) a ``MetricsRegistry`` on every
        execution path — already-built shard substrates and the mesh
        substrate pick it up immediately, lazy ones at construction."""
        self._metrics = metrics
        if self._subs is not None:
            for sub in self._subs:
                sub.metrics = metrics
        if self._mesh_sub is not None:
            self._mesh_sub.metrics = metrics

    def install_quantized(self, precision: str) -> None:
        """Pre-build the quantized corpus copies on every execution path."""
        if precision == "f32":
            return
        if self.mesh is not None:
            self.mesh_substrate.install_quantized(precision)
        else:
            for sub in self.substrates:
                sub.install_quantized(precision)

    def _search_local(self, qv, lo, hi, *, k: int, ef: int, plan: str,
                      beam_width: int = 1, precision: str = "f32",
                      trace=None, live=None):
        """Per-shard substrate dispatch, merged by the same ``merge_topk``
        the mesh path uses — identical ids by construction.  With
        ``async_dispatch`` every shard's work is enqueued before any block
        (the merge is the single synchronization point); otherwise shards
        run the sequential dispatch+block loop.

        Returns ``(ids, dists, stats)`` — stats aggregate the per-shard
        substrate stats: ``cache_hits`` is total shard hits normalized by
        the shard count (≈ fully-cached queries), ``scan_frac`` the mean
        routed scan fraction across shards."""
        q = len(qv)
        all_i = np.full((self.n_shards, q, k), -1, np.int32)
        all_d = np.full((self.n_shards, q, k), np.inf, np.float32)
        digests = None
        if self._cache is not None and q:       # hash each query ONCE, not
            from repro.search.cache import hash_query     # once per shard
            digests = [hash_query(qv[i]) for i in range(q)]
        pending = []
        for s, sub in enumerate(self.substrates):
            slo, shi = clip_interval(lo, hi, s * self.per, self.per)
            # every shard shares the one trace; its spans are tagged by the
            # substrate with ns=<shard>, and the blocking loop below drains
            # shards sequentially so appends never race
            req = SearchRequest(queries=qv, lo=slo, hi=shi,
                                k=k, ef=ef, strategy=plan,
                                beam_width=beam_width, precision=precision,
                                trace=trace,
                                live=None if live is None
                                else live[s * self.per:(s + 1) * self.per])
            p = sub.dispatch(req, defer=self.async_dispatch,
                             q_digests=digests)
            if not self.async_dispatch:
                p.result()              # block before the next shard starts
            pending.append(p)
        hits = 0
        scan_fracs = []
        for s, p in enumerate(pending):
            res = p.result()
            all_i[s] = res.ids
            all_d[s] = np.where(res.ids >= 0, res.dists, np.inf)
            hits += int(res.stats.get("cache_hits", 0))
            if "scan_frac" in res.stats:
                scan_fracs.append(float(res.stats["scan_frac"]))
        from repro.obs import stage
        with stage("merge", self._metrics, trace,
                   n_shards=self.n_shards) as sp:
            ids, dists = merge_topk(jnp.asarray(all_i), jnp.asarray(all_d), k)
            ids, dists = np.asarray(ids), np.asarray(dists)
            sp.attrs["q"] = q
        stats = {}
        if scan_fracs:
            stats["scan_frac"] = float(np.mean(scan_fracs))
        if self._cache is not None:
            stats["cache_hits"] = int(round(hits / self.n_shards))
        return ids, dists, stats

    # ------------------------------------------------------------------
    def rank_range(self, attr_ranges: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """[a_l, a_r] (inclusive) -> *global* rank interval [L, R] over the
        attribute-sorted corpus (host-side resolve; the engine's pipelined
        resolver stage calls this while the previous batch executes)."""
        return rank_interval(self.attrs_sorted,
                             np.asarray(attr_ranges, np.float32))

    def search_ranks(self, queries, lo, hi, *, k: int = 10, ef: int = 64,
                     plan: str = "graph", beam_width: int = 1,
                     precision: str = "f32", trace=None,
                     live=None) -> SearchResult:
        """Rank-space entry point (resolve already done): dispatch on the
        mesh path when a mesh is attached, else the (async) local path.
        ``live`` is the *global* (n,) per-rank liveness mask; the local
        path slices it per shard, the mesh path reshapes it across the
        data axis."""
        qv = np.asarray(queries, np.float32)
        ef = max(ef, k)
        if self.mesh is None:
            ids, dists, stats = self._search_local(qv, lo, hi, k=k, ef=ef,
                                                   plan=plan,
                                                   beam_width=beam_width,
                                                   precision=precision,
                                                   trace=trace, live=live)
            return SearchResult(ids, dists, stats, trace=trace)
        return self.mesh_substrate.run(SearchRequest(
            queries=qv, lo=lo, hi=hi, k=k, ef=ef, strategy=plan,
            beam_width=beam_width, precision=precision, trace=trace,
            live=live))

    def search(self, queries: np.ndarray, attr_ranges: np.ndarray, *,
               k: int = 10, ef: int = 64, plan: str = "graph",
               beam_width: int = 1, precision: str = "f32",
               trace=None, live=None) -> Tuple[np.ndarray, np.ndarray]:
        from repro.obs import stage
        with stage("resolve", None, trace) as sp:
            lo, hi = self.rank_range(attr_ranges)
            sp.attrs.update(
                q=len(np.atleast_2d(queries)), n=len(self.attrs_sorted),
                interval_widths=np.clip(
                    np.asarray(hi, np.int64) - np.asarray(lo, np.int64) + 1,
                    0, None) if trace is not None else None)
        res = self.search_ranks(queries, lo, hi, k=k, ef=ef, plan=plan,
                                beam_width=beam_width, precision=precision,
                                trace=trace, live=live)
        return res.ids, res.dists

    # ------------------------------------------------------------------
    def lower_for_dryrun(self, nq: int, d: int, k: int = 10, ef: int = 64,
                         precision: str = "f32"):
        """Compile-only proof that the sharded search lowers on a real mesh."""
        ms = self.mesh_substrate
        fn = ms.graph_fn(k, ef, precision=precision)
        slot = ms._quant_for(precision)
        xq = self.vecs if slot is None else slot["data"]
        scale = ms._ones_scale() if slot is None else slot["scale_pad"]
        live = ms._live_shards(None)        # all-ones dummy (uniform operand)
        args = (self.vecs, self.nbrs, self.rmq, self.dist_c, self.order,
                self.rank0, xq, scale, live,
                jax.ShapeDtypeStruct((nq, d), jnp.float32),
                jax.ShapeDtypeStruct((nq,), jnp.int32),
                jax.ShapeDtypeStruct((nq,), jnp.int32))
        sds = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args[:9]]
        return jax.jit(fn).lower(*sds, *args[9:])
