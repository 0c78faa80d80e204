"""High-level RFANN API: build / save / load / batched search on one RNSG
index.  All query execution is delegated to the unified search substrate
(``repro.search``) — this class only owns index lifecycle."""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from repro.core.construction import RNSGGraph, build_rnsg


class RNSGIndex:
    """The paper's system: one hereditary graph index answering every range."""

    def __init__(self, graph: RNSGGraph):
        self.g = graph
        self._substrate = None        # lazy unified search substrate

    # ------------------------------------------------------------------
    @classmethod
    def build(cls, vectors: np.ndarray, attrs: np.ndarray, **kw) -> "RNSGIndex":
        return cls(build_rnsg(vectors, attrs, **kw))

    @classmethod
    def build_sharded(cls, vectors: np.ndarray, attrs: np.ndarray,
                      **kw) -> "RNSGIndex":
        """Multi-device construction (``core.build_sharded``) — bit-identical
        to :meth:`build` with exact KNN; ``n_shards=`` picks the slab count
        (defaults to every local device)."""
        from repro.core.build_sharded import build_rnsg_sharded
        return cls(build_rnsg_sharded(vectors, attrs, **kw))

    def save(self, path: str, *, shards: int = 0) -> None:
        """``shards=0``: legacy atomic single-npz (graph only).  ``shards>=1``:
        the sharded directory format (``repro.index.io``) — also captures
        installed quantized corpora and mmap/parallel-restores."""
        if shards:
            from repro.index import io
            io.save_index(self, path, shards=shards)
        else:
            self.g.save(path)

    @classmethod
    def load(cls, path: str) -> "RNSGIndex":
        from repro.index import io
        if io.is_index_dir(path):
            idx = io.load_index(path)
            if not isinstance(idx, cls):
                raise TypeError(f"index at {path} is "
                                f"{type(idx).__name__}, not RNSGIndex — "
                                f"load it with repro.index.io.load_index")
            return idx
        return cls(RNSGGraph.load(path))

    # ------------------------------------------------------------------
    @property
    def substrate(self):
        """Lazily-built unified search substrate (resolve/dispatch/stitch)."""
        if self._substrate is None:
            from repro.search import SearchSubstrate
            self._substrate = SearchSubstrate.from_graph(
                self.g, metrics=getattr(self, "_metrics", None))
        return self._substrate

    # Back-compat aliases from the pre-substrate layering.
    @property
    def executor(self):
        return self.substrate

    @property
    def planner(self):
        return self.substrate.planner

    def install_cache(self, cache) -> None:
        """Install (or remove, with ``None``) a ``SearchCache`` at the
        substrate choke point — see ``repro.search.cache``."""
        self.substrate.cache = cache

    def install_metrics(self, metrics) -> None:
        """Install (or remove, with ``None``) a ``MetricsRegistry`` on the
        substrate — the engine wires its registry here so substrate-level
        counters/histograms land in ``engine.metrics()``."""
        self._metrics = metrics
        self.substrate.metrics = metrics

    def rank_range(self, attr_ranges: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """[a_l, a_r] (inclusive) -> rank interval [L, R] (inclusive).
        Pure host-side resolve — does not force the substrate's device
        upload for callers that only need rank mapping."""
        from repro.search import rank_interval
        return rank_interval(self.g.attrs, np.asarray(attr_ranges, np.float32))

    def install_quantized(self, precision: str) -> None:
        """Pre-build the quantized corpus copies for one precision (int8 /
        bf16) so the first ``precision=`` search pays no build cost."""
        self.substrate.install_quantized(precision)

    def search(self, queries: np.ndarray, attr_ranges: np.ndarray, *,
               k: int = 10, ef: int = 64, use_kernel: bool = False,
               plan: str = "graph", beam_width: int = 1,
               precision: str = "f32", trace=None, live=None):
        """queries:(Q,d); attr_ranges:(Q,2) attribute values (inclusive).
        plan: "graph" (pure beam search) | "auto" (cost-based scan/beam
        routing) | "scan" / "beam" (forced strategy).
        beam_width: batched-expansion width for beam dispatches (1 = the
        legacy single-node hop; B>1 fuses B node expansions per hop).
        precision: "f32" | "int8" | "bf16" — quantized scoring with a fused
        exact f32 rerank (same top-k id set as f32).
        trace: optional ``repro.obs.QueryTrace`` — collects the stages'
        spans (resolve, plan, *_dispatch, assemble, ...) and rides back on
        the result.
        Returns a ``SearchResult`` (tuple-compatible: ids, dists, stats)."""
        from repro.obs import stage
        with stage("resolve", None, trace) as sp:
            lo, hi = self.rank_range(attr_ranges)
            sp.attrs.update(
                q=len(np.atleast_2d(queries)), n=self.g.n,
                interval_widths=np.clip(
                    np.asarray(hi, np.int64) - np.asarray(lo, np.int64) + 1,
                    0, None) if trace is not None else None)
        return self.search_ranks(queries, lo, hi, k=k, ef=ef,
                                 use_kernel=use_kernel, plan=plan,
                                 beam_width=beam_width, precision=precision,
                                 trace=trace, live=live)

    def search_ranks(self, queries, lo, hi, *, k=10, ef=64, use_kernel=False,
                     plan="graph", beam_width=1, precision="f32", trace=None,
                     live=None):
        from repro.search import SearchRequest
        return self.substrate.run(SearchRequest(
            queries=np.asarray(queries, np.float32), lo=lo, hi=hi,
            k=k, ef=ef, strategy=plan, use_kernel=use_kernel,
            beam_width=beam_width, precision=precision, trace=trace,
            live=live))

    # ------------------------------------------------------------------
    @property
    def index_bytes(self) -> int:
        return self.g.index_bytes

    @property
    def n_edges(self) -> int:
        return self.g.n_edges

    def stats(self) -> Dict:
        deg = (self.g.nbrs >= 0).sum(1)
        return dict(n=self.g.n, m=self.g.m, edges=self.g.n_edges,
                    mean_degree=float(deg.mean()), max_degree=int(deg.max()),
                    index_mb=self.index_bytes / 2**20,
                    build_seconds=self.g.build_seconds)
