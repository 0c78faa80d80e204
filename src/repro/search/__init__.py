"""Unified search substrate: one strategy-routed execution layer.

Every query path in the repo — single-node ``RNSGIndex``, the adaptive
planner, the dynamic-batching engine, and range-partitioned distributed
serving (both its local and ``shard_map`` mesh paths) — flows through this
package:

    SearchRequest (queries, rank intervals, k/ef, strategy)
        -> resolve   (rank-interval mapping + RMQ entry selection)
        -> cache     (optional SearchCache: hit rows skip dispatch entirely)
        -> dispatch  (range-scan kernel | graph beam | planned mix;
                      async at the substrate boundary — PendingSearch)
        -> stitch    (request-order stats, rank -> original id remap)
        -> SearchResult

Two execution substrates implement dispatch + stitch over the same resolve
primitives:

* ``SearchSubstrate`` — one attribute-sorted corpus slice on the host
  (single node, or one shard of the distributed local path); the planner
  partitions each batch into fixed-shape jit dispatches.
* ``MeshSubstrate`` — all shards at once under ``shard_map``; each shard
  routes its own clip of every global interval host-side, and one program
  per scan bucket and one for the beam write into per-shard request-order
  buffers before the cross-shard ``merge_topk``.

See docs/architecture.md for the layer diagram and docs/distributed.md for
the mesh dispatch flow.
"""
from repro.search.cache import SearchCache, query_key
from repro.search.request import (PRECISIONS, STRATEGIES, SearchRequest,
                                  SearchResult)
from repro.search.resolve import (clip_interval, clip_interval_jax,
                                  rank_interval, rank_interval_jax,
                                  remap_ids, remap_ids_jax, select_entry)
from repro.search.substrate import (MeshSubstrate, PendingSearch,
                                    SearchSubstrate, merge_topk)

__all__ = ["PRECISIONS", "STRATEGIES", "SearchRequest", "SearchResult",
           "SearchSubstrate",
           "MeshSubstrate", "PendingSearch", "SearchCache", "query_key",
           "merge_topk",
           "rank_interval", "rank_interval_jax", "select_entry",
           "remap_ids", "remap_ids_jax", "clip_interval", "clip_interval_jax"]
