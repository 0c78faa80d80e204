"""Adaptive query planner: selectivity-aware routing between the exact fused
range-scan kernel and graph beam search (see docs/planner.md).

The planner is pure policy — pow2 bucketing, the per-query routing
decision (``choose_strategy`` scalar / ``choose_strategy_batch``
vectorized), and ``plan_batch`` partitioning.  It
never dispatches; execution — kernel dispatch, padding, stitching — lives
in the unified search substrate (``repro.search.SearchSubstrate`` on the
host, ``repro.search.MeshSubstrate`` under ``shard_map``, which runs
``choose_strategy_batch`` host-side over every shard's clip of each
interval and groups the (query, shard) pairs by route)."""
from repro.planner.bucketing import (bucket_for_len, ef_bucket, ef_bucket_np,
                                     next_pow2, next_pow2_np, pad_pow2,
                                     window_rows, window_rows_np)
from repro.planner.planner import BEAM, SCAN, Partition, Plan, QueryPlanner

__all__ = ["QueryPlanner", "Plan", "Partition",
           "SCAN", "BEAM", "bucket_for_len", "ef_bucket", "ef_bucket_np",
           "next_pow2", "next_pow2_np", "pad_pow2", "window_rows",
           "window_rows_np"]
