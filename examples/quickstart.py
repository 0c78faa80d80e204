"""Quickstart: build an RNSG index and answer range-filtered ANN queries.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core.rfann import RNSGIndex
from repro.data.ann import (ground_truth, make_attrs, make_vectors,
                            recall_at_k, selectivity_ranges)

n, d, nq, k = 4096, 32, 100, 10

# a corpus: one vector + one numeric attribute (price, timestamp, ...) each
vectors = make_vectors(n, d, seed=0)
attrs = make_attrs(n, seed=0)

# ONE index serves every query range (Theorems 3.5 / 4.7: heredity)
index = RNSGIndex.build(vectors, attrs, m=16, ef_spatial=16, ef_attribute=24)
print("index:", index.stats())

queries = make_vectors(nq, d, seed=7)
ranges = selectivity_ranges(attrs, nq, frac=0.05, seed=1)   # 5% selectivity

ids, dists, stats = index.search(queries, ranges, k=k, ef=64)
order = np.argsort(attrs, kind="stable")
gt_r, _ = ground_truth(vectors[order], attrs[order], queries, ranges, k)
gt = np.where(gt_r >= 0, order[np.maximum(gt_r, 0)], -1)
print(f"recall@{k} = {recall_at_k(ids, gt):.4f}  "
      f"(mean hops {stats['hops'].mean():.1f}, "
      f"mean dist-evals {stats['ndist'].mean():.0f})")

# every hit respects the range filter
for q in range(nq):
    for i in ids[q]:
        assert i < 0 or ranges[q, 0] <= attrs[i] <= ranges[q, 1]
print("all results in range ✓")

# adaptive query planner (docs/planner.md): each query is routed by its
# selectivity — a fused exact scan of the rank slice for narrow ranges, beam
# search for wide ones
mixed = np.concatenate([selectivity_ranges(attrs, nq // 2, 0.005, seed=2),
                        selectivity_ranges(attrs, nq // 2, 0.5, seed=3)])
pids, _, pstats = index.search(queries, mixed, k=k, ef=64, plan="auto")
gt_r, _ = ground_truth(vectors[order], attrs[order], queries, mixed, k)
gt = np.where(gt_r >= 0, order[np.maximum(gt_r, 0)], -1)
print(f"planner recall@{k} = {recall_at_k(pids, gt):.4f}  "
      f"({pstats['scan_frac']:.0%} of queries routed to range_scan)")
