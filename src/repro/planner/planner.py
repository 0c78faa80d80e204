"""Selectivity-aware query planner: route each range query to a strategy
that answers it at the recall the deployment needs.

Given a batch of rank intervals ``[L, R]`` (ranks are free — the index
already computes them), the planner routes each query by the length of its
slice and partitions the batch:

* ``scan``  — exact fused brute-force over the contiguous rank slice, for
              every slice of at most ``max_scan_frac`` of the corpus or of
              at most ``k`` rows (empty intervals included),
* ``beam``  — graph beam search with a selectivity-scaled ``ef``, for wider
              slices, where traversal touches a small fraction of the slice
              and stays accurate.

One threshold, not a price: the beam's recall at the served ``ef`` falls
off on narrow slices of a large graph (2^20 latent-8 DEEP-like rows, m=32,
ef=64: recall@10 0.97 at half the corpus, 0.30-0.76 from 2^-3 down to
2^-9, ``tools/beam_levels.py``), so a beam that is cheaper there is not an
answer there.  Queries with ``k`` beyond the scan kernel's lane row always
go to the beam.

Each partition carries a pow2 bucket signature so the executor dispatches it
as one fixed-shape jit call regardless of batch mix.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Tuple

import numpy as np

from repro.planner.bucketing import (ROW_TILE, buckets_np, ef_bucket,
                                     next_pow2, pad_pow2)

SCAN, BEAM = 0, 1

#: the scan kernel keeps its running top-k in one row-tile of lanes, so it
#: serves k ≤ 128; larger k always routes to beam (empty ranges included —
#: the beam returns an all-pad row for them)
SCAN_MAX_K = ROW_TILE


@dataclass
class Partition:
    kind: str                 # "scan" | "beam"
    param: int                # scan: bucket; beam: ef
    indices: np.ndarray       # positions in the request batch
    pad_q: int                # padded batch size for this dispatch

    @property
    def signature(self) -> Tuple[str, int, int]:
        return (self.kind, self.param, self.pad_q)


@dataclass
class Plan:
    strategy: np.ndarray                  # (Q,) int8: 0 scan / 1 beam
    partitions: List[Partition] = field(default_factory=list)

    @property
    def scan_frac(self) -> float:
        return float((self.strategy == SCAN).mean()) if len(self.strategy) else 0.0


class QueryPlanner:
    def __init__(self, n: int, *, min_bucket: int = 64,
                 max_scan_frac: float = 0.125):
        self.n = int(n)
        self.min_bucket = int(min_bucket)
        # the scan serves every slice up to this length, the beam every
        # longer one (module docstring)
        self.max_scan_len = max(self.min_bucket,
                                int(max_scan_frac * self.n))
        self.max_bucket = next_pow2(self.n)

    # ----------------------------------------------------- routing decision
    def choose_strategy(self, length: int, *, k: int) -> int:
        """Routing of one rank-interval length — scalar reference semantics
        for ``choose_strategy_batch`` (the unit tests hold the two in
        lockstep): ``k`` beyond the scan's lane row always beams; otherwise
        slices up to ``max_scan_len`` (empty ones, and any of at most ``k``
        rows, included) scan and longer ones beam."""
        if k > SCAN_MAX_K:
            return BEAM
        return SCAN if int(length) <= max(self.max_scan_len, k) else BEAM

    def choose_strategy_batch(self, lens: np.ndarray, *, k: int) -> np.ndarray:
        """Vectorized ``choose_strategy``: (Q,) lengths -> (Q,) int8 strategy
        vector (``SCAN``/``BEAM``).  Pure numpy over the whole batch — this
        is the host-side half of mesh dispatch, where the strategy vector is
        computed once and passed into ``shard_map`` as a replicated operand."""
        use_scan = (np.asarray(lens, np.int64) <= max(self.max_scan_len, k)) \
            & (k <= SCAN_MAX_K)
        return np.where(use_scan, SCAN, BEAM).astype(np.int8)

    # ------------------------------------------------------------------
    def plan_batch(self, lo: np.ndarray, hi: np.ndarray, *, k: int, ef: int,
                   mode: str = "auto") -> Plan:
        """lo/hi: (Q,) int rank intervals (inclusive; lo > hi = empty).
        mode: "auto" (by selectivity) | "scan" | "beam" (forced)."""
        lo = np.asarray(lo, np.int64)
        hi = np.asarray(hi, np.int64)
        q = len(lo)
        lens = np.clip(hi - lo + 1, 0, None)
        buckets = buckets_np(lens, min_bucket=self.min_bucket,
                             max_bucket=self.max_bucket)
        if mode == "scan":
            use_scan = np.ones(q, bool)
        elif mode == "beam":
            # empty ranges go to the (free) scan while it can serve k
            use_scan = (lens <= 0) & (k <= SCAN_MAX_K)
        else:
            use_scan = self.choose_strategy_batch(lens, k=k) == SCAN
        strategy = np.where(use_scan, SCAN, BEAM).astype(np.int8)

        partitions: List[Partition] = []
        scan_idx = np.flatnonzero(use_scan)
        for b in np.unique(buckets[scan_idx]) if len(scan_idx) else []:
            idx = scan_idx[buckets[scan_idx] == b]
            partitions.append(Partition("scan", int(b), idx,
                                        pad_pow2(len(idx))))
        beam_idx = np.flatnonzero(~use_scan)
        if len(beam_idx):
            efs = np.asarray([ef_bucket(int(lens[i]), k, ef)
                              for i in beam_idx], np.int64)
            for e in np.unique(efs):
                idx = beam_idx[efs == e]
                partitions.append(Partition("beam", int(e), idx,
                                            pad_pow2(len(idx))))
        # a plan never carries an empty partition (beam dispatch pads by
        # duplicating idx[-1], which needs at least one real query)
        return Plan(strategy=strategy,
                    partitions=[p for p in partitions if len(p.indices)])
