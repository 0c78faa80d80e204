"""The one traffic generator: turns a mix's data file
(``bench/traffic/<mix>.json``) and ``--seed`` into a schedule of queries.

A mix file holds only parameters:

* ``loop`` — ``"closed"`` (``clients`` callers, each sending its next query
  when the last one is answered) or ``"open"`` (arrivals at ``rate``
  queries/s, Poisson, whatever the system does);
* ``levels`` and ``weights`` — each query covers ``2^-level`` of the
  corpus' attribute ranks, drawn in proportion to ``weights``;
* ``settle_s`` — seconds of the same traffic served before the measured
  window, so queues and batching reach their steady state (routing needs
  none: a slice of at most ``max_scan_frac`` of the corpus scans, a longer
  one beams);
* ``pool`` (closed loop) — queries in the schedule, sent in turn.

Every seed serves the same multiset of levels and inter-arrival gaps per
block of ``BLOCK`` queries, in a seeded order: the seed changes the order
and the vectors, not the amount of work.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from bench import data

BLOCK = 1000


def load_mix(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    if mix["loop"] not in ("closed", "open"):
        raise ValueError(f"{path}: loop must be closed or open")
    if len(mix["levels"]) != len(mix["weights"]):
        raise ValueError(f"{path}: one weight per level")
    if mix["loop"] == "open" and not mix.get("rate", 0) > 0:
        raise ValueError(f"{path}: an open loop needs a rate")
    return mix


@dataclass
class Schedule:
    level: np.ndarray       # (N,) selectivity exponent of each query
    due: np.ndarray         # (N,) seconds after the traffic starts (open)

    def __len__(self) -> int:
        return len(self.level)


def _blocked(n_ops: int, fill) -> np.ndarray:
    """Concatenate per-block draws ``fill(block_len)`` to ``n_ops``."""
    parts, left = [], n_ops
    while left > 0:
        parts.append(fill(BLOCK)[:left])
        left -= BLOCK
    return np.concatenate(parts) if parts else np.zeros(0)


def make_schedule(mix: dict, seed: int, n_ops: int) -> Schedule:
    """The first ``n_ops`` queries of the mix under ``seed``."""
    r = data.rng(seed, data.STREAM_SCHEDULE)
    levels = np.asarray(mix["levels"], np.int64)
    counts = data.stratified_counts(mix["weights"], BLOCK)
    lvl = _blocked(n_ops, lambda b: r.permutation(np.repeat(levels, counts)))
    if mix["loop"] == "open":
        rate = float(mix["rate"])
        gaps = _blocked(n_ops, lambda b: data.exponential_gaps(b, rate, r))
        due = np.cumsum(gaps)
    else:
        due = np.zeros(n_ops)
    return Schedule(lvl.astype(np.int64), due)


def ops_needed(mix: dict, seconds: float) -> int:
    """Queries an open loop offers over settle plus window, with room for
    the Poisson tail; a closed loop draws from ``pool``."""
    if mix["loop"] == "closed":
        return int(mix["pool"])
    total = float(mix["settle_s"]) + float(seconds)
    return int(mix["rate"] * total * 1.1) + BLOCK
