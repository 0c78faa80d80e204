"""Per-query trace records threaded through the whole query path.

A ``QueryTrace`` rides as the optional ``trace`` field of a
``SearchRequest`` and comes back attached to the ``SearchResult``.  Every
stage (``repro.obs.stage``) that runs while the request is served appends
a **span** — a named, wall-timed segment with free-form attributes — in
the order the stages ran (see docs/observability.md for the stage table):

    resolve          attribute range -> rank interval (interval widths, Q)
    plan             cache split and routing decision (cache outcome,
                     strategy vector, partitions)
    *_prep           host arrays, padding and copies for one partition
    *_dispatch       device-work enqueue
    *_block          wait for the device outputs and copy them back
    assemble         request-order scatter, id remap, cache store

Span attributes hold numpy arrays where the quantity is per-query (e.g.
the strategy vector) and scalars otherwise; ``to_dict()`` converts
everything to plain JSON-able Python for logging.

Tracing is strictly **opt-in per request**: a stage pays one ``is None``
check when no trace is attached.

A trace is owned by one request as it moves resolver -> dispatcher ->
finalize; stages run sequentially even when they hop threads, so spans are
a plain list (appends are atomic under the GIL).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np


@dataclass
class Span:
    name: str
    t0: float
    t1: float = 0.0
    attrs: Dict[str, Any] = field(default_factory=dict)
    child_s: float = 0.0        # wall of the stages nested inside this one

    @property
    def wall_ms(self) -> float:
        return max(self.t1 - self.t0, 0.0) * 1e3

    @property
    def self_ms(self) -> float:
        return max(self.t1 - self.t0 - self.child_s, 0.0) * 1e3

    def to_dict(self) -> dict:
        return dict(name=self.name, wall_ms=round(self.wall_ms, 4),
                    self_ms=round(self.self_ms, 4),
                    attrs={k: _plain(v) for k, v in self.attrs.items()})


class QueryTrace:
    """One request's span list plus request-level metadata."""

    def __init__(self, request_id: Optional[str] = None, **meta):
        self.request_id = request_id
        self.meta: Dict[str, Any] = dict(meta)
        self.spans: List[Span] = []

    def get(self, name: str) -> Optional[Span]:
        """Last span with this name (stages may repeat, e.g. one dispatch
        span per partition or per shard)."""
        for sp in reversed(self.spans):
            if sp.name == name:
                return sp
        return None

    def all(self, name: str) -> List[Span]:
        return [sp for sp in self.spans if sp.name == name]

    def names(self) -> List[str]:
        return [sp.name for sp in self.spans]

    def wall_ms(self, name: str) -> float:
        return sum(sp.wall_ms for sp in self.spans if sp.name == name)

    def to_dict(self) -> dict:
        return dict(request_id=self.request_id,
                    meta={k: _plain(v) for k, v in self.meta.items()},
                    spans=[sp.to_dict() for sp in self.spans])


def _plain(v):
    """numpy -> JSON-able Python (arrays to lists, scalars unboxed)."""
    if isinstance(v, np.ndarray):
        return v.tolist()
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    if isinstance(v, (np.bool_,)):
        return bool(v)
    return v
