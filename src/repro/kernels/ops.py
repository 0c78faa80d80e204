"""Jit'd public wrappers for the Pallas kernels.

On TPU the kernels compile to Mosaic.  On CPU (tests, small local runs)
they execute in ``interpret=True`` mode — the kernel body runs verbatim for
correctness.  Any other backend raises: a served kernel never silently
degrades to the interpreter on an accelerator.
"""
from __future__ import annotations

import jax

from repro.kernels.gather_dist import (gather_dist_pallas,
                                       gather_rerank_pallas,
                                       gather_topk_pallas)
from repro.kernels.l2dist import l2dist_pallas
from repro.kernels.range_scan import range_scan_pallas


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"Pallas kernels run on TPU (Mosaic) or CPU "
                           f"(interpret mode), not on {backend!r}")
    return backend == "cpu"


def l2dist(q: jax.Array, x: jax.Array, **kw) -> jax.Array:
    """(Q,d) × (N,d) -> (Q,N) squared-L2 distance matrix."""
    return l2dist_pallas(q, x, interpret=_interpret(), **kw)


def gather_dist(x: jax.Array, ids: jax.Array, q: jax.Array,
                scale: jax.Array | None = None) -> jax.Array:
    """Fused gather+score of M neighbor rows against one query.  ``x`` may
    be a quantized corpus; ``scale`` dequantizes int8 rows in VMEM."""
    return gather_dist_pallas(x, ids, q, scale=scale, interpret=_interpret())


def gather_topk(x: jax.Array, ids: jax.Array, q: jax.Array, *, k: int,
                scale: jax.Array | None = None):
    """Fused gather+score+top-k: the batched beam's frontier feed.  Negative
    ids are masked; only the k merge survivors leave the kernel."""
    return gather_topk_pallas(x, ids, q, k=k, scale=scale,
                              interpret=_interpret())


def gather_rerank(x: jax.Array, ids: jax.Array, q: jax.Array, *, k: int):
    """Batched f32 rescore of (Q, M) quantized-pass survivor ids against
    (Q, d) queries — the exactness-restoring stage of the quantized path."""
    return gather_rerank_pallas(x, ids, q, k=k, interpret=_interpret())


def range_scan(x: jax.Array, starts: jax.Array, lens: jax.Array,
               q: jax.Array, *, bucket: int, k: int, n_valid: int = 0,
               scale: jax.Array | None = None,
               live: jax.Array | None = None):
    """Per-query masked scan + top-k over contiguous rank slices of x.
    ``n_valid`` masks the zero rows padding x to a row-tile multiple
    (0 = trust the window contract, i.e. all of x is real).  ``x`` may be
    a quantized corpus copy; ``scale`` dequantizes int8 rows in VMEM.
    ``live`` ((1, n_pad) i32) masks tombstoned rows (streaming deletes)."""
    return range_scan_pallas(x, starts, lens, q, bucket=bucket, k=k,
                             n_valid=n_valid, scale=scale, live=live,
                             interpret=_interpret())
