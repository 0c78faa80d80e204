"""Sharded RNSG construction: Algorithm 2 as one batched jit/shard_map body.

The single-host pipeline (``build_rnsg``) is embarrassingly parallel in the
attribute-rank dimension: every per-node result — the exact-KNN row, the
±ef_attribute rank window, the gap-sorted candidate arrays, and the
Algorithm-1 keep/prune recurrence — depends only on that node's own row and
the (read-only) corpus.  This module shards all four stages by contiguous
attribute-rank **slab** across the mesh's ``data`` axis with one
``shard_map`` dispatch per build:

* **exact KNN** — each device scores its slab's query rows against the
  replicated corpus in the same 512-row blocks (and the same pad geometry)
  as ``index.knn.exact_knn``, so every real row's top-k is the bit-identical
  float comparison sequence;
* **rank window + gap sort** — pure id arithmetic on the slab's global rank
  offsets.  The ±ef_attribute window rows a slab edge needs ("halo" rows) come
  free from the replicated corpus — a future multi-host port would exchange
  only those 2·ef_attribute boundary rows per slab;
* **prune + pack** — the shared traceable bodies from ``core.pruning``
  (``prune_side`` / ``pack_kept``), gathering candidate vectors from the
  replicated corpus.

Because every stage is row-independent and the sorts are *stable* (a stable
sort's permutation is uniquely determined by its keys, independent of the
implementation), the sharded build is **bit-identical** to ``build_rnsg``
for every shard count — property-tested across S ∈ {1, 2, 8} in
``tests/test_build_sharded.py``.

The corpus is replicated per device (the dominant build costs — the O(n²d)
KNN matmuls and the O(n·C²·d) prune tiles — shard perfectly; the replicated
operand is the standard single-pod trade, and the slab outputs are the only
cross-device traffic).  Entry structures (centroid distances + RMQ table)
are O(n·d) host work and stay global.
"""
from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.construction import RNSGGraph, gap_sorted_side
from repro.core.entry import build_rmq, centroid_dists
from repro.core.pruning import pack_kept, prune_side
from repro.index.knn import topk_smallest, sq_dists

_PAD_VAL = 1e9          # must match index.knn.exact_knn's pad rows


def _slab_nbrs_body(n: int, n_pad: int, rows_per_shard: int, block: int,
                    k: int, ef_attribute: int, m: int, axis: str):
    """The per-device shard_map body: slab rows -> slab adjacency."""
    half = max(m // 2, 1)

    def body(q_slab, corpus):
        # q_slab: (rows_per_shard, d) this slab's rows (pad rows = 1e9);
        # corpus: (n_pad, d) replicated, identical to exact_knn's padding
        row0 = jax.lax.axis_index(axis) * rows_per_shard

        def knn_block(i):
            q = jax.lax.dynamic_slice_in_dim(q_slab, i * block, block)
            d = sq_dists(q, corpus)
            rows = row0 + i * block + jnp.arange(block)
            # exclude self; clamp keeps shard-pad rows (global id >= n_pad,
            # results discarded) in bounds without touching real rows
            d = d.at[jnp.arange(block),
                     jnp.minimum(rows, n_pad - 1)].set(jnp.inf)
            return topk_smallest(d, k)[1]

        knn = jax.lax.map(knn_block,
                          jnp.arange(rows_per_shard // block))
        knn = knn.reshape(rows_per_shard, k)
        # pad-row ids (>= n) never survive: the gap-sort side mask drops
        # them exactly like the host pipeline's kn < n bound
        ids = (row0 + jnp.arange(rows_per_shard, dtype=jnp.int32))[:, None]
        cand_l = gap_sorted_side(ids, n, knn, ef_attribute, "l")
        cand_r = gap_sorted_side(ids, n, knn, ef_attribute, "r")

        def prune_block(i):
            xv = jax.lax.dynamic_slice_in_dim(q_slab, i * block, block)
            cl = jax.lax.dynamic_slice_in_dim(cand_l, i * block, block)
            cr = jax.lax.dynamic_slice_in_dim(cand_r, i * block, block)
            kept_l = prune_side(xv, cl, corpus[jnp.maximum(cl, 0)], half)
            kept_r = prune_side(xv, cr, corpus[jnp.maximum(cr, 0)], half)
            return pack_kept(cl, kept_l, cr, kept_r, m)

        nbrs = jax.lax.map(prune_block,
                           jnp.arange(rows_per_shard // block))
        return nbrs.reshape(rows_per_shard, m)

    return body


def build_rnsg_sharded(vectors: np.ndarray, attrs: np.ndarray, *,
                       n_shards: Optional[int] = None, mesh: Optional[Mesh] = None,
                       axis: str = "data", m: int = 32, ef_spatial: int = 32,
                       ef_attribute: int = 48, block: int = 512,
                       reverse_edges: bool = False,
                       reverse_cap: Optional[int] = None) -> RNSGGraph:
    """Sharded Algorithm 2 — bit-identical to ``build_rnsg`` (exact KNN).

    ``n_shards`` defaults to the mesh's ``axis`` size (or the local device
    count when no mesh is given); a one-axis mesh over the first
    ``n_shards`` local devices is built when none is passed.  ``block``
    must match the exact-KNN row block (512) for bit-identical float
    geometry — it is exposed only for tests.
    """
    t0 = time.perf_counter()
    vectors = np.asarray(vectors, np.float32)
    attrs = np.asarray(attrs, np.float32)
    n = len(attrs)
    if mesh is None:
        devs = jax.devices()
        n_shards = n_shards or len(devs)
        if n_shards > len(devs):
            raise ValueError(f"build_rnsg_sharded: n_shards={n_shards} "
                             f"exceeds the {len(devs)} available devices")
        mesh = Mesh(np.asarray(devs[:n_shards]), (axis,))
    else:
        n_shards = n_shards or mesh.shape[axis]
        if n_shards != mesh.shape[axis]:
            raise ValueError(f"build_rnsg_sharded: n_shards={n_shards} != "
                             f"mesh axis {axis!r} size {mesh.shape[axis]}")
    k_eff = min(ef_spatial, n - 1)
    if k_eff < 1:               # degenerate corpus: nothing to shard
        from repro.core.construction import build_rnsg
        g = build_rnsg(vectors, attrs, m=m, ef_spatial=ef_spatial,
                       ef_attribute=ef_attribute,
                       reverse_edges=reverse_edges, reverse_cap=reverse_cap)
        g.meta["shards"] = n_shards
        return g

    order = np.argsort(attrs, kind="stable")
    vs, as_ = vectors[order], attrs[order]

    # corpus padding identical to exact_knn (pad rows sit at 1e9); the
    # query-side slab padding extends further so every shard holds the
    # same whole number of 512-row blocks
    n_pad = n + (-n) % block
    rows_per_shard = -(-n_pad // (n_shards * block)) * block
    total = n_shards * rows_per_shard
    corpus = np.full((n_pad, vs.shape[1]), _PAD_VAL, np.float32)
    corpus[:n] = vs
    queries = np.full((total, vs.shape[1]), _PAD_VAL, np.float32)
    queries[:n] = vs

    body = _slab_nbrs_body(n, n_pad, rows_per_shard, block, k_eff,
                           ef_attribute, m, axis)
    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P()),
                               out_specs=P(axis), check_vma=False))
    nbrs = np.asarray(fn(jnp.asarray(queries), jnp.asarray(corpus)))[:n]

    if reverse_edges:
        from repro.index.baselines import add_reverse_edges
        nbrs = add_reverse_edges(nbrs, reverse_cap or int(m * 1.25))

    c, dist_c = centroid_dists(vs)
    rmq = build_rmq(dist_c)
    dt = time.perf_counter() - t0
    return RNSGGraph(vecs=vs, attrs=as_, nbrs=nbrs,
                     order=order.astype(np.int32),
                     centroid=c.astype(np.float32), dist_c=dist_c, rmq=rmq,
                     build_seconds=dt,
                     meta=dict(m=m, ef_spatial=ef_spatial,
                               ef_attribute=ef_attribute, knn="exact",
                               shards=n_shards))
