"""KNN substrate: exact blocked brute force (JAX matmul) + NNDescent.

Distances are squared-L2 throughout (monotone in L2, so all pruning rules and
recall are unchanged).
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def sq_dists(a: jax.Array, b: jax.Array) -> jax.Array:
    """(Na,d) × (Nb,d) -> (Na,Nb) squared L2 via ‖a‖² - 2a·b + ‖b‖².
    The product runs at full f32 precision: exact KNN promises exact ids,
    and a TPU's default f32 matmul is a single bf16 pass."""
    an = jnp.sum(a * a, axis=-1, keepdims=True)
    bn = jnp.sum(b * b, axis=-1)
    ab = jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)
    d = an - 2.0 * ab + bn[None, :]
    return jnp.maximum(d, 0.0)


def topk_smallest(d: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Exact row-wise k smallest of d (B, N) — same values, ids and
    tie order (lower index first) as ``lax.top_k(-d, k)`` — without a
    top-k over all N columns, which dominates the build on a TPU.

    Columns are cut into groups of G; the k groups whose minima are
    smallest (ties toward the lower group) hold the k smallest entries:
    an entry of a group outside them has k distinct entries, the other
    groups' minima, ahead of it.  So only k·G candidates per row are
    ranked, gathered in ascending column order to keep the tie order."""
    b, n = d.shape
    g = 1 << max(min(128, n // k).bit_length() - 1, 0)   # pow2; n % g == 0
    grp = d.reshape(b, n // g, g)
    _, gsel = jax.lax.top_k(-jnp.min(grp, axis=2), k)
    gsel = jnp.sort(gsel, axis=1)
    cand = jnp.take_along_axis(grp, gsel[:, :, None], axis=1).reshape(b, -1)
    col = (gsel[:, :, None] * g + jnp.arange(g)).reshape(b, -1)
    nd, pos = jax.lax.top_k(-cand, k)
    return -nd, jnp.take_along_axis(col, pos, axis=1)


@partial(jax.jit, static_argnames=("k", "block"))
def _exact_knn_jit(vecs: jax.Array, k: int, block: int):
    n = vecs.shape[0]
    nb = n // block

    def one_block(i):
        q = jax.lax.dynamic_slice_in_dim(vecs, i * block, block)
        d = sq_dists(q, vecs)
        rows = i * block + jnp.arange(block)
        d = d.at[jnp.arange(block), rows].set(jnp.inf)      # exclude self
        return topk_smallest(d, k)

    dists, ids = jax.lax.map(one_block, jnp.arange(nb))
    return dists.reshape(n, k), ids.reshape(n, k)


def exact_knn(vecs: np.ndarray, k: int, block: int = 512) -> Tuple[np.ndarray, np.ndarray]:
    """Exact KNN (ids exclude self). Pads n to a block multiple internally.

    When ``k >= n`` the top-k necessarily spills into the pad rows; those
    slots come back masked (id -1, distance +inf) instead of leaking
    out-of-range pad-row ids into callers' gathers."""
    n = vecs.shape[0]
    pad = (-n) % block
    if pad:  # padded rows sit far away and never enter any real row's top-k
        vecs = np.concatenate(
            [vecs, 1e9 * np.ones((pad, vecs.shape[1]), np.float32)])
    d, i = _exact_knn_jit(jnp.asarray(vecs, jnp.float32), k, block)
    d, i = np.asarray(d[:n]), np.asarray(i[:n])
    oob = i >= n                     # pad-row ids: only reachable when k >= n
    if oob.any():
        i = np.where(oob, -1, i)
        d = np.where(oob, np.inf, d)
    return d, i


# ----------------------------------------------------------------------
@partial(jax.jit, static_argnames=("k", "iters", "block"))
def _nndescent_jit(vecs: jax.Array, init_ids: jax.Array, k: int, iters: int,
                   block: int = 1024):
    n = vecs.shape[0]
    vn = jnp.sum(vecs * vecs, axis=-1)

    def dist_rows(ids):                                     # (n,c) -> dists
        c = ids.shape[1]

        def one(i):
            rows = jax.lax.dynamic_slice_in_dim(ids, i * block, block)   # (b,c)
            q = jax.lax.dynamic_slice_in_dim(vecs, i * block, block)     # (b,d)
            nb = vecs[rows]                                              # (b,c,d)
            dots = jnp.einsum("bd,bcd->bc", q, nb)
            d = vn[rows] - 2.0 * dots + jnp.sum(q * q, -1, keepdims=True)
            return jnp.maximum(d, 0.0)

        assert n % block == 0, (n, block)
        return jax.lax.map(one, jnp.arange(n // block)).reshape(n, c)

    def merge(ids_a, d_a, ids_b, d_b):
        ids = jnp.concatenate([ids_a, ids_b], axis=1)
        d = jnp.concatenate([d_a, d_b], axis=1)
        # dedupe: mark repeats with +inf (sort by id, equal-neighbor mask)
        order = jnp.argsort(ids, axis=1)
        ids_s = jnp.take_along_axis(ids, order, axis=1)
        d_s = jnp.take_along_axis(d, order, axis=1)
        dup = jnp.concatenate(
            [jnp.zeros((n, 1), bool), ids_s[:, 1:] == ids_s[:, :-1]], axis=1)
        self_m = ids_s == jnp.arange(n)[:, None]
        d_s = jnp.where(dup | self_m, jnp.inf, d_s)
        nd, sel = jax.lax.top_k(-d_s, k)
        return jnp.take_along_axis(ids_s, sel, axis=1), -nd

    d0 = dist_rows(init_ids)
    ids, d = merge(init_ids, d0, init_ids, d0)

    def body(_, state):
        ids, d = state
        # neighbors-of-neighbors (forward); reverse edges via transpose sample
        non = ids[ids].reshape(n, -1)                       # (n, k*k)
        d_non = dist_rows(non)
        return merge(ids, d, non, d_non)

    ids, d = jax.lax.fori_loop(0, iters, body, (ids, d))
    return ids, d


def nndescent(vecs: np.ndarray, k: int, iters: int = 6, seed: int = 0,
              block: int = 1024):
    """Approximate KNN graph via fixed-iteration vectorized NNDescent."""
    n = vecs.shape[0]
    pad = (-n) % block
    if pad:
        vecs = np.concatenate(
            [vecs, 1e9 * np.ones((pad, vecs.shape[1]), np.float32)])
    rng = np.random.default_rng(seed)
    init = rng.integers(0, n, (n + pad, k)).astype(np.int32)
    ids, d = _nndescent_jit(jnp.asarray(vecs, jnp.float32), jnp.asarray(init),
                            k, iters, block)
    return np.asarray(d[:n]), np.asarray(ids[:n])


def knn_recall(approx_ids: np.ndarray, exact_ids: np.ndarray) -> float:
    hits = sum(len(set(a) & set(e)) for a, e in zip(approx_ids, exact_ids))
    return hits / exact_ids.size
