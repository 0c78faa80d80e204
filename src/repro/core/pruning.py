"""Algorithm 1 — Fast Range-Aware Pruning (RRNGPrune).

Two implementations:

* ``rrng_prune_np``: faithful per-node reference (numpy), matching the paper's
  pseudocode line by line (split at x.a — Lemma 4.1; scan each side by
  ascending attribute gap — Lemma 4.2; keep ≤ m/2 per side).
* ``prune_all_jax``: vectorized construction engine.  Per node the candidate
  side-arrays are pre-sorted by rank gap; the sequential keep/prune recurrence
  runs as a ``lax.fori_loop`` over candidates against precomputed distance
  tiles (the MXU-friendly form — see kernels/l2dist for the TPU tile).

Ids are attribute ranks (dataset pre-sorted by attribute).
"""
from __future__ import annotations

from functools import partial
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _sq(a, b):
    diff = a - b
    return float(np.dot(diff, diff))


def rrng_prune_np(x: int, cands: np.ndarray, vecs: np.ndarray, m: int) -> List[int]:
    """Faithful Algorithm 1. cands: candidate ids (any order, != x)."""
    cands = np.asarray([c for c in np.unique(cands) if c != x and c >= 0])
    c_l = sorted([c for c in cands if c < x], key=lambda c: x - c)   # asc gap
    c_r = sorted([c for c in cands if c > x], key=lambda c: c - x)
    half = max(m // 2, 1)

    def prune(side):
        kept: List[int] = []
        for vi in side:
            d_xi = _sq(vecs[x], vecs[vi])
            ok = True
            for vj in kept:
                if _sq(vecs[x], vecs[vj]) < d_xi and _sq(vecs[vj], vecs[vi]) < d_xi:
                    ok = False
                    break
            if ok and len(kept) < half:
                kept.append(vi)
        return kept

    return prune(c_l) + prune(c_r)


# ----------------------------------------------------------------------
def prune_side(x_vecs, cand_ids, cand_vecs, m_half: int):
    """x_vecs: (B,d); cand_ids: (B,C) gap-sorted, -1 pad; cand_vecs: (B,C,d).
    Returns kept mask (B,C) honoring the sequential RRNG rule + cap.
    Pure traceable body — also inlined per slab by the sharded builder
    (``repro.core.build_sharded``); every op is row-independent, so block
    and shard partitioning cannot change any row's result."""
    d_xc = jnp.sum(jnp.square(cand_vecs - x_vecs[:, None, :]), axis=-1)   # (B,C)
    # candidate-candidate distance tiles
    cn = jnp.sum(cand_vecs * cand_vecs, axis=-1)
    # full f32 product (a TPU's default is one bf16 pass): the keep/prune
    # comparisons below must match the exact distances
    d_cc = (cn[:, :, None]
            - 2.0 * jnp.einsum("bcd,bed->bce", cand_vecs, cand_vecs,
                               precision=jax.lax.Precision.HIGHEST)
            + cn[:, None, :])
    d_cc = jnp.maximum(d_cc, 0.0)
    valid = cand_ids >= 0
    C = cand_ids.shape[1]

    def body(i, kept):
        d_xi = d_xc[:, i]
        # pruned iff ∃ kept j (earlier, smaller gap): d_xj < d_xi ∧ d_ji < d_xi
        conflict = kept & (d_xc < d_xi[:, None]) & (d_cc[:, i, :] < d_xi[:, None])
        pruned = jnp.any(conflict, axis=1)
        under = jnp.sum(kept, axis=1) < m_half
        keep_i = valid[:, i] & ~pruned & under
        return kept.at[:, i].set(keep_i)

    kept = jax.lax.fori_loop(0, C, body, jnp.zeros_like(valid))
    return kept


_prune_side_batch = partial(jax.jit, static_argnames=("m_half",))(prune_side)


def pack_kept(cand_l, kept_l, cand_r, kept_r, m: int):
    """Compact the kept candidates of both sides into (B, m) neighbor ids,
    -1 padded — left-side keeps first (in gap order), then right, truncated
    at m.  A stable argsort on the ~kept mask is the vectorized equivalent
    of the per-row ``concatenate(cand[kept])`` pack (stability preserves
    the within-side candidate order and the left-before-right concat
    order), so the output is bit-identical to the sequential pack."""
    cand = jnp.concatenate([cand_l, cand_r], axis=1)
    kept = jnp.concatenate([kept_l, kept_r], axis=1)
    order = jnp.argsort(~kept, axis=1, stable=True)
    cand = jnp.take_along_axis(cand, order, axis=1)
    kept = jnp.take_along_axis(kept, order, axis=1)
    c2 = cand.shape[1]
    if c2 < m:
        cand = jnp.pad(cand, ((0, 0), (0, m - c2)), constant_values=-1)
        kept = jnp.pad(kept, ((0, 0), (0, m - c2)))
    return jnp.where(kept[:, :m], cand[:, :m], -1).astype(jnp.int32)


@partial(jax.jit, static_argnames=("m", "m_half"))
def _prune_pack_block(x_vecs, cand_l, cv_l, cand_r, cv_r, m: int,
                      m_half: int):
    kept_l = prune_side(x_vecs, cand_l, cv_l, m_half)
    kept_r = prune_side(x_vecs, cand_r, cv_r, m_half)
    return pack_kept(cand_l, kept_l, cand_r, kept_r, m)


def prune_all_jax(vecs: np.ndarray, cand_l: np.ndarray, cand_r: np.ndarray,
                  m: int, block: int = 2048) -> np.ndarray:
    """Run Algorithm 1 for every node. cand_l/cand_r: (n, Ch) rank-gap-sorted
    candidate ids per side (-1 padded). Returns (n, m) neighbor ids (-1 pad).
    The keep/prune recurrence and the kept→adjacency pack both run on
    device (``_prune_pack_block``); the host loop only enqueues row blocks
    and reads the adjacency back once."""
    n = vecs.shape[0]
    half = max(m // 2, 1)
    v = jnp.asarray(vecs, jnp.float32)
    out = []
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        xv = v[lo:hi]
        ci_l = jnp.asarray(cand_l[lo:hi], jnp.int32)
        ci_r = jnp.asarray(cand_r[lo:hi], jnp.int32)
        out.append(_prune_pack_block(
            xv, ci_l, v[jnp.maximum(ci_l, 0)],
            ci_r, v[jnp.maximum(ci_r, 0)], m, half))
    if not out:
        return np.full((0, m), -1, np.int32)
    return np.asarray(jnp.concatenate(out))
