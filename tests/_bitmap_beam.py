"""Test-only oracle: the single-node beam search with an (n+1,) visited
bitmap, the form ``beam_search_batch(beam_width=1)`` took before its visited
set became an n-independent hash table.  The served search must return the
same ids, distances and hop counts; its ``ndist`` may only be larger (a node
the table forgot is re-scored, then dropped by the merge)."""
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.beam import _pool_finish, rerank_pool

INF = jnp.inf


@partial(jax.jit, static_argnames=("k", "ef", "max_steps", "early_stop"))
def bitmap_beam(vecs, nbrs, qv, lo, hi, entry, *, k=10, ef=64, max_steps=0,
                early_stop=True, quant=None, live=None):
    n, m = nbrs.shape
    steps_cap = max_steps or 8 * ef + 64
    if live is not None:
        live = live.astype(bool)
    score_x, score_scale = (vecs, None) if quant is None else quant

    def rows(ids):
        nv = score_x[ids].astype(jnp.float32)
        return nv if score_scale is None else nv * score_scale[None, :]

    def one_query(q, L, R, e0):
        empty = L > R
        e0 = jnp.atleast_1d(e0)[:ef]
        ev = (e0 >= 0) & ~empty
        e0c = jnp.clip(e0, 0, n - 1)
        ne = e0.shape[0]
        d0 = jnp.where(ev, jnp.sum(jnp.square(rows(e0c) - q[None, :]), -1),
                       INF)
        cand_ids = jnp.full((ef,), -1, jnp.int32).at[:ne].set(
            e0c.astype(jnp.int32))
        cand_d = jnp.full((ef,), INF).at[:ne].set(d0)
        expanded = jnp.zeros((ef,), bool).at[:ne].set(~ev)
        visited = jnp.zeros((n + 1,), bool).at[jnp.where(ev, e0c, n)].set(True)

        def cond(st):
            cand_d, expanded, _, _, steps, _ = st
            best = jnp.min(jnp.where(~expanded, cand_d, INF))
            worst = jnp.max(jnp.where(jnp.isfinite(cand_d), cand_d, -INF))
            worst = jnp.where(jnp.any(~jnp.isfinite(cand_d)), INF, worst)
            go = (best <= worst) & (steps < steps_cap)
            if early_stop:
                go &= jnp.isfinite(best)
            return go

        def body(st):
            cand_d, expanded, cand_ids, visited, steps, ndist = st
            bi = jnp.argmin(jnp.where(~expanded, cand_d, INF))
            expanded = expanded.at[bi].set(True)
            nb = nbrs[jnp.maximum(cand_ids[bi], 0)]
            valid = (nb >= 0) & (nb >= L) & (nb <= R)
            valid = valid & ~visited[jnp.maximum(nb, 0)]
            visited = visited.at[jnp.where(valid, nb, n)].set(True)
            diff = rows(jnp.maximum(nb, 0)) - q[None, :]
            d_nb = jnp.where(valid, jnp.sum(diff * diff, axis=-1), INF)
            ids_all = jnp.concatenate([cand_ids, nb.astype(jnp.int32)])
            d_all = jnp.concatenate([cand_d, d_nb])
            exp_all = jnp.concatenate([expanded, ~valid])
            order = jnp.argsort(d_all, stable=True)[:ef]
            return (d_all[order], exp_all[order], ids_all[order], visited,
                    steps + 1, ndist + jnp.sum(valid))

        zero = jnp.zeros((), jnp.int32)
        cand_d, _, cand_ids, _, steps, ndist = jax.lax.while_loop(
            cond, body, (cand_d, expanded, cand_ids, visited, zero, zero))
        out_ids, out_d = _pool_finish(cand_d, cand_ids, live, k, quant)
        return out_ids, out_d, steps, ndist

    ids, dists, steps, ndist = jax.vmap(one_query)(qv, lo, hi, entry)
    if quant is not None:
        ids, dists = rerank_pool(vecs, ids, qv, k, False)
    return ids, dists, {"hops": steps, "ndist": ndist}
