"""Range-filtered beam search over the RNSG, in pure ``jax.lax`` control flow.

The search never materializes the induced subgraph: the range filter is an
id-interval mask applied to neighbor expansions (ids are attribute ranks), and
Theorem 4.7 (heredity) guarantees this equals searching the induced RNSG.

Two hot paths share one ``while_loop``-per-query / ``vmap``-over-batch shape,
and both carry a visited set whose size does not depend on the corpus size n
(a vmapped batch carries (Q, H) state, never (Q, n+1)):

* ``beam_width=1`` — single-node expansion: candidate pool = (ef,) arrays
  re-argsorted (stable) over ``[pool, fresh]`` each hop.
* ``beam_width=B>1`` — kernel-fused batched expansion: each iteration pops
  the best ``B`` unexpanded candidates, scores all ``B*m`` neighbors in one
  fused gather+score call, and folds them into the sorted pool with a
  bounded O(ef+B*m) merge (sort only the fresh distances, then a stable
  two-pointer merge via ``searchsorted`` — never a full pool argsort).

The visited set is a **fixed-size lossy hash table** (2-probe,
open-addressed, sized from ``ef`` and ``m`` — see
``visited_table_size``) backed by a pool-membership test.  Collisions only
ever cause false *negatives*: a forgotten node is re-scored, and the merge
provably drops it — it was pushed out of (or kept out of) the pool by ef
entries no worse than it, the pool's worst distance is monotonically
non-increasing once full, and pool entries win distance ties — so ids,
distances and hop counts are exactly those of an (n+1,) visited bitmap
(the test suite keeps that bitmap search as its oracle); only the ``ndist``
counter grows, by the re-scores, and ``evictions`` counts the ids the table
forgot.

Each hop's phases run under ``jax.named_scope`` — ``beam.expand`` (pick the
node(s) to expand, gather and score their neighbors), ``beam.visited``
(the visited-set test and update), ``beam.merge`` (fold the fresh
candidates into the pool) and ``beam.finish`` (tombstone filter, top-k or
rerank) — so the compiled program's op metadata, and a device profile,
name the phase each op belongs to.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

INF = jnp.inf

# Knuth / Murmur-style odd multipliers for the two probe hashes.
_HASH1 = 2654435761
_HASH2 = 2246822519


def visited_table_size(ef: int, m: int, beam_width: int = 1) -> int:
    """Slots in the per-query lossy visited table (power of two).

    Deliberately **independent of n**: the table replaces an (n+1,) bitmask.

    ``beam_width=1`` inserts every in-range neighbor it scores, about
    ``ef·m`` ids a search; the table takes four times that, so its load
    stays under a quarter and few inserts evict (a 2-probe table that
    overwrites its second probe loses about α²/3 of the inserts made while
    it fills to load α).  Its re-scores cost no device work (every hop
    scores all m neighbors and masks them); they only show in ``ndist``.

    ``beam_width>1`` scores ~ef·m̄ distinct nodes, but most re-discoveries
    are already caught by the pool-membership and intra-hop dedup, so ~half
    a slot per potential insertion keeps the re-score rate in the low
    percent while the carried (Q, H) loop state stays small (the table is
    copied once per iteration on backends that can't scatter in place, so
    oversizing it costs more than the re-scores it prevents)."""
    ef, m = max(int(ef), 1), max(int(m), 1)
    target = ef * max(m, 4) // 2 if beam_width > 1 else 4 * ef * m
    size = 1 << (target - 1).bit_length()
    return int(min(max(size, 256), 1 << 13))


def _hash_slots(ids: jax.Array, size: int) -> Tuple[jax.Array, jax.Array]:
    """Two independent probe slots in [0, size) for each id (size pow2)."""
    bits = int(size).bit_length() - 1
    u = ids.astype(jnp.uint32)
    h1 = ((u * jnp.uint32(_HASH1)) >> (32 - bits)).astype(jnp.int32)
    h2 = ((u * jnp.uint32(_HASH2)) >> (32 - bits)).astype(jnp.int32)
    return h1, h2


def _table_insert(table: jax.Array, ids: jax.Array,
                  size: int) -> Tuple[jax.Array, jax.Array]:
    """Insert distinct ids (−1 = skip; none already stored) into the 2-probe
    table ((size+1,), slot ``size`` is the write sink).  First probe wins if
    its slot is empty or already holds the id; otherwise the second probe
    is overwritten — lossy by design, a forgotten id is merely re-scored if
    met again.  Returns the table and the number of ids it forgot: the ids
    inserted less the empty slots they filled (an overwritten occupant, or
    one of two new ids written to one slot).  Each written slot keeps
    exactly one of the distinct ids sent to it, so a slot counts as filled
    through its one surviving id — O(len(ids)) gathers, no pairwise test."""
    valid = ids >= 0
    h1, h2 = _hash_slots(ids, size)
    cur = table[h1]
    slot = jnp.where((cur == -1) | (cur == ids), h1, h2)
    slot = jnp.where(valid, slot, size)
    new = table.at[slot].set(jnp.where(valid, ids, -1))
    filled = valid & (new[slot] == ids) & (table[slot] == -1)
    return new, jnp.sum(valid) - jnp.sum(filled)


def _table_lookup(table: jax.Array, ids: jax.Array, size: int) -> jax.Array:
    """Membership test: exact-positive (the slot stores the id itself, so a
    hit is never spurious), lossy-negative (an evicted id reads as new)."""
    h1, h2 = _hash_slots(ids, size)
    return (table[h1] == ids) | (table[h2] == ids)


def _merge_sorted(pool_d, pool_i, pool_e, fresh_d, fresh_i, fresh_e, ef: int):
    """Stable bounded merge: two distance-sorted candidate lists -> the best
    ``ef``.  The batched path's replacement for the single-node path's
    full argsort over the (ef+m) pool: one ``searchsorted`` places every
    pool entry in the merged order (pool entries win distance ties,
    matching the stable argsort over ``[pool, fresh]`` the single-node path
    performs), a second
    inverts that placement so each output lane *gathers* its element —
    scatter-free on purpose, vmapped scatters serialize on CPU/XLA while
    gathers vectorize."""
    f = fresh_d.shape[0]
    # the two searchsorted calls below are a sorted-list *merge*, not rank
    # resolution — exempted from the single-source-resolve guard
    pos_p = jnp.arange(ef) + jnp.searchsorted(                # sorted-merge
        fresh_d, pool_d, side="left")
    j = jnp.arange(ef)
    i = jnp.searchsorted(pos_p, j, side="left")               # sorted-merge
    ic = jnp.minimum(i, ef - 1)
    is_pool = pos_p[ic] == j
    jf = jnp.clip(j - i, 0, f - 1)                # fresh index for non-pool lanes
    md = jnp.where(is_pool, pool_d[ic], fresh_d[jf])
    mi = jnp.where(is_pool, pool_i[ic], fresh_i[jf])
    me = jnp.where(is_pool, pool_e[ic], fresh_e[jf])
    return md, mi, me


def rerank_pool(vecs, pool_ids, qv, k: int, use_kernel: bool):
    """Exact f32 rescore of each query's final candidate pool — the rerank
    stage of the quantized beam: traversal ordered by quantized distances,
    the returned top-k rescored against the f32 vectors.  Pool ids are
    sorted ascending first (``sort_candidates``) so the stable tie-breaking
    of the top-k matches the exact path's tie-toward-lower-rank."""
    from repro.kernels.quantize import sort_candidates
    ids_s = sort_candidates(pool_ids)                        # (Q, ef)
    if use_kernel and k <= 128:
        from repro.kernels.ops import gather_rerank
        return gather_rerank(vecs, ids_s, qv, k=k)
    rows = vecs[jnp.maximum(ids_s, 0)]                       # (Q, ef, d)
    d2 = jnp.sum(jnp.square(rows - qv[:, None, :]), axis=-1)
    d2 = jnp.where(ids_s >= 0, d2, INF)
    neg, sel = jax.lax.top_k(-d2, k)
    ids = jnp.where(jnp.isfinite(neg), jnp.take_along_axis(ids_s, sel,
                                                           axis=1), -1)
    return ids, -neg


def _pool_finish(cand_d, cand_ids, live, k: int, quant):
    """Final per-query pool stage shared by both expansion paths: drop
    tombstoned candidates (``live`` (n,) bool — FreshDiskANN semantics:
    deleted nodes stay *traversable* routing nodes all through the search,
    they just never leave it), then slice the top-k, or hand the pool to
    the f32 rerank.  The argsort after masking is stable, so surviving
    candidates keep their ascending-distance / tie-toward-lower-rank
    order."""
    if live is not None:
        dead = (cand_ids < 0) | ~live[jnp.maximum(cand_ids, 0)]
        cand_d = jnp.where(dead, INF, cand_d)
        o = jnp.argsort(cand_d)
        cand_d, cand_ids = cand_d[o], cand_ids[o]
    if quant is not None:           # return the full pool for the f32 rerank
        return jnp.where(jnp.isfinite(cand_d), cand_ids, -1), cand_d
    return (jnp.where(jnp.isfinite(cand_d[:k]), cand_ids[:k], -1),
            cand_d[:k])


@partial(jax.jit, static_argnames=("k", "ef", "max_steps", "use_kernel",
                                   "early_stop", "beam_width",
                                   "_visited_slots"))
def beam_search_batch(vecs: jax.Array, nbrs: jax.Array, qv: jax.Array,
                      lo: jax.Array, hi: jax.Array, entry: jax.Array,
                      *, k: int = 10, ef: int = 64, max_steps: int = 0,
                      use_kernel: bool = False, early_stop: bool = True,
                      beam_width: int = 1, quant=None, live=None,
                      _visited_slots: int = 0):
    """vecs:(n,d) f32; nbrs:(n,m) i32; qv:(Q,d); lo/hi/entry:(Q,) rank ids.
    Returns (ids:(Q,k) i32 rank ids (-1 pad), dists:(Q,k), stats dict of
    per-query ``hops``, ``ndist`` (scored neighbors) and ``evictions``
    (ids the visited table forgot)).

    ``quant=(data, scale)`` switches neighbor scoring to the quantized
    corpus copy (``data``: (n,d) int8/bf16 in the same rank order;
    ``scale``: (d,) f32 per-dim dequant factors, or None for bf16) — the
    traversal then moves 4x/2x fewer bytes per scored neighbor, and the
    final pool is rescored in f32 (``rerank_pool``) before the top-k is
    taken, so whenever the pool saw every true neighbor (any time the f32
    search would return them, e.g. the exhaustive ``ef ≥ |interval|``
    regime) the returned id set is exactly the f32 one.

    ``early_stop`` exits the while_loop as soon as no finite unexpanded
    candidate remains.  When the in-range node count is below ``ef`` the
    pool never fills, so the worst-candidate bound stays +inf and the
    legacy condition (kept under ``early_stop=False`` for A/B benchmarks)
    burns the full ``steps_cap``; the results are identical either way —
    the extra iterations re-expand the best already-expanded node, whose
    neighbors are all visited.

    ``beam_width=B>1`` expands the best B unexpanded candidates per
    iteration (batched-expansion path, see module docstring; widths beyond
    ``ef`` are clamped — the pool only ever holds ``ef`` candidates);
    ``hops`` in the stats then counts *iterations* (≈ node expansions / B),
    while ``ndist`` stays the number of scored neighbors and is comparable
    across widths.

    ``live`` ((n,) bool, optional) is the streaming tombstone mask: dead
    nodes are traversed exactly like live ones (they keep the graph
    navigable — removing them would break the heredity argument) but are
    filtered out of the final pool before the top-k / rerank.

    ``_visited_slots`` overrides the visited table's size (a power of two;
    0 sizes it by ``visited_table_size``) so tests can force evictions."""
    n, m = nbrs.shape
    steps_cap = max_steps or 8 * ef + 64
    H = _visited_slots or visited_table_size(ef, m, beam_width)
    if live is not None:
        live = live.astype(bool)

    if beam_width > 1:
        return _beam_batched(vecs, nbrs, qv, lo, hi, entry, k=k, ef=ef,
                             steps_cap=steps_cap, use_kernel=use_kernel,
                             early_stop=early_stop, beam_width=beam_width,
                             H=H, quant=quant, live=live)

    # traversal scores against the quantized copy when one is given (the
    # dtype is trace-static, so the scale branch costs nothing at runtime)
    score_x, score_scale = (vecs, None) if quant is None else quant

    if use_kernel:
        from repro.kernels.ops import gather_dist as _gd
    else:
        _gd = None

    def neighbor_dists(q, ids, valid):
        if _gd is not None:
            d = _gd(score_x, ids, q, scale=score_scale)
        else:
            nv = score_x[jnp.maximum(ids, 0)].astype(jnp.float32)
            if score_scale is not None:
                nv = nv * score_scale[None, :]
            diff = nv - q[None, :]
            d = jnp.sum(diff * diff, axis=-1)
        return jnp.where(valid, d, INF)

    def entry_dists(q, e0c, ev):
        nv = score_x[e0c].astype(jnp.float32)
        if score_scale is not None:
            nv = nv * score_scale[None, :]
        return jnp.where(ev, jnp.sum(jnp.square(nv - q[None, :]), axis=-1),
                         INF)

    def one_query(q, L, R, e0):
        empty = L > R
        e0 = jnp.atleast_1d(e0)[:ef]                          # (E,) multi-entry
        ev = (e0 >= 0) & ~empty
        e0c = jnp.clip(e0, 0, n - 1)
        ne = e0.shape[0]
        d0 = entry_dists(q, e0c, ev)
        cand_ids = jnp.full((ef,), -1, jnp.int32).at[:ne].set(e0c.astype(jnp.int32))
        cand_d = jnp.full((ef,), INF).at[:ne].set(d0)
        expanded = jnp.zeros((ef,), bool).at[:ne].set(~ev)
        table, _ = _table_insert(jnp.full((H + 1,), -1, jnp.int32),
                                 jnp.where(ev, e0c.astype(jnp.int32), -1), H)

        def cond(st):
            cand_d, expanded, _, _, steps, _, _ = st
            unexp = jnp.where(~expanded, cand_d, INF)
            best = jnp.min(unexp)
            worst = jnp.max(jnp.where(jnp.isfinite(cand_d), cand_d, -INF))
            worst = jnp.where(jnp.any(~jnp.isfinite(cand_d)), INF, worst)
            go = (best <= worst) & (steps < steps_cap)
            if early_stop:
                go &= jnp.isfinite(best)
            return go

        def body(st):
            cand_d, expanded, cand_ids, table, steps, ndist, lost = st
            with jax.named_scope("beam.expand"):
                unexp = jnp.where(~expanded, cand_d, INF)
                bi = jnp.argmin(unexp)
                expanded = expanded.at[bi].set(True)
                node = jnp.maximum(cand_ids[bi], 0)
                nb = nbrs[node].astype(jnp.int32)             # (m,)
                valid = (nb >= 0) & (nb >= L) & (nb <= R)
            with jax.named_scope("beam.visited"):
                # every node ever scored is either held in the pool with a
                # finite distance or in the table, unless the table forgot
                # it after the pool dropped it — then the re-score ranks it
                # behind all ef pool entries and the merge drops it again
                # (module docstring); unfilled slots (inf) hold no node
                in_pool = jnp.any((nb[:, None] == cand_ids[None, :])
                                  & jnp.isfinite(cand_d)[None, :], axis=1)
                valid &= ~in_pool & ~_table_lookup(table, nb, H)
                table, lost_h = _table_insert(
                    table, jnp.where(valid, nb, -1), H)
            with jax.named_scope("beam.expand"):
                d_nb = neighbor_dists(q, nb, valid)
            with jax.named_scope("beam.merge"):
                ids_all = jnp.concatenate([cand_ids, nb])
                d_all = jnp.concatenate([cand_d, d_nb])
                # invalid neighbors: never expand
                exp_all = jnp.concatenate([expanded, ~valid])
                order = jnp.argsort(d_all, stable=True)[:ef]
                return (d_all[order], exp_all[order], ids_all[order],
                        table, steps + 1, ndist + jnp.sum(valid),
                        lost + lost_h)

        zero = jnp.zeros((), jnp.int32)
        st = (cand_d, expanded, cand_ids, table, zero, zero, zero)
        cand_d, _, cand_ids, _, steps, ndist, lost = jax.lax.while_loop(
            cond, body, st)
        with jax.named_scope("beam.finish"):
            out_ids, out_d = _pool_finish(cand_d, cand_ids, live, k, quant)
        return out_ids, out_d, steps, ndist, lost

    ids, dists, steps, ndist, lost = jax.vmap(one_query)(qv, lo, hi, entry)
    if quant is not None:
        with jax.named_scope("beam.finish"):
            ids, dists = rerank_pool(vecs, ids, qv, k, use_kernel)
    return ids, dists, {"hops": steps, "ndist": ndist, "evictions": lost}


# ======================================================================
# Batched multi-node expansion (beam_width > 1)
# ======================================================================
def _beam_batched(vecs, nbrs, qv, lo, hi, entry, *, k: int, ef: int,
                  steps_cap: int, use_kernel: bool, early_stop: bool,
                  beam_width: int, H: int, quant=None, live=None):
    n, m = nbrs.shape
    score_x, score_scale = (vecs, None) if quant is None else quant
    # the pool holds ef candidates, so at most ef can be unexpanded — a
    # wider request (e.g. --beam-width 128 at the default ef=64) is clamped
    # rather than rejected
    B = min(int(beam_width), ef)
    F = B * m                           # fresh neighbors per iteration
    # only the best min(F, ef) fresh candidates can survive the bounded
    # merge, so the fused kernel keeps a running top-fm in VMEM and the
    # full (F,) distance vector never leaves it
    fm = min(F, ef)

    if use_kernel:
        from repro.kernels.ops import gather_dist as _gd
        from repro.kernels.ops import gather_topk as _gtk
        kernel_topk = fm <= 128         # running top-k lives in one lane row
    else:
        _gd = _gtk = None
        kernel_topk = False

    def fresh_sorted(q, ids_f, valid):
        """(F,) masked neighbor ids -> distance-sorted (fm,) fresh list
        (ids -1 / dist inf beyond the valid entries)."""
        ids_m = jnp.where(valid, ids_f, -1)
        if kernel_topk:
            fi, fd = _gtk(score_x, ids_m, q, k=fm, scale=score_scale)
            return fd, fi
        if _gd is not None:
            d = jnp.where(valid, _gd(score_x, ids_f, q, scale=score_scale),
                          INF)
        else:
            nv = score_x[jnp.maximum(ids_f, 0)].astype(jnp.float32)
            if score_scale is not None:
                nv = nv * score_scale[None, :]
            diff = nv - q[None, :]
            d = jnp.where(valid, jnp.sum(diff * diff, axis=-1), INF)
        o = jnp.argsort(d)[:fm]         # sort F fresh values, never the pool
        return d[o], ids_m[o]

    def one_query(q, L, R, e0):
        empty = L > R
        e0 = jnp.atleast_1d(e0)[:ef]
        ev = (e0 >= 0) & ~empty
        e0c = jnp.clip(e0, 0, n - 1)
        ne = e0.shape[0]
        nv0 = score_x[e0c].astype(jnp.float32)
        if score_scale is not None:
            nv0 = nv0 * score_scale[None, :]
        d0 = jnp.sum(jnp.square(nv0 - q[None, :]), axis=-1)
        d0 = jnp.where(ev, d0, INF)
        cand_ids = jnp.full((ef,), -1, jnp.int32).at[:ne].set(
            e0c.astype(jnp.int32))
        cand_d = jnp.full((ef,), INF).at[:ne].set(d0)
        expanded = jnp.zeros((ef,), bool).at[:ne].set(~ev)
        o = jnp.argsort(cand_d)         # sort once; the merge keeps it sorted
        cand_d, cand_ids, expanded = cand_d[o], cand_ids[o], expanded[o]
        table, _ = _table_insert(jnp.full((H + 1,), -1, jnp.int32),
                                 jnp.where(ev, e0c.astype(jnp.int32), -1), H)

        def cond(st):
            cand_d, expanded, _, _, steps, _, _ = st
            unexp = jnp.where(~expanded, cand_d, INF)
            best = jnp.min(unexp)
            worst = jnp.max(jnp.where(jnp.isfinite(cand_d), cand_d, -INF))
            worst = jnp.where(jnp.any(~jnp.isfinite(cand_d)), INF, worst)
            go = (best <= worst) & (steps < steps_cap)
            if early_stop:
                go &= jnp.isfinite(best)
            return go

        def body(st):
            cand_d, expanded, cand_ids, table, steps, ndist, lost = st
            with jax.named_scope("beam.expand"):
                # best B unexpanded: the pool is sorted, so they are the
                # first B selectable lanes
                lane = jnp.where(~expanded & jnp.isfinite(cand_d),
                                 jnp.arange(ef), ef)
                lanes = jnp.sort(lane)[:B]                   # (B,)
                take = lanes < ef
                node = jnp.where(take,
                                 cand_ids[jnp.minimum(lanes, ef - 1)], -1)
                expanded = expanded | jnp.any(
                    (jnp.arange(ef)[None, :] == lanes[:, None])
                    & take[:, None], axis=0)
                nb = nbrs[jnp.maximum(node, 0)]              # (B, m)
                ids_f = nb.reshape(F).astype(jnp.int32)
                valid = ((ids_f >= 0) & (ids_f >= L) & (ids_f <= R)
                         & jnp.repeat(node >= 0, m))
            with jax.named_scope("beam.visited"):
                # intra-hop dedup: two expanded nodes may share a neighbor
                # — keep the first occurrence (the single-node path never sees
                # this: its single hop has unique neighbors)
                eq = ids_f[:, None] == ids_f[None, :]
                before = jnp.arange(F)[None, :] < jnp.arange(F)[:, None]
                valid &= ~jnp.any(eq & before & valid[None, :], axis=1)
                # pool-membership dedup: anything currently held in the
                # pool is by definition already scored (covers hash
                # evictions of live candidates — the exactness keystone,
                # see module docstring)
                valid &= ~jnp.any(ids_f[:, None] == cand_ids[None, :],
                                  axis=1)
                # lossy visited set: false negatives fall through to a
                # re-score
                valid &= ~_table_lookup(table, ids_f, H)
                table, lost_h = _table_insert(
                    table, jnp.where(valid, ids_f, -1), H)
            with jax.named_scope("beam.expand"):
                fd, fi = fresh_sorted(q, ids_f, valid)
            with jax.named_scope("beam.merge"):
                fe = fi < 0                                  # pads: never expand
                cand_d, cand_ids, expanded = _merge_sorted(
                    cand_d, cand_ids, expanded, fd, fi, fe, ef)
                return (cand_d, expanded, cand_ids, table,
                        steps + 1, ndist + jnp.sum(valid), lost + lost_h)

        zero = jnp.zeros((), jnp.int32)
        st = (cand_d, expanded, cand_ids, table, zero, zero, zero)
        cand_d, _, cand_ids, _, steps, ndist, lost = jax.lax.while_loop(
            cond, body, st)
        with jax.named_scope("beam.finish"):
            out_ids, out_d = _pool_finish(cand_d, cand_ids, live, k, quant)
        return out_ids, out_d, steps, ndist, lost

    ids, dists, steps, ndist, lost = jax.vmap(one_query)(qv, lo, hi, entry)
    if quant is not None:
        with jax.named_scope("beam.finish"):
            ids, dists = rerank_pool(vecs, ids, qv, k, use_kernel)
    return ids, dists, {"hops": steps, "ndist": ndist, "evictions": lost}
