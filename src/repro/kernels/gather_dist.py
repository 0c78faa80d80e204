"""Pallas TPU kernels: fused neighbor gather + squared-L2 scoring, blocked.

The beam-search expansion hot path: gather M arbitrary rows of X (HBM) and
score them against one query.  The neighbor ids are *scalar-prefetched* so
the BlockSpec index_map can steer each grid step's DMA to the right row of X
— the TPU-native replacement for the CPU pointer-chase.

Both kernels process the id vector in **row tiles** of T ids: the grid is
``(num_tiles, T)``, the innermost dimension walks the tile, and each row's
Σ(x−q)² lands in a lane of a (1, T) VMEM accumulator.  A TPU block's last
two dims must divide the (sublane, lane) tile or equal the array's, so a
steered step cannot DMA one (1, d) row: it DMAs the **aligned row group**
``(R, d)`` holding the row (R = one sublane tile: 8 f32 / 16 bf16 / 32 int8
rows, so every dtype moves the same bytes) and selects the row in VMEM with
a one-hot sublane mask.  Mosaic pipelines the group DMAs across steps.
Work leaves VMEM once per *tile*, not once per row:

* ``gather_dist_pallas`` — writes the accumulated (1, T) distance block to
  the output on the tile's last step (full (M,) distances, the legacy
  contract: negative/out-of-range ids are clipped, callers mask).
* ``gather_topk_pallas`` — instead folds the masked tile (ids < 0 → +inf)
  into a per-query **running top-k** held in (1, T)-lane output blocks
  (dists + ids), mirroring the ``range_scan`` running-top-k trick: a
  k-step select-min over the 2-block lane union (vector argmin + one-hot
  updates, so it lowers on both Mosaic and interpret backends).  The full
  (M,) distance vector never round-trips to HBM — only the merge
  survivors the batched beam's bounded frontier merge actually consumes.
  Ties break toward the lower input index, matching a stable
  ``jnp.argsort`` over the materialized distances.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _tile(m: int, cap: int = 128) -> int:
    """Row-tile size for an id vector of length m (pow2, ≤ cap)."""
    return int(min(cap, 1 << max(int(m) - 1, 0).bit_length() if m > 1 else 1))


def _row_group(x: jax.Array) -> int:
    """Rows per steered DMA: one sublane tile of x's dtype (32-bit words
    pack 4/itemsize rows per sublane), or all of x when it is shorter."""
    return int(min(8 * max(4 // x.dtype.itemsize, 1), x.shape[0]))


def _row_spec(x: jax.Array, flat_pos):
    """BlockSpec steering the aligned row group that holds the id at flat
    position ``flat_pos(*grid_idx)`` of the scalar-prefetched id table."""
    r = _row_group(x)
    return pl.BlockSpec(
        (r, x.shape[1]),
        lambda *a: (a[-1][flat_pos(*a[:-1])] // r, 0))


def _row_d2(x_ref, q_ref, scale_ref, row):
    """Σ(x−q)² of corpus row ``row``: picked out of the DMA'd aligned row
    group by a one-hot sublane mask, dequantized in VMEM when the corpus is
    int8 (``scale_ref`` holds the (1, d) per-dimension factors)."""
    grp = x_ref[...].astype(jnp.float32)                 # (R, d)
    r = grp.shape[0]
    sel = jax.lax.broadcasted_iota(jnp.int32, (r, 1), 0) == row % r
    xf = jnp.sum(jnp.where(sel, grp, 0.0), axis=0, keepdims=True)   # (1, d)
    if scale_ref is not None:
        xf = xf * scale_ref[...]
    diff = xf - q_ref[...].astype(jnp.float32)
    return jnp.sum(diff * diff)


def _dist_body(ids_ref, x_ref, q_ref, scale_ref, o_ref, acc_ref, *,
               tile: int):
    i = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    d2 = _row_d2(x_ref, q_ref, scale_ref, ids_ref[i * tile + t])
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1) == t
    acc_ref[...] = jnp.where(lane, d2, acc_ref[...])

    @pl.when(t == tile - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def _dist_kernel(ids_ref, x_ref, q_ref, o_ref, acc_ref, **kw):
    _dist_body(ids_ref, x_ref, q_ref, None, o_ref, acc_ref, **kw)


def _dist_kernel_scaled(ids_ref, x_ref, scale_ref, q_ref, o_ref, acc_ref,
                        **kw):
    _dist_body(ids_ref, x_ref, q_ref, scale_ref, o_ref, acc_ref, **kw)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_dist_pallas(x: jax.Array, ids: jax.Array, q: jax.Array, *,
                       interpret: bool = False,
                       scale: jax.Array | None = None) -> jax.Array:
    """x:(N,d); ids:(M,) int32; q:(d,) -> (M,) f32 squared distances.
    Out-of-range/negative ids are clipped (callers mask separately).
    ``x`` may be int8/bf16; ``scale`` ((d,) f32) dequantizes int8 rows."""
    n, d = x.shape
    m = ids.shape[0]
    tile = _tile(m)
    nt = -(-m // tile)
    ids_c = jnp.clip(ids, 0, n - 1).astype(jnp.int32)
    ids_c = jnp.pad(ids_c, (0, nt * tile - m))      # tail rows: row 0, sliced off
    x_spec = _row_spec(x, lambda i, t: i * tile + t)
    q_spec = pl.BlockSpec((1, d), lambda i, t, ids_ref: (0, 0))
    if scale is None:
        kernel, in_specs, ops = _dist_kernel, [x_spec, q_spec], (x, q[None, :])
    else:
        kernel = _dist_kernel_scaled
        in_specs = [x_spec, q_spec, q_spec]      # scale: one (1, d) block
        ops = (x, scale.astype(jnp.float32)[None, :], q[None, :])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt, tile),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, 1, tile),
                               lambda i, t, ids_ref: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((1, tile), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(kernel, tile=tile),
        grid_spec=grid_spec,
        name="gather_dist_pallas",     # stable op name in device profiles
        out_shape=jax.ShapeDtypeStruct((nt, 1, tile), jnp.float32),
        interpret=interpret,
    )(ids_c, *ops)
    return out.reshape(nt * tile)[:m]


def _fold_topk(acc_ref, idm_ref, od_ref, oi_ref, *, tile: int, k: int):
    """Fold one accumulated (1, tile) distance block into the running top-k
    held in the (1, tile) output lanes (dists + ids).  Shared by the
    single-query ``gather_topk`` and the batched ``gather_rerank``."""
    idv = idm_ref[...]                                   # (1, tile) i32
    d_blk = jnp.where(idv >= 0, acc_ref[...], jnp.inf)
    # union of the running top-k and this tile; tiles arrive in
    # ascending-id-index order and the running half comes first, so the
    # first-occurrence argmin breaks distance ties toward the lower
    # input index (matching a stable argsort of the full vector)
    cd = jnp.concatenate([od_ref[...], d_blk], axis=1)   # (1, 2*tile)
    ci = jnp.concatenate([oi_ref[...], idv], axis=1)
    lane_u = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * tile), 1)
    lane_o = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    new_d = jnp.full((1, tile), jnp.inf, jnp.float32)
    new_i = jnp.full((1, tile), -1, jnp.int32)
    for s in range(k):            # static unroll: k-step select-min
        mv = jnp.min(cd)
        sel = lane_u == jnp.argmin(cd).astype(jnp.int32)
        idn = jnp.sum(jnp.where(sel, ci, 0)).astype(jnp.int32)
        idn = jnp.where(jnp.isfinite(mv), idn, -1)
        new_d = jnp.where(lane_o == s, mv, new_d)
        new_i = jnp.where(lane_o == s, idn, new_i)
        cd = jnp.where(sel, jnp.inf, cd)
    od_ref[...] = new_d
    oi_ref[...] = new_i


def _topk_body(ids_ref, x_ref, q_ref, scale_ref, idm_ref, od_ref, oi_ref,
               acc_ref, *, tile: int, k: int):
    i = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when((i == 0) & (t == 0))
    def _init_topk():
        od_ref[...] = jnp.full_like(od_ref, jnp.inf)
        oi_ref[...] = jnp.full_like(oi_ref, -1)

    @pl.when(t == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    d2 = _row_d2(x_ref, q_ref, scale_ref, ids_ref[i * tile + t])
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1) == t
    acc_ref[...] = jnp.where(lane, d2, acc_ref[...])

    @pl.when(t == tile - 1)
    def _merge():
        _fold_topk(acc_ref, idm_ref, od_ref, oi_ref, tile=tile, k=k)


def _topk_kernel(ids_ref, x_ref, q_ref, idm_ref, od_ref, oi_ref, acc_ref,
                 **kw):
    _topk_body(ids_ref, x_ref, q_ref, None, idm_ref, od_ref, oi_ref, acc_ref,
               **kw)


def _topk_kernel_scaled(ids_ref, x_ref, scale_ref, q_ref, idm_ref, od_ref,
                        oi_ref, acc_ref, **kw):
    _topk_body(ids_ref, x_ref, q_ref, scale_ref, idm_ref, od_ref, oi_ref,
               acc_ref, **kw)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def gather_topk_pallas(x: jax.Array, ids: jax.Array, q: jax.Array, *,
                       k: int, interpret: bool = False,
                       scale: jax.Array | None = None):
    """x:(N,d); ids:(M,) int32, **negative = masked**; q:(d,).
    Returns (ids:(k,) i32 sorted by ascending distance (-1 pad),
    dists:(k,) f32, +inf pad) — the top-k over the *unmasked* ids only.
    ``x`` may be int8/bf16; ``scale`` ((d,) f32) dequantizes int8 rows.

    Requires ``k ≤ min(next_pow2(M), 128)`` (the running top-k lives in one
    lane row) and raises ``ValueError`` beyond it — callers needing a
    larger k must themselves use ``gather_dist`` + a host sort, as the
    batched beam's ``kernel_topk`` gate in ``core/beam.py`` does."""
    n, d = x.shape
    m = ids.shape[0]
    tile = _tile(max(m, k))             # lane row must hold k survivors
    if k > tile:
        raise ValueError(f"gather_topk: k={k} exceeds the {tile}-lane "
                         f"running top-k row (use gather_dist + sort)")
    nt = -(-m // tile)
    pad = nt * tile - m
    ids_m = jnp.pad(ids.astype(jnp.int32), (0, pad), constant_values=-1)
    ids_c = jnp.clip(ids_m, 0, n - 1)
    x_spec = _row_spec(x, lambda i, t: i * tile + t)
    q_spec = pl.BlockSpec((1, d), lambda i, t, ids_ref: (0, 0))
    idm_spec = pl.BlockSpec((1, tile), lambda i, t, ids_ref: (0, i))
    if scale is None:
        kernel = _topk_kernel
        in_specs = [x_spec, q_spec, idm_spec]
        ops = (x, q[None, :], ids_m[None, :])
    else:
        kernel = _topk_kernel_scaled
        in_specs = [x_spec, q_spec, q_spec, idm_spec]
        ops = (x, scale.astype(jnp.float32)[None, :], q[None, :],
               ids_m[None, :])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt, tile),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, tile), lambda i, t, ids_ref: (0, 0)),
            pl.BlockSpec((1, tile), lambda i, t, ids_ref: (0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((1, tile), jnp.float32)],
    )
    od, oi = pl.pallas_call(
        functools.partial(kernel, tile=tile, k=k),
        grid_spec=grid_spec,
        name="gather_topk_pallas",     # stable op name in device profiles
        out_shape=(jax.ShapeDtypeStruct((1, tile), jnp.float32),
                   jax.ShapeDtypeStruct((1, tile), jnp.int32)),
        interpret=interpret,
    )(ids_c, *ops)
    return oi[0, :k], od[0, :k]


# ======================================================================
# Batched rerank: per-query gather + f32 top-k over survivor id lists
# ======================================================================
def _rerank_kernel(ids_ref, x_ref, q_ref, idm_ref, od_ref, oi_ref, acc_ref,
                   *, tile: int, k: int, mp: int):
    i = pl.program_id(0)          # query
    j = pl.program_id(1)          # id tile within this query's list
    t = pl.program_id(2)          # position within the tile

    @pl.when((j == 0) & (t == 0))
    def _init_topk():             # grid is row-major: (i, 0, 0) starts query i
        od_ref[...] = jnp.full_like(od_ref, jnp.inf)
        oi_ref[...] = jnp.full_like(oi_ref, -1)

    @pl.when(t == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    d2 = _row_d2(x_ref, q_ref, None, ids_ref[i * mp + j * tile + t])
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1) == t
    acc_ref[...] = jnp.where(lane, d2, acc_ref[...])

    @pl.when(t == tile - 1)
    def _merge():
        _fold_topk(acc_ref, idm_ref, od_ref, oi_ref, tile=tile, k=k)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def gather_rerank_pallas(x: jax.Array, ids: jax.Array, q: jax.Array, *,
                         k: int, interpret: bool = False):
    """Batched ``gather_topk``: the f32 rerank stage of the quantized path.

    x:(N,d) f32; ids:(Q,M) int32 survivor ranks per query (**negative =
    masked**, callers pre-sort ascending via ``sort_candidates`` so distance
    ties break toward the lower rank); q:(Q,d).  Returns (ids:(Q,k) i32
    ascending-distance (-1 pad), dists:(Q,k) f32 (+inf pad)).

    One grid, Q running top-k rows: grid = (Q, tiles, tile) with the same
    scalar-prefetched row steering as ``gather_topk`` — the per-(query, t)
    row DMA index comes from the flattened id table.  Requires ``k ≤
    min(next_pow2(M), 128)``."""
    n, d = x.shape
    Q, m = ids.shape
    tile = _tile(max(m, k))
    if k > tile:
        raise ValueError(f"gather_rerank: k={k} exceeds the {tile}-lane "
                         f"running top-k row (use gather_dist + sort)")
    nt = -(-m // tile)
    mp = nt * tile
    ids_m = jnp.pad(ids.astype(jnp.int32), ((0, 0), (0, mp - m)),
                    constant_values=-1)
    ids_c = jnp.clip(ids_m, 0, n - 1).reshape(Q * mp)
    # q, the id mask and the outputs are viewed 3-D, (Q, 1, ·), with the
    # query dim squeezed (a (1, ·) block of a (Q, ·) array is not tileable)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Q, nt, tile),
        in_specs=[
            _row_spec(x, lambda i, j, t: i * mp + j * tile + t),
            pl.BlockSpec((None, 1, d), lambda i, j, t, ids_ref: (i, 0, 0)),
            pl.BlockSpec((None, 1, tile),
                         lambda i, j, t, ids_ref: (i, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, tile),
                         lambda i, j, t, ids_ref: (i, 0, 0)),
            pl.BlockSpec((None, 1, tile),
                         lambda i, j, t, ids_ref: (i, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((1, tile), jnp.float32)],
    )
    od, oi = pl.pallas_call(
        functools.partial(_rerank_kernel, tile=tile, k=k, mp=mp),
        grid_spec=grid_spec,
        name="gather_rerank_pallas",   # stable op name in device profiles
        out_shape=(jax.ShapeDtypeStruct((Q, 1, tile), jnp.float32),
                   jax.ShapeDtypeStruct((Q, 1, tile), jnp.int32)),
        interpret=interpret,
    )(ids_c, x, q[:, None, :], ids_m[:, None, :])
    return oi[:, 0, :k], od[:, 0, :k]
