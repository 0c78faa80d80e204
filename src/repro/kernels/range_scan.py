"""Pallas TPU kernel: fused brute-force scan over a contiguous rank slice.

The planner's exact strategy for highly selective ranges: ids are attribute
ranks, so the candidate set of a range query is the contiguous slice
``x[L : R+1]`` and an exact masked L2 scan + top-k beats graph traversal when
the slice is small.

Each query carries its own ``(start, len)``; the per-query window start is
*scalar-prefetched* so the BlockSpec index_map steers each grid step's DMA to
the right row-block of X.  Window starts are aligned down to the row-tile
(``tb``) boundary and one extra row-block is appended, so a bucket of length B
is served by ``ceil(B/tb)+1`` fixed-shape blocks regardless of alignment;
positions outside ``[start, start+len)`` are masked to +inf by absolute rank.

Grid = (Q, row-blocks, d-chunks); the d-axis is the innermost "arbitrary"
dimension accumulating qn − 2·qᵀx + xn into a (1, tb) VMEM *scratch* block
(same scheme as ``l2dist``).  On the last d-step the block's masked distances
are folded into a per-query running top-k held in the (1, tb)-lane output
blocks (dists + rank ids), so the full (Q, W) distance matrix is **never
materialized** — the kernel's output is (Q, tb) regardless of window size.
The merge is a k-step select-min over the 2·tb-lane union of the running
top-k and the new block (vector argmin + one-hot updates only, so it lowers
on both the Mosaic and interpret backends); ties break toward lower rank,
matching ``jax.lax.top_k`` on the materialized matrix.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def window_rows(bucket: int, tb: int = 128) -> int:
    """Rows actually scanned for a bucket: ceil(bucket/tb) blocks plus one
    extra block so any start alignment is covered (single source of truth —
    the kernel, its jnp oracle, and the planner cost model all use this)."""
    return (-(-bucket // tb) + 1) * tb


def _body(starts_ref, lens_ref, x_ref, scale_ref, live_ref, q_ref, od_ref,
          oi_ref, acc_ref, *, nd: int, tb: int, k: int, n_valid: int):
    i = pl.program_id(0)          # query
    j = pl.program_id(1)          # row block within the window
    kd = pl.program_id(2)         # d-chunk

    @pl.when((j == 0) & (kd == 0))
    def _init_topk():
        od_ref[...] = jnp.full_like(od_ref, jnp.inf)
        oi_ref[...] = jnp.full_like(oi_ref, -1)

    @pl.when(kd == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)            # (tb, td)
    if scale_ref is not None:                     # int8: dequant in VMEM
        x = x * scale_ref[...]                    # (1, td) broadcast
    q = q_ref[...].astype(jnp.float32)            # (1, td)
    # full-f32 MXU passes: the scan promises exact ids, and a default
    # single-pass bf16 product would reorder near-ties on the chip
    dot = jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    acc_ref[...] += -2.0 * dot
    acc_ref[...] += jnp.sum(q * q, axis=1, keepdims=True)
    acc_ref[...] += jnp.sum(x * x, axis=1)[None, :]

    @pl.when(kd == nd - 1)
    def _merge():
        start = starts_ref[i]
        ln = lens_ref[i]
        base = (start // tb) * tb
        rank = base + j * tb + jax.lax.broadcasted_iota(jnp.int32, (1, tb), 1)
        valid = (rank >= start) & (rank < start + ln) & (rank < n_valid)
        if live_ref is not None:              # per-row tombstone mask
            valid &= live_ref[...] != 0       # (1, tb), same row block as x
        d_blk = jnp.where(valid, jnp.maximum(acc_ref[...], 0.0), jnp.inf)
        # union of the running top-k and this block; blocks arrive in
        # ascending-rank order and the running half comes first, so the
        # first-occurrence argmin breaks distance ties toward lower rank
        cd = jnp.concatenate([od_ref[...], d_blk], axis=1)      # (1, 2*tb)
        ci = jnp.concatenate([oi_ref[...], rank], axis=1)
        lane_u = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * tb), 1)
        lane_o = jax.lax.broadcasted_iota(jnp.int32, (1, tb), 1)
        new_d = jnp.full((1, tb), jnp.inf, jnp.float32)
        new_i = jnp.full((1, tb), -1, jnp.int32)
        for t in range(k):            # static unroll: k-step select-min
            m = jnp.min(cd)
            sel = lane_u == jnp.argmin(cd).astype(jnp.int32)
            idv = jnp.sum(jnp.where(sel, ci, 0)).astype(jnp.int32)
            idv = jnp.where(jnp.isfinite(m), idv, -1)
            new_d = jnp.where(lane_o == t, m, new_d)
            new_i = jnp.where(lane_o == t, idv, new_i)
            cd = jnp.where(sel, jnp.inf, cd)
        od_ref[...] = new_d
        oi_ref[...] = new_i


def _make_kernel(has_scale: bool, has_live: bool):
    """Kernel entry point for one (scale, live) operand combination; the
    optional refs arrive positionally between x and q in operand order."""
    def kernel(starts_ref, lens_ref, x_ref, *rest, **kw):
        rest = list(rest)
        scale_ref = rest.pop(0) if has_scale else None
        live_ref = rest.pop(0) if has_live else None
        q_ref, od_ref, oi_ref, acc_ref = rest
        _body(starts_ref, lens_ref, x_ref, scale_ref, live_ref, q_ref,
              od_ref, oi_ref, acc_ref, **kw)
    return kernel


_KERNELS = {(s, lv): _make_kernel(s, lv)
            for s in (False, True) for lv in (False, True)}


@functools.partial(jax.jit,
                   static_argnames=("bucket", "k", "tb", "td", "interpret",
                                    "n_valid"))
def range_scan_pallas(x: jax.Array, starts: jax.Array, lens: jax.Array,
                      q: jax.Array, *, bucket: int, k: int, tb: int = 128,
                      td: int = 512, interpret: bool = False,
                      n_valid: int = 0, scale: jax.Array | None = None,
                      live: jax.Array | None = None):
    """x:(n_pad,d_pad) rank-ordered, n_pad % tb == 0, d_pad % 128 == 0;
    starts/lens:(Q,) i32 per-query rank windows (len ≤ bucket); q:(Q,d_pad).
    Returns (ids:(Q,k) i32 absolute ranks (-1 pad), dists:(Q,k) f32).

    ``x`` may be a quantized corpus copy (int8/bf16): the block is upcast to
    f32 in VMEM right after the narrow DMA, and an optional ``scale``
    ((d_pad,) f32 per-dimension dequant factors, int8 mode) multiplies it
    before scoring — the accumulation/top-k machinery is dtype-agnostic.

    ``n_valid`` (0 = n_pad): ranks ≥ n_valid never enter the top-k, even when
    a window nominally covers them.  Shard-local dispatch (the mesh substrate
    traces this kernel per shard with windows clipped to the shard's rank
    slice) passes the shard's true row count so the zero rows padding the
    corpus to a row-tile multiple can never win.

    ``live`` ((1, n_pad) i32, optional) is the per-row generalization of
    ``n_valid``: rows whose lane is 0 never enter the top-k.  The streaming
    layer threads tombstone masks through it (base segment: deleted ranks;
    delta segment: the pad tail beyond the current row count) — being an
    operand rather than a static arg, mask churn never retraces."""
    n_pad, d_pad = x.shape
    Q = q.shape[0]
    n_valid = int(n_valid) or n_pad
    if k > tb:
        raise ValueError(f"range_scan: k={k} exceeds the {tb}-lane running "
                         f"top-k row (the planner routes such queries to "
                         f"beam)")
    td = d_pad if d_pad <= td else 128
    nd = d_pad // td
    w = window_rows(bucket, tb)
    nb = w // tb
    max_blk = n_pad // tb - 1
    starts = starts.astype(jnp.int32)
    lens = lens.astype(jnp.int32)

    x_spec = pl.BlockSpec((tb, td),
                          lambda i, j, kd, s_ref, l_ref:
                          (jnp.minimum(s_ref[i] // tb + j, max_blk), kd))
    # q and the outputs are viewed 3-D, (Q, 1, ·), with the query dim
    # squeezed: a (1, ·) block of a (Q, ·) array breaks the TPU rule that
    # a block's last two dims divide (8, 128) or equal the array's
    q_spec = pl.BlockSpec((None, 1, td),
                          lambda i, j, kd, s_ref, l_ref: (i, 0, kd))
    kernel = _KERNELS[(scale is not None, live is not None)]
    in_specs, ops = [x_spec], [x]
    if scale is not None:
        in_specs.append(pl.BlockSpec((1, td),
                                     lambda i, j, kd, s_ref, l_ref: (0, kd)))
        ops.append(scale.astype(jnp.float32)[None, :])
    if live is not None:
        # same row block as x: lanes line up with the ranks scored there
        in_specs.append(pl.BlockSpec(
            (1, tb), lambda i, j, kd, s_ref, l_ref:
            (0, jnp.minimum(s_ref[i] // tb + j, max_blk))))
        ops.append(live.astype(jnp.int32))
    in_specs.append(q_spec)
    ops.append(q[:, None, :])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Q, nb, nd),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, 1, tb),
                         lambda i, j, kd, s_ref, l_ref: (i, 0, 0)),
            pl.BlockSpec((None, 1, tb),
                         lambda i, j, kd, s_ref, l_ref: (i, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((1, tb), jnp.float32)],
    )
    dists, ids = pl.pallas_call(
        functools.partial(kernel, nd=nd, tb=tb, k=k, n_valid=n_valid),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((Q, 1, tb), jnp.float32),
                   jax.ShapeDtypeStruct((Q, 1, tb), jnp.int32)),
        interpret=interpret,
    )(starts, lens, *ops)

    return ids[:, 0, :k], dists[:, 0, :k]
