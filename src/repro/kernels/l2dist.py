"""Pallas TPU kernel: tiled batched squared-L2 distance.

Computes D[i,j] = ‖q_i − x_j‖² as qn_i − 2·q_iᵀx_j + xn_j so the dominant term
is an MXU matmul.  3-D grid (Q-tiles × N-tiles × d-chunks): the d-axis is the
innermost "arbitrary" dimension accumulating partial dot products into the
output tile living in VMEM; norms are folded in on the last d-step.

VMEM budget per step: q tile (TQ×TD) + x tile (TN×TD) + out tile (TQ×TN),
all f32 → with TQ=TN=128, TD=512 this is 128·512·4·2 + 128·128·4 ≈ 590 KB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(q_ref, x_ref, o_ref, *, nd: int):
    kd = pl.program_id(2)

    @pl.when(kd == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    q = q_ref[...].astype(jnp.float32)            # (TQ, TD)
    x = x_ref[...].astype(jnp.float32)            # (TN, TD)
    partial_dot = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    o_ref[...] += -2.0 * partial_dot
    o_ref[...] += jnp.sum(q * q, axis=1, keepdims=True)
    o_ref[...] += jnp.sum(x * x, axis=1)[None, :]

    @pl.when(kd == nd - 1)
    def _fin():
        o_ref[...] = jnp.maximum(o_ref[...], 0.0)


@functools.partial(jax.jit, static_argnames=("tq", "tn", "td", "interpret"))
def l2dist_pallas(q: jax.Array, x: jax.Array, *, tq: int = 128, tn: int = 128,
                  td: int = 512, interpret: bool = False) -> jax.Array:
    """q:(Q,d), x:(N,d) -> (Q,N) f32. Q,N,d padded to tile multiples."""
    Q, d = q.shape
    N = x.shape[0]
    tq, tn, td = min(tq, max(Q, 8)), min(tn, max(N, 128)), min(td, max(d, 128))
    pq, pn, pd = (-Q) % tq, (-N) % tn, (-d) % td
    qp = jnp.pad(q, ((0, pq), (0, pd)))
    xp = jnp.pad(x, ((0, pn), (0, pd)))
    nd = (d + pd) // td
    grid = ((Q + pq) // tq, (N + pn) // tn, nd)
    out = pl.pallas_call(
        functools.partial(_kernel, nd=nd),
        grid=grid,
        name="l2dist_pallas",          # stable op name in device profiles
        in_specs=[
            pl.BlockSpec((tq, td), lambda i, j, k: (i, k)),
            pl.BlockSpec((tn, td), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((tq, tn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Q + pq, N + pn), jnp.float32),
        interpret=interpret,
    )(qp, xp)
    return out[:Q, :N]
