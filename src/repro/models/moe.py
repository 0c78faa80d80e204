"""Top-k routed MoE with sort-based (permutation) dispatch.

Two distributed modes, both expressed inside ``shard_map`` so the collective
pattern is explicit:

* **EP** (``E % tp == 0``): experts sharded over the ``model`` axis; tokens are
  dispatched locally into an ``(E, C, D)`` buffer, exchanged with a tiled
  ``all_to_all`` over ``model``, processed by the local expert slice, and
  returned with the reverse ``all_to_all``.
* **fallback** (``E`` not divisible, e.g. mixtral's 8 experts on TP=16):
  expert weights are replicated inside the block (the FSDP all-gather is
  inserted by GSPMD at the shard_map boundary) and every device processes its
  own tokens' experts locally.

The single-device path (``mesh=None``) runs the same local math — used by the
smoke tests and the pure-jnp MoE oracle tests.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.layers import norm
from repro.models.params import ModelDims


def _route(xt: jax.Array, router: jax.Array, k: int):
    logits = (xt @ router).astype(jnp.float32)               # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, k)                    # (T,k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    # load-balance aux (Switch-style) + router z-loss
    e = router.shape[-1]
    me = jnp.mean(probs, axis=0)                             # (E,)
    ce = jnp.mean(jax.nn.one_hot(eidx[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * ce) + 1e-3 * jnp.mean(jnp.square(jax.nn.logsumexp(logits, -1)))
    return gates, eidx, aux


def _capacity(t: int, k: int, e: int, cf: float) -> int:
    return max(1, int(math.ceil(t * k * cf / e)))


def _sort_dispatch(xt: jax.Array, eidx: jax.Array, e: int, c: int):
    t, k = eidx.shape
    d = xt.shape[-1]
    flat_e = eidx.reshape(-1)                                 # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    e_s = flat_e[order]
    tok_s = order // k
    counts = jnp.bincount(flat_e, length=e)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(t * k) - starts[e_s]
    keep = pos < c
    slot = jnp.where(keep, e_s * c + pos, e * c)              # overflow -> dump row
    buf = jnp.zeros((e * c + 1, d), xt.dtype).at[slot].set(xt[tok_s])
    return buf[:e * c].reshape(e, c, d), (tok_s, slot, keep, order)


def _combine(out_buf: jax.Array, meta, gates: jax.Array, t: int):
    tok_s, slot, keep, order = meta
    e_c, d = out_buf.shape[0] * out_buf.shape[1], out_buf.shape[-1]
    padded = jnp.concatenate([out_buf.reshape(e_c, d),
                              jnp.zeros((1, d), out_buf.dtype)], axis=0)
    y_s = padded[slot] * gates.reshape(-1)[order][:, None].astype(out_buf.dtype)
    return jnp.zeros((t, d), out_buf.dtype).at[tok_s].add(y_s)


def _expert_ffn(buf: jax.Array, w_in, w_gate, w_out) -> jax.Array:
    h = jnp.einsum("ecd,edf->ecf", buf, w_in)
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    h = jax.nn.silu(g) * h
    return jnp.einsum("ecf,efd->ecd", h, w_out)


def _moe_local(xt, router, w_in, w_gate, w_out, k: int, cf: float,
               axis_names: Tuple[str, ...] = (), tp_axis: Optional[str] = None,
               e_total: int = 0):
    """Per-device MoE body. If tp_axis is set, experts are EP-sharded over it."""
    t = xt.shape[0]
    e_local = w_in.shape[0]
    e = e_total or e_local
    gates, eidx, aux = _route(xt, router, k)
    c = _capacity(t, k, e, cf)
    buf, meta = _sort_dispatch(xt, eidx, e, c)
    if tp_axis is not None:
        buf = jax.lax.all_to_all(buf, tp_axis, split_axis=0, concat_axis=1,
                                 tiled=True)                  # (E_loc, tp*C, D)
        out = _expert_ffn(buf, w_in, w_gate, w_out)
        out = jax.lax.all_to_all(out, tp_axis, split_axis=1, concat_axis=0,
                                 tiled=True)                  # (E, C, D)
    else:
        out = _expert_ffn(buf, w_in, w_gate, w_out)
    y = _combine(out, meta, gates, t)
    if axis_names:
        aux = jax.lax.pmean(aux, axis_names)
    return y, aux


def moe_ffn(x: jax.Array, p: Dict, cfg: ArchConfig, dm: ModelDims,
            mesh=None) -> Tuple[jax.Array, jax.Array]:
    """x: (B,S,D) -> (y, aux_loss). Pre-norm applied here."""
    h = norm(x, p, cfg.norm)
    b, s, d = h.shape
    t = b * s
    xt = h.reshape(t, d)

    if mesh is None or math.prod(mesh.shape.values()) == 1:
        y, aux = _moe_local(xt, p["router"], p["w_in"], p["w_gate"], p["w_out"],
                            cfg.moe_top_k, cfg.capacity_factor)
        return y.reshape(b, s, d), aux

    names = tuple(mesh.shape.keys())                          # e.g. (pod,data,model)
    tp = mesh.shape.get("model", 1)
    ep = tp > 1 and dm.e % tp == 0
    # shard tokens as widely as divisibility allows
    tok_axes = ()
    for cand in (names, names[:-1], names[:1], ()):
        if math.prod(mesh.shape[a] for a in cand) and \
           t % max(1, math.prod(mesh.shape[a] for a in cand)) == 0:
            tok_axes = cand
            break
    tok_spec = P(tok_axes if tok_axes else None, None)
    w_spec = P("model", None, None) if ep else P(None, None, None)

    body = partial(_moe_local, k=cfg.moe_top_k, cf=cfg.capacity_factor,
                   axis_names=names, tp_axis="model" if ep else None,
                   e_total=dm.e)
    y, aux = jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(tok_spec, P(None, None), w_spec, w_spec,
                  P("model", None, None) if ep else P(None, None, None)),
        out_specs=(tok_spec, P()),
    )(xt, p["router"], p["w_in"], p["w_gate"], p["w_out"])
    return y.reshape(b, s, d), aux
