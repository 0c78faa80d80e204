"""GPipe-style pipeline parallelism via ``shard_map`` + ``ppermute``.

Stage s of S holds its own slice of the layer stack (params stacked on a
leading stage dim, sharded over the pipeline mesh axis). Microbatches stream
through the classic GPipe schedule: T = M + S - 1 ticks; each tick every
stage computes its current microbatch and ``ppermute``s the activation to its
successor. Fixed shapes throughout; reverse-mode AD works (the transpose of a
ppermute is the reverse permute), so the same schedule backpropagates.

This is the optional PP axis for depth-dominant models; the frameworks'
default strategies (FSDP for train, TP/replica for serve) cover the assigned
mesh, and PP composes with them by dedicating the `pod` axis to stages.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def gpipe(stage_fn: Callable, mesh, axis: str, n_stages: int, n_micro: int):
    """Returns pipelined(params_stacked, x_micro) -> y_micro.

    stage_fn(stage_params, x) -> y        (same shape in/out)
    params_stacked: leaves with leading dim n_stages (sharded over `axis`)
    x_micro: (n_micro, ...) microbatches (replicated; only stage 0 consumes)
    """
    assert mesh.shape[axis] == n_stages

    def body(params, xs):
        params = jax.tree.map(lambda a: a[0], params)      # this stage's slice
        stage = jax.lax.axis_index(axis)
        ticks = n_micro + n_stages - 1
        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        buf = jnp.zeros_like(xs[0])                        # inbound activation
        outs = jnp.zeros_like(xs)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 injects microbatch t; others consume the inbound buffer
            mb = jnp.clip(t, 0, n_micro - 1)
            x_in = jnp.where(stage == 0, xs[mb], buf)
            active = (t >= stage) & (t - stage < n_micro)
            y = stage_fn(params, x_in)
            y = jnp.where(active, y, buf)
            # last stage records its finished microbatch (t - (S-1))
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            record = active & (stage == n_stages - 1)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(record, y, outs[out_idx]), out_idx, 0)
            buf = jax.lax.ppermute(y, axis, fwd)
            return buf, outs

        _, outs = jax.lax.fori_loop(0, ticks, tick, (buf, outs))
        # every stage holds zeros except the last; sum-gather the real outputs
        outs = jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    def pipelined(params_stacked, x_micro):
        in_specs = (jax.tree.map(lambda _: P(axis), params_stacked), P())
        return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=P(),
                             check_vma=False)(params_stacked, x_micro)

    return pipelined
