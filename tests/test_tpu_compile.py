"""Compile-only checks of the served Pallas kernels for a TPU v5e chip.

Interpret mode never checks tile alignment or VMEM limits, so every kernel
of the served path is compiled here by the TPU compiler for a *described*
v5e chip (nothing runs, no chip is needed) at real widths, and each
compiled program must contain the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and a test run with
several workers imports every test file in every worker.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gather_dist import (gather_dist_pallas,
                                       gather_rerank_pallas,
                                       gather_topk_pallas)
from repro.kernels.range_scan import range_scan_pallas


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args, **kwargs) -> str:
    return jax.jit(fn).lower(*args, **kwargs).compile().as_text()


# (n_pad, d_pad, dtype, with scale, with live, bucket, Q) at real widths:
# the DEEP shape (d=96 padded to 128) at 2^20 rows, its int8 copy with the
# dequant scale and a tombstone mask, and a d=1024 corpus (8 d-chunks)
SCAN_CASES = {
    "f32_d96": (1 << 20, 128, jnp.float32, False, False, 1 << 17, 64),
    "int8_scale_live": (1 << 20, 128, jnp.int8, True, True, 1 << 13, 64),
    "f32_d1024": (1 << 16, 1024, jnp.float32, False, False, 1 << 12, 16),
}


@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_range_scan_compiles_for_v5e(one_chip, case):
    n_pad, d_pad, dt, with_scale, with_live, bucket, nq = SCAN_CASES[case]
    s = one_chip
    args = [_sds(s, (n_pad, d_pad), dt), _sds(s, (nq,), jnp.int32),
            _sds(s, (nq,), jnp.int32), _sds(s, (nq, d_pad), jnp.float32)]
    scale = _sds(s, (d_pad,), jnp.float32) if with_scale else None
    live = _sds(s, (1, n_pad), jnp.int32) if with_live else None

    def fn(x, starts, lens, q, scale=None, live=None):
        return range_scan_pallas(x, starts, lens, q, bucket=bucket, k=10,
                                 scale=scale, live=live)
    extra = {k: v for k, v in (("scale", scale), ("live", live))
             if v is not None}
    assert "tpu_custom_call" in _compiled_text(fn, *args, **extra)


@pytest.mark.parametrize("dt", [jnp.float32, jnp.int8])
def test_gather_dist_compiles_for_v5e(one_chip, dt):
    s = one_chip
    n, d, m = 1 << 20, 96, 64
    scaled = dt == jnp.int8

    def fn(x, ids, q, scale=None):
        return gather_dist_pallas(x, ids, q, scale=scale)
    args = [_sds(s, (n, d), dt), _sds(s, (m,), jnp.int32),
            _sds(s, (d,), jnp.float32)]
    if scaled:
        args.append(_sds(s, (d,), jnp.float32))
    assert "tpu_custom_call" in _compiled_text(fn, *args)


@pytest.mark.parametrize("dt", [jnp.float32, jnp.int8])
def test_gather_topk_compiles_for_v5e(one_chip, dt):
    s = one_chip
    n, d, m = 1 << 20, 96, 32 * 4      # beam_width 4 × degree 32
    scaled = dt == jnp.int8

    def fn(x, ids, q, scale=None):
        return gather_topk_pallas(x, ids, q, k=64, scale=scale)
    args = [_sds(s, (n, d), dt), _sds(s, (m,), jnp.int32),
            _sds(s, (d,), jnp.float32)]
    if scaled:
        args.append(_sds(s, (d,), jnp.float32))
    assert "tpu_custom_call" in _compiled_text(fn, *args)


def test_gather_rerank_compiles_for_v5e(one_chip):
    s = one_chip
    n, d, nq, m = 1 << 20, 96, 64, 128     # rerank_depth(10, 64) survivors

    def fn(x, ids, q):
        return gather_rerank_pallas(x, ids, q, k=10)
    assert "tpu_custom_call" in _compiled_text(
        fn, _sds(s, (n, d), jnp.float32), _sds(s, (nq, m), jnp.int32),
        _sds(s, (nq, d), jnp.float32))
