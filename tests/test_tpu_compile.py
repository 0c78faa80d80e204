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


# each kernel's explicit ``name=`` — the op name device profiles print — and
# a small set of its operands (x, then the kernel's other inputs)
def _kernel_cases():
    from repro.kernels.l2dist import l2dist_pallas
    f32, i32 = jnp.float32, jnp.int32
    return {
        "range_scan_pallas": (
            lambda x, st, ln, q: range_scan_pallas(x, st, ln, q, bucket=1024,
                                                   k=10),
            [((1 << 14, 128), f32), ((8,), i32), ((8,), i32),
             ((8, 128), f32)]),
        "gather_dist_pallas": (
            gather_dist_pallas,
            [((4096, 128), f32), ((256,), i32), ((128,), f32)]),
        "gather_topk_pallas": (
            lambda x, ids, q: gather_topk_pallas(x, ids, q, k=10),
            [((4096, 128), f32), ((256,), i32), ((128,), f32)]),
        "gather_rerank_pallas": (
            lambda x, ids, q: gather_rerank_pallas(x, ids, q, k=10),
            [((4096, 128), f32), ((8, 64), i32), ((8, 128), f32)]),
        "l2dist_pallas": (
            l2dist_pallas, [((128, 128), f32), ((1024, 128), f32)]),
    }


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_kernel_op_name_is_explicit(one_chip, name):
    """Every Pallas kernel carries an explicit name, which the compiled
    program gives its Mosaic op — the name a device profile prints, stable
    under whatever jit encloses the kernel."""
    import re
    fn, shapes = _kernel_cases()[name]
    text = _compiled_text(lambda *a: fn(*a),
                          *[_sds(one_chip, sh, dt) for sh, dt in shapes])
    ops = re.findall(r"%([\w.\-]+) = [^\n]*custom-call\([^\n]*tpu_custom_call",
                     text)
    assert ops and all(op.rsplit(".", 1)[0] == name for op in ops), ops


@pytest.mark.parametrize("bw", [1, 4])
def test_beam_program_has_no_corpus_sized_visited_state(one_chip, bw):
    """The served beam (2^20 rows, d=96, degree 32, a 32-lane partition,
    ef=64, k=10) compiles for v5e with no buffer of extent n + 1 or
    32 · (n + 1): its visited set is a table sized by (ef, m), not a
    per-lane bitmap over the corpus."""
    import re
    from repro.core.beam import beam_search_batch
    s = one_chip
    n, d, m, q = 1 << 20, 96, 32, 32

    def fn(vecs, nbrs, qv, lo, hi, entry):
        return beam_search_batch(vecs, nbrs, qv, lo, hi, entry, k=10, ef=64,
                                 beam_width=bw)
    text = _compiled_text(
        fn, _sds(s, (n, d), jnp.float32), _sds(s, (n, m), jnp.int32),
        _sds(s, (q, d), jnp.float32), _sds(s, (q,), jnp.int32),
        _sds(s, (q,), jnp.int32), _sds(s, (q,), jnp.int32))
    for extent in (n + 1, q * (n + 1)):
        assert not re.search(rf"\b{extent}\b", text), extent


@pytest.mark.parametrize("kind", ["scan", "beam", "merge"])
def test_mesh_programs_compile_for_v5e_2x2(topo, kind, monkeypatch):
    """The range-sharded deployment's mesh programs compile for a v5e 2x2
    host at its real size (4 shards of 2^20 rows, d=96, degree 32, k=10,
    ef=64; a 64-row batch, groups padded to 64, the widest scan bucket
    2^17): the scan group runs the Mosaic kernel on each chip, and the merge
    gathers across the mesh."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import repro.kernels.ops as kernel_ops
    from repro.search.substrate import mesh_program
    # this process's backend is the CPU, where kernels run interpreted:
    # trace them for Mosaic, as on the described chip
    monkeypatch.setattr(kernel_ops, "_interpret", lambda: False)
    mesh = Mesh(np.asarray(topo.devices[:4]), ("data",))
    sh, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    s, per, d, m, k, rows, pad = 4, 1 << 20, 96, 32, 10, 64, 64
    f32, i32 = jnp.float32, jnp.int32
    qall = _sds(rep, (rows + 1, 128), f32)
    ops = _sds(sh, (s, 3, pad), i32)
    buf = [_sds(sh, (s, rows + 1, k), i32), _sds(sh, (s, rows + 1, k), f32)]
    vecs, order = _sds(sh, (s, per, d), f32), _sds(sh, (s, per), i32)
    scale, live = _sds(rep, (128,), f32), _sds(sh, (s, per), jnp.bool_)
    if kind == "scan":
        fn = mesh_program(mesh, "data", "scan", k=k, ef=64, bucket=1 << 17,
                          precision="f32", use_live=False)
        args = [_sds(sh, (s, per, 128), f32), vecs, order, scale, live,
                qall, ops, *buf]
    elif kind == "beam":
        fn = mesh_program(mesh, "data", "beam", k=k, ef=64, beam_width=1,
                          precision="f32", use_live=False)
        args = [vecs, _sds(sh, (s, per, m), i32), _sds(sh, (s, 21, per), i32),
                _sds(sh, (s, per), f32), order, vecs, scale, live, qall, ops,
                *buf]
    else:
        fn = mesh_program(mesh, "data", "merge", k=k)
        args = buf
    text = fn.lower(*args).compile().as_text()
    if kind == "scan":
        assert "tpu_custom_call" in text
    if kind == "merge":
        assert "all-gather" in text
