"""Substrate tests: checkpointing, fault tolerance, data pipeline, sharding
resolver, serving engine, distributed RFANN."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpoint import CheckpointManager
from repro.data.ann import (ground_truth, make_attrs, make_vectors,
                            mixed_workload, recall_at_k, selectivity_ranges)
from repro.data.tokens import Prefetcher, SyntheticTokenStream, TokenStreamConfig
from repro.parallel.sharding import (DEFAULT_RULES, FSDP_RULES,
                                     spec_for_logical)
from repro.runtime.fault_tolerance import (Heartbeat, StragglerMonitor,
                                           int8_compress_decompress)
from repro.serving.distributed import DistributedRFANN
from repro.serving.engine import RFANNEngine
from repro.core.rfann import RNSGIndex


# ---------------------------------------------------------------- checkpoint
def _state(seed=0):
    rng = np.random.default_rng(seed)
    return {"params": {"w": jnp.asarray(rng.standard_normal((8, 4)), jnp.float32),
                       "b": jnp.asarray(rng.standard_normal(4), jnp.bfloat16)},
            "opt": {"step": jnp.asarray(7, jnp.int32)}}


def test_checkpoint_roundtrip_and_gc(tmp_path):
    ckpt = CheckpointManager(str(tmp_path), keep=2)
    st = _state()
    for step in (10, 20, 30, 40):
        ckpt.save(step, st, blocking=True, extra={"note": "x"})
    assert ckpt.all_steps() == [30, 40]          # gc kept last 2
    back = ckpt.restore(jax.tree.map(lambda a: jnp.zeros_like(a), st))
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(back)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert ckpt.meta()["step"] == 40


def test_checkpoint_async_and_atomic(tmp_path):
    ckpt = CheckpointManager(str(tmp_path))
    st = _state(1)
    ckpt.save(5, st, blocking=False)
    ckpt.wait()
    assert ckpt.latest_step() == 5
    # a stale tmp file never shadows a real checkpoint
    (tmp_path / "tmp.99.npz").write_bytes(b"garbage")
    assert ckpt.latest_step() == 5


def test_elastic_resume_resharding(tmp_path):
    """Checkpoint saved (conceptually) on mesh A restores onto 'mesh' B —
    arrays are stored unsharded, so only the device_put differs."""
    ckpt = CheckpointManager(str(tmp_path))
    st = _state(2)
    ckpt.save(1, st, blocking=True)
    mesh = jax.make_mesh((1,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = jax.tree.map(lambda a: NamedSharding(mesh, P()), st)
    back = ckpt.restore(st, shardings=sh)
    assert back["params"]["w"].sharding == NamedSharding(mesh, P())


def test_train_resume_equivalence(tmp_path):
    from repro.launch.train import main as train_main
    base = ["--arch", "mamba2-780m", "--batch", "2", "--seq", "32",
            "--log-every", "1000"]
    _, full = train_main(base + ["--steps", "8"])
    d = str(tmp_path / "ck")
    train_main(base + ["--steps", "4", "--ckpt-dir", d, "--ckpt-every", "100"])
    _, resumed = train_main(base + ["--steps", "8", "--ckpt-dir", d, "--resume"])
    # restart-from-checkpoint must replay the exact loss trajectory
    assert np.allclose(full[4:], resumed, rtol=1e-4), (full, resumed)


# ---------------------------------------------------------------- fault tolerance
def test_straggler_monitor_flags_and_evicts():
    mon = StragglerMonitor(n_hosts=4, evict_after=3)
    out = {}
    for _ in range(6):
        t = np.asarray([1.0, 1.0, 1.0, 3.5])
        out = mon.record(t)
    assert out["stragglers"] == [3]
    assert out["evict"] == [3]


def test_straggler_monitor_recovers():
    mon = StragglerMonitor(n_hosts=4, evict_after=10)
    for _ in range(2):
        mon.record(np.asarray([1.0, 1.0, 1.0, 3.5]))
    for _ in range(8):          # EMA decays back under the threshold
        out = mon.record(np.asarray([1.0, 1.0, 1.0, 1.0]))
    assert mon.flags[3] == 0 and out["evict"] == []


def test_heartbeat_detects_dead_host():
    hb = Heartbeat(3, timeout=1.0)
    now = time.monotonic()
    hb.beat(0, now)
    hb.beat(1, now)
    hb.beat(2, now - 5.0)
    assert hb.dead_hosts(now) == [2]


def test_int8_compression_error_bound():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal((256, 64)), jnp.float32)
    gq = int8_compress_decompress(g)
    scale = float(jnp.max(jnp.abs(g))) / 127.0
    assert float(jnp.max(jnp.abs(gq - g))) <= scale * 0.5 + 1e-6


# ---------------------------------------------------------------- data pipeline
def test_token_stream_determinism_and_host_sharding():
    c = dict(vocab_size=97, seq_len=16, global_batch=8)
    s0 = SyntheticTokenStream(TokenStreamConfig(**c, n_hosts=2, host_id=0))
    s0b = SyntheticTokenStream(TokenStreamConfig(**c, n_hosts=2, host_id=0))
    s1 = SyntheticTokenStream(TokenStreamConfig(**c, n_hosts=2, host_id=1))
    a, b = s0.batch_at(5), s0b.batch_at(5)
    assert np.array_equal(a["tokens"], b["tokens"])           # replayable
    assert not np.array_equal(a["tokens"], s1.batch_at(5)["tokens"])
    assert a["tokens"].shape == (4, 16)                        # host shard
    assert np.array_equal(a["labels"][:, :-1], a["tokens"][:, 1:])


def test_prefetcher_preserves_order():
    it = iter([{"i": np.asarray(i)} for i in range(10)])
    got = [int(b["i"]) for b in Prefetcher(it, depth=3)]
    assert got == list(range(10))


# ---------------------------------------------------------------- resolver
def test_resolver_divisibility_and_conflicts():
    from jax.sharding import AbstractMesh
    mesh = AbstractMesh((4, 2), ("data", "model"))
    # divisible both dims
    assert spec_for_logical(("fsdp", "tp"), (8, 6), mesh) == \
        jax.sharding.PartitionSpec("data", "model")
    # dim not divisible -> dropped
    assert spec_for_logical(("fsdp", "tp"), (7, 6), mesh)[0] is None
    # same mesh axis never used twice in one tensor
    spec = spec_for_logical(("expert", "fsdp", "tp"), (2, 8, 6), mesh)
    flat = [a for part in spec if part for a in
            (part if isinstance(part, tuple) else (part,))]
    assert len(flat) == len(set(flat))
    # batch over (pod, data) prefix logic
    mesh3 = AbstractMesh((2, 2, 2), ("pod", "data", "model"))
    assert spec_for_logical(("batch",), (4,), mesh3) == \
        jax.sharding.PartitionSpec(("pod", "data"))
    # FSDP strategy: batch spreads over (data, model) when pod doesn't divide
    assert spec_for_logical(("batch",), (4,), mesh3, FSDP_RULES) == \
        jax.sharding.PartitionSpec(("data", "model"))


# ---------------------------------------------------------------- distributed RFANN
def test_distributed_rfann_matches_ground_truth():
    n, d, nq, k = 2048, 16, 40, 10
    vecs = make_vectors(n, d, seed=0)
    attrs = make_attrs(n, seed=0)
    dist = DistributedRFANN(vecs, attrs, n_shards=4, m=16, ef_spatial=16,
                            ef_attribute=24)
    qv = make_vectors(nq, d, seed=9)
    ranges, _ = mixed_workload(attrs, nq, seed=2, levels=4)
    ids, dd = dist.search(qv, ranges, k=k, ef=96)
    order = np.argsort(attrs, kind="stable")
    gt_r, _ = ground_truth(vecs[order], attrs[order], qv, ranges, k)
    gt = np.where(gt_r >= 0, order[np.maximum(gt_r, 0)], -1)
    assert recall_at_k(ids, gt) > 0.95


def test_distributed_single_shard_range_equals_shard_search():
    """A range inside one shard: heredity ⇒ the merge equals that shard alone."""
    n, d = 1024, 8
    vecs = make_vectors(n, d, seed=1)
    attrs = np.sort(make_attrs(n, seed=1))
    dist = DistributedRFANN(vecs, attrs, n_shards=4, m=16, ef_spatial=16,
                            ef_attribute=24)
    lo, hi = dist.shard_span[1]          # entirely inside shard 1
    qv = make_vectors(6, d, seed=3)
    rg = np.tile(np.asarray([[lo, hi]], np.float32), (6, 1))
    ids, dd = dist.search(qv, rg, k=5, ef=64)
    assert (ids >= 0).all()
    for q in range(6):
        for i in ids[q]:
            assert lo <= attrs[i] <= hi


# ---------------------------------------------------------------- engine
def test_engine_dynamic_batching():
    n, d = 1024, 16
    vecs = make_vectors(n, d, seed=0)
    attrs = make_attrs(n, seed=0)
    idx = RNSGIndex.build(vecs, attrs, m=16, ef_spatial=16, ef_attribute=24)
    eng = RFANNEngine(idx, k=5, ef=32, max_batch=16, max_wait_ms=5)
    qv = make_vectors(32, d, seed=2)
    rgs = selectivity_ranges(attrs, 32, 0.5, seed=0)
    futs = [eng.submit(qv[i], rgs[i]) for i in range(32)]
    res = [f.result(timeout=60) for f in futs]
    eng.close()
    assert len(res) == 32 and all(r[0].shape == (5,) for r in res)
    assert eng.stats.summary()["mean_batch"] > 1.0
