"""Multi-device tests (subprocess: these need XLA_FLAGS set before jax import,
which must not leak into the rest of the suite)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


@pytest.mark.slow
def test_sharded_train_step_moe_ep():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.registry import get_smoke_config
        from repro.models.lm import Model
        from repro.models.params import ShardPlan, logical_axes
        from repro.parallel.sharding import (make_act_sharder,
                                             tree_shardings,
                                             batch_logical, spec_for_logical)
        from repro.launch.specs import concrete_batch
        from repro.training.train_step import build_train_step, init_train_state

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        cfg = get_smoke_config("llama4-maverick-400b-a17b")
        plan = ShardPlan(tp=2, fsdp=4)
        model = Model(cfg, plan, mesh=mesh, act_shard=make_act_sharder(mesh))
        state = init_train_state(model, jax.random.key(0))
        lax_tree = logical_axes(cfg, plan)
        psh = tree_shardings(lax_tree, model.param_shapes(), mesh)
        state = {"params": jax.device_put(state["params"], psh),
                 "opt": {"m": jax.device_put(state["opt"]["m"], psh),
                         "v": jax.device_put(state["opt"]["v"], psh),
                         "step": state["opt"]["step"]}}
        rng = np.random.default_rng(0)
        batch = concrete_batch(cfg, "train", 8, 16, rng)
        blog = batch_logical(cfg, "train")
        bsh = {k: NamedSharding(mesh, spec_for_logical(blog[k], v.shape, mesh))
               for k, v in batch.items()}
        batch = jax.device_put(batch, bsh)
        with jax.set_mesh(mesh):
            state2, m = jax.jit(build_train_step(model))(state, batch)
        assert np.isfinite(float(m["loss"])), m
        # MoE EP path must actually emit an all-to-all
        with jax.set_mesh(mesh):
            txt = jax.jit(build_train_step(model)).lower(state, batch).compile().as_text()
        assert "all-to-all" in txt, "expected EP all-to-all in HLO"
        print("OK", float(m["loss"]))
    """)
    assert "OK" in out


@pytest.mark.slow
def test_moe_ep_sharded_matches_local():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp, dataclasses
        from repro.configs.registry import get_smoke_config
        from repro.models.lm import Model
        from repro.models.params import ShardPlan, resolve_dims
        from repro.models.moe import moe_ffn
        cfg = dataclasses.replace(get_smoke_config("mixtral-8x7b"), dtype="float32")
        mesh = jax.make_mesh((2, 2), ("data", "model"))
        dm = resolve_dims(cfg, ShardPlan(tp=2, fsdp=2))
        rng = np.random.default_rng(0)
        b, s, d = 4, 8, cfg.d_model
        x = jnp.asarray(rng.standard_normal((b, s, d)), jnp.float32)
        e, f = cfg.n_experts, cfg.d_ff
        p = {"router": jnp.asarray(rng.standard_normal((d, e)), jnp.float32),
             "w_in": jnp.asarray(rng.standard_normal((e, d, f)) * .1, jnp.float32),
             "w_gate": jnp.asarray(rng.standard_normal((e, d, f)) * .1, jnp.float32),
             "w_out": jnp.asarray(rng.standard_normal((e, f, d)) * .1, jnp.float32),
             "norm": jnp.ones((d,), jnp.float32)}
        y_local, _ = moe_ffn(x, p, cfg, dm, mesh=None)
        with jax.set_mesh(mesh):
            y_shard, _ = jax.jit(lambda x, p: moe_ffn(x, p, cfg, dm, mesh=mesh))(x, p)
        err = float(jnp.max(jnp.abs(y_local - y_shard)))
        assert err < 1e-4, err
        print("OK", err)
    """)
    assert "OK" in out


@pytest.mark.slow
def test_distributed_rfann_shard_map_matches_local():
    out = _run("""
        import numpy as np, jax
        from repro.data.ann import make_vectors, make_attrs, mixed_workload
        from repro.serving.distributed import DistributedRFANN
        vecs = make_vectors(1024, 8, seed=0); attrs = make_attrs(1024, seed=0)
        mesh = jax.make_mesh((8,), ("data",))
        qv = make_vectors(16, 8, seed=5)
        rg, _ = mixed_workload(attrs, 16, seed=1, levels=4)
        d_local = DistributedRFANN(vecs, attrs, n_shards=8, m=16,
                                   ef_spatial=16, ef_attribute=16)
        ids_a, d_a = d_local.search(qv, rg, k=5, ef=48)
        d_mesh = DistributedRFANN(vecs, attrs, n_shards=8, mesh=mesh, m=16,
                                  ef_spatial=16, ef_attribute=16)
        ids_b, d_b = d_mesh.search(qv, rg, k=5, ef=48)
        assert np.array_equal(ids_a, ids_b), (ids_a, ids_b)
        print("OK")
    """)
    assert "OK" in out


def test_distributed_delta_tombstone_parity_8_shards():
    """Streaming segments on the sharded paths (subprocess, 8 forced host
    devices): a rank-space tombstone mask threaded through ``live=`` must
    give identical merged top-k on the mesh and local paths, and merging
    either with the same brute-force delta segment through the shared
    ``merge_topk`` stays identical — with no tombstoned id ever surfacing."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.data.ann import make_vectors, make_attrs, mixed_workload
        from repro.search import merge_topk
        from repro.serving.distributed import DistributedRFANN
        from repro.streaming import DeltaView
        vecs = make_vectors(1024, 8, seed=0); attrs = make_attrs(1024, seed=0)
        rng = np.random.default_rng(3)
        live = rng.random(1024) > 0.2           # rank-space tombstones
        qv = make_vectors(12, 8, seed=5)
        rg, _ = mixed_workload(attrs, 12, seed=1, levels=4)
        mesh = jax.make_mesh((8,), ("data",))
        kw = dict(n_shards=8, m=16, ef_spatial=16, ef_attribute=16)
        d_local = DistributedRFANN(vecs, attrs, **kw)
        d_mesh = DistributedRFANN(vecs, attrs, mesh=mesh, **kw)
        # a delta segment of 64 fresh points, searched once and merged with
        # both paths' base results through the one shared merge_topk
        dv = make_vectors(64, 8, seed=9); da_ = make_attrs(64, seed=9)
        o = np.argsort(da_, kind="stable")
        delta = DeltaView(dv[o], da_[o],
                          np.arange(2048, 2048 + 64, dtype=np.int32)[o])
        order = np.argsort(attrs, kind="stable")
        dead = set(order[~live].tolist())
        for plan in ("graph", "auto"):
            ia, da = d_local.search(qv, rg, k=5, ef=64, plan=plan, live=live)
            ib, db = d_mesh.search(qv, rg, k=5, ef=64, plan=plan, live=live)
            assert np.array_equal(ia, ib), plan
            di, dd = delta.search(qv, rg, 5)
            merged = []
            for ids, ds in ((ia, da), (ib, db)):
                mi, _ = merge_topk(
                    jnp.asarray(np.stack([ids.astype(np.int32), di])),
                    jnp.asarray(np.stack([np.where(ids >= 0, ds, np.inf),
                                          dd])), 5)
                merged.append(np.asarray(mi))
            assert np.array_equal(merged[0], merged[1]), plan
            got = set(int(x) for x in merged[0].ravel() if x >= 0)
            assert not (got & dead), (plan, got & dead)
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_async_local_dispatch_matches_sequential_8_shards():
    """Concurrency acceptance (subprocess, 8 forced host devices): the async
    local path — every shard's substrate dispatch enqueued before any block
    — must reproduce the sequential baseline's merged top-k exactly, on a
    mixed narrow/wide/degenerate workload under every plan, with a shared
    result cache giving bit-identical repeat batches on top.

    In-process twin (tier-1, smaller corpus, no subprocess):
    tests/test_async_cache.py::test_async_local_matches_sequential_8_shards.
    This copy runs the full-size workload in a clean interpreter so async
    scheduling is exercised without the rest of the suite's jit caches."""
    out = _run("""
        import numpy as np
        from repro.data.ann import make_vectors, make_attrs, selectivity_ranges
        from repro.search import SearchCache
        from repro.serving.distributed import DistributedRFANN
        vecs = make_vectors(1024, 16, seed=0); attrs = make_attrs(1024, seed=0)
        qv = make_vectors(24, 16, seed=5)
        s = np.sort(attrs)
        rg = np.concatenate([
            selectivity_ranges(attrs, 10, 0.01, seed=1),
            selectivity_ranges(attrs, 10, 0.5, seed=2),
            np.asarray([[s[5] + 1e-7, s[5] + 2e-7],      # globally empty
                        [s[17], s[17]],                  # single point
                        [s[3], s[40]],                   # one-shard clip
                        [s[0], s[-1]]], np.float32)])    # full span
        kw = dict(n_shards=8, m=16, ef_spatial=16, ef_attribute=16)
        d_seq = DistributedRFANN(vecs, attrs, async_dispatch=False, **kw)
        d_async = DistributedRFANN(vecs, attrs, async_dispatch=True, **kw)
        for plan in ("graph", "auto", "scan", "beam"):
            ia, da = d_seq.search(qv, rg, k=5, ef=48, plan=plan)
            ib, db = d_async.search(qv, rg, k=5, ef=48, plan=plan)
            assert np.array_equal(ia, ib), plan
            assert np.array_equal(da, db), plan
        cache = SearchCache(8 << 20)
        d_async.install_cache(cache)
        i1, d1 = d_async.search(qv, rg, k=5, ef=48, plan="auto")
        i2, d2 = d_async.search(qv, rg, k=5, ef=48, plan="auto")
        assert np.array_equal(i1, i2) and np.array_equal(d1, d2)
        assert cache.hits == 8 * len(rg), cache.snapshot()
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_production_mesh_shapes():
    out = _run("""
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh()
        m2 = make_production_mesh(multi_pod=True)
        assert dict(m1.shape) == {"data": 16, "model": 16}
        assert dict(m2.shape) == {"pod": 2, "data": 16, "model": 16}
        print("OK")
    """, devices=512)
    assert "OK" in out


@pytest.mark.slow
def test_gpipe_pipeline_fwd_and_grad_parity():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.parallel.pipeline import gpipe
        mesh = jax.make_mesh((4,), ("pp",))
        S, M, B, D = 4, 8, 2, 16
        rng = np.random.default_rng(0)
        params = {"w": jnp.asarray(rng.standard_normal((S, D, D)) * .3, jnp.float32),
                  "b": jnp.asarray(rng.standard_normal((S, D)) * .1, jnp.float32)}
        x = jnp.asarray(rng.standard_normal((M, B, D)), jnp.float32)
        stage_fn = lambda p, h: jnp.tanh(h @ p["w"] + p["b"])
        pipe = gpipe(stage_fn, mesh, "pp", S, M)
        with jax.set_mesh(mesh):
            y = jax.jit(pipe)(params, x)
        ref = x
        for s in range(S):
            ref = jnp.tanh(ref @ params["w"][s] + params["b"][s])
        assert float(jnp.max(jnp.abs(y - ref))) < 1e-5
        loss_pipe = lambda p: jnp.sum(pipe(p, x) ** 2)
        def loss_ref(p):
            h = x
            for s in range(S):
                h = jnp.tanh(h @ p["w"][s] + p["b"][s])
            return jnp.sum(h ** 2)
        with jax.set_mesh(mesh):
            g1 = jax.jit(jax.grad(loss_pipe))(params)
        g2 = jax.grad(loss_ref)(params)
        err = max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)))
        assert err < 1e-4, err
        print("OK", err)
    """, devices=4)
    assert "OK" in out
