"""Streaming index under the serving engine: per-segment cache epochs,
queries racing compaction, the install_quantized cache-epoch fix, and
the WAL durability harness (SIGKILL mid-churn, SIGTERM graceful drain)."""
import os
import threading

import numpy as np
import pytest

from repro.core.rfann import RNSGIndex
from repro.search import SearchCache
from repro.search.cache import CacheEntry
from repro.serving.engine import RFANNEngine
from repro.streaming import BASE_NS, StreamingRFANN


def _entry(k=4):
    return CacheEntry(ids=np.arange(k, dtype=np.int32),
                      dists=np.zeros(k, np.float32), stats={})


# ------------------------------------------------------- per-segment epochs
def test_invalidate_segment_scopes_to_namespace():
    c = SearchCache(max_bytes=1 << 20)
    c.store(("base", 1), _entry())
    c.store(("other", 1), _entry())
    c.invalidate_segment("base")
    assert ("base", 1) not in c._d and ("other", 1) in c._d
    assert c.seg_invalidations == 1
    # global invalidate still drops everything
    c.store(("base", 2), _entry())
    c.invalidate()
    assert len(c) == 0


def test_segment_epoch_fences_late_stores():
    c = SearchCache(max_bytes=1 << 20)
    ep = c.epoch_for("base")
    c.invalidate_segment("base")                # concurrent compaction
    c.store(("base", 1), _entry(), epoch=ep)    # late store: dropped
    assert ("base", 1) not in c._d
    c.store(("base", 2), _entry(), epoch=c.epoch_for("base"))
    assert ("base", 2) in c._d
    # the *global* epoch component still fences per-segment stores
    ep = c.epoch_for("base")
    c.invalidate()
    c.store(("base", 3), _entry(), epoch=ep)
    assert ("base", 3) not in c._d
    # legacy int epochs (pre-segment callers) keep working
    c.store(("x", 1), _entry(), epoch=c.epoch)
    assert ("x", 1) in c._d
    c.store(("x", 2), _entry(), epoch=c.epoch - 1)
    assert ("x", 2) not in c._d


def test_engine_swap_index_segment_scoped():
    rng = np.random.default_rng(0)
    idx = RNSGIndex.build(rng.standard_normal((96, 8)).astype(np.float32),
                          rng.random(96).astype(np.float32), m=8)
    eng = RFANNEngine(idx, cache_bytes=1 << 20, max_wait_ms=0.5)
    try:
        eng.cache.store(("base", 1), _entry())
        eng.cache.store(("other", 1), _entry())
        eng.swap_index(idx, segment="base")     # self-swap, one segment
        assert ("base", 1) not in eng.cache._d
        assert ("other", 1) in eng.cache._d
        eng.swap_index(idx)                     # full swap: everything cold
        assert len(eng.cache._d) == 0
    finally:
        eng.close()


# ------------------------------------ install_quantized must go cache-cold
@pytest.mark.parametrize("precision", ["int8", "bf16"])
def test_install_quantized_after_cache_bumps_epoch_local(precision):
    rng = np.random.default_rng(1)
    vecs = rng.standard_normal((160, 8)).astype(np.float32)
    attrs = rng.random(160).astype(np.float32)
    idx = RNSGIndex.build(vecs, attrs, m=8)
    cache = SearchCache(max_bytes=1 << 20)
    idx.install_cache(cache)
    qv = rng.standard_normal((2, 8)).astype(np.float32)
    ar = np.asarray([[0.0, 1.0]] * 2, np.float32)
    idx.search(qv, ar, k=5, plan="scan", precision=precision)
    assert len(cache) == 2
    idx.search(qv, ar, k=5, plan="scan", precision=precision)
    assert cache.hits == 2
    idx.install_quantized(precision)    # rebuild: rows must not survive
    assert len(cache) == 0
    ns = idx.substrate.cache_ns
    assert cache.epoch_for(ns)[1] >= 1
    res = idx.search(qv, ar, k=5, plan="scan", precision=precision)
    assert cache.hits == 2              # cold again: no new hits
    assert (np.asarray(res.ids) >= 0).any()


def test_install_quantized_after_cache_bumps_epoch_mesh():
    import jax
    from jax.sharding import Mesh
    from repro.serving.distributed import DistributedRFANN
    rng = np.random.default_rng(2)
    vecs = rng.standard_normal((128, 8)).astype(np.float32)
    attrs = rng.random(128).astype(np.float32)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    dist = DistributedRFANN(vecs, attrs, n_shards=1, mesh=mesh, m=8)
    cache = SearchCache(max_bytes=1 << 20)
    dist.install_cache(cache)
    qv = rng.standard_normal((2, 8)).astype(np.float32)
    ar = np.asarray([[0.0, 1.0]] * 2, np.float32)
    dist.search(qv, ar, k=5, plan="graph", ef=128, precision="int8")
    assert len(cache) == 2
    dist.search(qv, ar, k=5, plan="graph", ef=128, precision="int8")
    assert cache.hits == 2
    dist.install_quantized("int8")
    assert len(cache) == 0
    assert cache.epoch_for("mesh")[1] >= 1


# --------------------------------------------- queries racing compactions
def test_queries_racing_compaction_through_engine():
    """N query threads × M compactions through ``RFANNEngine``: no stale
    cache rows (a deleted id never reappears once its delete returned), no
    tombstoned ids ever, and the obs counters total exactly."""
    rng = np.random.default_rng(3)
    n0, d, k = 256, 8, 8
    vecs = rng.standard_normal((n0, d)).astype(np.float32)
    attrs = rng.random(n0).astype(np.float32)
    s = StreamingRFANN(vecs, attrs, m=8, ef_spatial=16, ef_attribute=24,
                       max_delta=10**9)
    eng = RFANNEngine(s, k=k, ef=64, plan="scan", max_wait_ms=0.5,
                      cache_bytes=1 << 20)
    n_threads, n_compactions, reqs_per_thread = 4, 3, 30
    deleted: set = set()
    del_lock = threading.Lock()
    errors: list = []

    def hammer():
        r = np.random.default_rng(threading.get_ident() % 2**31)
        try:
            for _ in range(reqs_per_thread):
                q = r.standard_normal(d).astype(np.float32)
                a, b = np.sort(r.random(2).astype(np.float32))
                with del_lock:
                    dead_before = set(deleted)
                ids = eng.submit(q, (a, b)).result(timeout=60).ids
                bad = set(int(i) for i in ids if i >= 0) & dead_before
                if bad:
                    errors.append(f"tombstoned ids served: {bad}")
        except Exception as e:          # surface in the main thread
            errors.append(repr(e))

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    n_ins = n_del = 0
    try:
        for _ in range(n_compactions):
            for _ in range(12):
                eng.insert(rng.standard_normal(d).astype(np.float32),
                           float(rng.random()))
                n_ins += 1
            for _ in range(6):
                live = [i for i in list(eng.index._id_loc)
                        if i not in deleted]
                victim = int(rng.choice(live))
                with del_lock:
                    eng.delete(victim)
                    deleted.add(victim)
                n_del += 1
            assert s.compact(wait=True)
    finally:
        for t in threads:
            t.join(timeout=120)
        eng.close()
        s.close()
    assert not errors, errors
    assert s.compactions == n_compactions
    snap = eng.metrics()
    assert snap["counters"]["stream_compactions_total"] == n_compactions
    assert snap["counters"]["stream_inserts_total"] == n_ins
    assert snap["counters"]["stream_deletes_total"] == n_del
    assert (snap["counters"]["engine_requests_total"]
            == n_threads * reqs_per_thread)
    assert snap["streaming"]["compactions"] == n_compactions
    # the post-compaction live set is exactly base-live ∪ residual delta
    lv, la, li = s.live_items()
    assert len(set(li.tolist())) == len(li)
    assert not (set(li.tolist()) & deleted)


def test_repeat_query_sees_delete_immediately():
    """The stale-cache check in its sharpest form: a cached query row whose
    result contains X must go cold the moment X is deleted (per-segment
    epoch bump), not only at the next compaction."""
    rng = np.random.default_rng(4)
    n0, d, k = 192, 8, 5
    vecs = rng.standard_normal((n0, d)).astype(np.float32)
    attrs = rng.random(n0).astype(np.float32)
    s = StreamingRFANN(vecs, attrs, m=8, max_delta=10**9)
    eng = RFANNEngine(s, k=k, ef=64, plan="scan", max_wait_ms=0.5,
                      cache_bytes=1 << 20)
    try:
        q = rng.standard_normal(d).astype(np.float32)
        rgq = (0.0, 1.0)
        ids0 = eng.submit(q, rgq).result(timeout=60).ids
        victim = int(ids0[0])
        eng.submit(q, rgq).result(timeout=60)       # now cached
        eng.delete(victim)
        ids1 = eng.submit(q, rgq).result(timeout=60).ids
        assert victim not in set(int(i) for i in ids1)
        # and after compaction the answer is still victim-free
        assert s.compact(wait=True)
        ids2 = eng.submit(q, rgq).result(timeout=60).ids
        assert victim not in set(int(i) for i in ids2)
        assert set(int(i) for i in ids2 if i >= 0) \
            == set(int(i) for i in ids1 if i >= 0)
    finally:
        eng.close()
        s.close()


def test_engine_rejects_invalid_compaction_policy():
    """Regression: a zero/negative policy used to be accepted silently and
    wedge ``_maybe_compact`` into a compact-per-op loop."""
    rng = np.random.default_rng(6)
    s = StreamingRFANN(rng.standard_normal((32, 8)).astype(np.float32),
                       rng.random(32).astype(np.float32), m=8)
    with pytest.raises(ValueError, match=r"max_delta=0"):
        RFANNEngine(s, max_delta=0)
    with pytest.raises(ValueError, match=r"compact_every=-2"):
        s.set_compaction_policy(compact_every=-2)


def test_engine_forwards_compaction_policy():
    rng = np.random.default_rng(6)
    s = StreamingRFANN(rng.standard_normal((96, 8)).astype(np.float32),
                       rng.random(96).astype(np.float32), m=8,
                       max_delta=10**9)
    eng = RFANNEngine(s, max_wait_ms=0.5, max_delta=7, compact_every=123)
    try:
        assert s.max_delta == 7 and s.compact_every == 123
        for _ in range(7):      # hits max_delta: background compaction
            eng.insert(rng.standard_normal(8).astype(np.float32),
                       float(rng.random()))
        s.close()               # join the worker
        assert s.compactions == 1
        assert s.stats()["n_delta"] == 0
    finally:
        eng.close()
        s.close()


# ------------------------------------------------------------ durability
def _child_env():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    return env


def test_engine_wal_churn_survives_sigkill(tmp_path):
    """Hard process death mid-churn (SIGKILL — no atexit, no flush): the
    restarted index must serve exactly the acknowledged live set.  The
    child acks each mutation to a side file only *after* the engine call
    returned, so every acked op was WAL-logged first; recovery must
    reproduce ``live_after(m)`` for some prefix ``m >= acked``."""
    import importlib.util
    import subprocess
    import sys
    import time

    child_py = os.path.join(os.path.dirname(__file__),
                            "_wal_churn_child.py")
    spec = importlib.util.spec_from_file_location("_wal_churn_child",
                                                  child_py)
    child = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(child)

    wal, ckpt, ack = tmp_path / "wal", tmp_path / "ckpt", tmp_path / "ack"
    proc = subprocess.Popen(
        [sys.executable, child_py, str(wal), str(ckpt), str(ack)],
        env=_child_env(), stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    target, acked = 120, 0
    deadline = time.time() + 240
    try:
        while time.time() < deadline:
            if ack.exists():
                ints = [int(x) for x in ack.read_text().split()
                        if x.isdigit()]
                acked = ints[-1] if ints else 0
                if acked >= target:
                    break
            if proc.poll() is not None:
                break
            time.sleep(0.05)
    finally:
        proc.kill()                             # SIGKILL mid-churn
        out = proc.communicate(timeout=60)[0]
    assert acked >= target, (
        f"child only acked {acked} ops before timeout/exit; output:\n"
        f"{out.decode(errors='replace')[-2000:]}")

    from repro.streaming import StreamingRFANN
    rec = StreamingRFANN.recover(ckpt, wal, attach=False)
    got = set(rec._id_loc)
    n = len(child.script())
    match = next((m for m in range(acked, n + 1)
                  if got == child.live_after(m)), None)
    assert match is not None, (
        f"recovered live set ({len(got)} ids) matches no prefix >= "
        f"acked={acked} — acknowledged mutations were lost")
    # recovered index serves: search over the full attr range returns
    # only live external ids
    q = np.zeros((1, 8), np.float32)
    res = rec.search(q, np.array([[-10.0, 10.0]], np.float32), k=5)
    assert all(int(i) in got for i in res.ids[0] if i >= 0)


def test_serve_sigterm_drains_and_restarts(tmp_path):
    """SIGTERM on the serve launcher: graceful drain (PreemptionHandler),
    WAL sealed, index checkpointed, exit 0 — then a restart
    restores from the checkpoint and replays the WAL with zero
    acknowledged mutations lost."""
    import subprocess
    import sys
    import time

    wal, ckpt = tmp_path / "wal", tmp_path / "ckpt"
    argv = [sys.executable, "-m", "repro.launch.serve", "--mode", "rfann",
            "--n", "400", "--dim", "8", "--m", "8", "--max-delta", "64",
            "--requests", "100000", "--rate", "40",
            "--wal-dir", str(wal), "--index-path", str(ckpt)]
    proc = subprocess.Popen(argv, env=_child_env(), stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT)
    deadline = time.time() + 240
    try:
        # wait until the engine is up (baseline checkpoint committed and
        # the WAL has started taking appends), then preempt it
        while time.time() < deadline:
            if (ckpt / "manifest.json").exists() and wal.is_dir() \
                    and any(wal.iterdir()):
                break
            assert proc.poll() is None, "serve exited before starting"
            time.sleep(0.2)
        time.sleep(3.0)                         # let churn land in the WAL
        proc.terminate()                        # SIGTERM
        out = proc.communicate(timeout=180)[0].decode(errors="replace")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode == 0, f"serve did not exit cleanly:\n{out[-2000:]}"
    assert "SIGTERM: draining" in out
    assert "index persisted" in out

    # restart: restores + replays, serves a short run to completion
    argv2 = argv[:argv.index("--requests")] + [
        "--requests", "16", "--wal-dir", str(wal),
        "--index-path", str(ckpt)]
    out2 = subprocess.run(argv2, env=_child_env(), stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, timeout=240,
                          check=True).stdout.decode(errors="replace")
    assert "restored index" in out2
    assert "replayed" in out2


# ------------------------------------------------------- compaction failure
def test_failed_compaction_raises_not_prints(monkeypatch):
    """A rebuild that fails on the worker thread surfaces as an error to
    ``compact(wait=True)`` — and a failed background rebuild to
    ``close()`` — instead of only printing a thread traceback; the served
    view stays the pre-compaction one."""
    import repro.streaming.streaming as smod
    rng = np.random.default_rng(1)
    s = StreamingRFANN(rng.standard_normal((96, 8)).astype(np.float32),
                       rng.random(96).astype(np.float32), m=8,
                       max_delta=1 << 20)
    for j in range(4):
        s.insert(rng.standard_normal(8).astype(np.float32), float(j) / 4)

    def boom(*a, **kw):
        raise MemoryError("injected rebuild failure")
    monkeypatch.setattr(smod, "build_rnsg", boom)
    with pytest.raises(RuntimeError, match="compaction failed") as ei:
        s.compact(wait=True)
    assert isinstance(ei.value.__cause__, MemoryError)
    assert s.stats()["n_delta"] == 4 and s.compactions == 0
    assert s.compact(wait=False)                   # background: raised at
    s._worker.join()                               # close, not swallowed
    with pytest.raises(RuntimeError, match="compaction failed"):
        s.close()


# ------------------------------------------------------------- k > 128
@pytest.mark.parametrize("m", [20, 300])
def test_delta_search_exact_above_scan_k(m):
    """k > 128 exceeds the scan kernel's running top-k row; the delta
    serves it through its own exact window top-k (also when k exceeds the
    delta's padded capacity) with the scan's contract: ids and distances
    of the k nearest rows in the range, -1/+inf padded."""
    from repro.streaming.delta import DeltaView
    rng = np.random.default_rng(m)
    d, k = 8, 150
    view = DeltaView.empty(d)
    for j in range(m):
        view = view.with_inserted(rng.standard_normal(d), float(rng.random()),
                                  1000 + j)
    qv = rng.standard_normal((3, d)).astype(np.float32)
    ar = np.asarray([[0.0, 1.0], [0.2, 0.7], [2.0, 3.0]], np.float32)
    ids, dists = view.search(qv, ar, k)
    assert ids.shape == dists.shape == (3, k)
    for i in range(3):
        inr = (view.attrs >= ar[i, 0]) & (view.attrs <= ar[i, 1])
        dd = ((view.vecs[inr].astype(np.float64) - qv[i]) ** 2).sum(1)
        o = np.argsort(dd, kind="stable")[:k]
        want = view.ids[inr][o]
        got = ids[i][ids[i] >= 0]
        assert set(got.tolist()) == set(want.tolist())
        np.testing.assert_allclose(np.sort(dists[i][:len(got)]), dd[o],
                                   rtol=1e-4, atol=1e-4)
        assert np.isinf(dists[i][len(got):]).all()


def test_streaming_search_above_scan_k_with_delta():
    """A streaming query with k > 128 and a non-empty delta is served
    (base through beam, delta exactly) and returns no tombstoned id."""
    rng = np.random.default_rng(8)
    n0, d, k = 192, 8, 150
    s = StreamingRFANN(rng.standard_normal((n0, d)).astype(np.float32),
                       rng.random(n0).astype(np.float32), m=8,
                       max_delta=10**9)
    eng = RFANNEngine(s, k=k, ef=256, plan="auto", max_wait_ms=0.5)
    try:
        for j in range(40):
            eng.insert(rng.standard_normal(d).astype(np.float32),
                       float(rng.random()))
        dead = {3, 17, n0 + 5}
        for e in dead:
            eng.delete(e)
        q = rng.standard_normal(d).astype(np.float32)
        res = eng.submit(q, (0.0, 1.0)).result(timeout=60)
        assert len(res.ids) == k
        got = {int(i) for i in res.ids if i >= 0}
        assert not got & dead
        lv, _, li = s.live_items()
        dd = ((lv.astype(np.float64) - q) ** 2).sum(1)
        want = set(li[np.argsort(dd, kind="stable")[:k]].tolist())
        assert len(got & want) >= 0.95 * k
        assert got & set(range(n0, n0 + 40))      # delta rows were merged
    finally:
        eng.close()
        s.close()
