"""Serving launcher.

``--mode rfann`` (the paper's kind): build an RNSG over a synthetic corpus and
drive the dynamic-batching engine with Poisson request arrivals — reports
QPS, recall and latency percentiles.

``--mode lm``: batched LM serving (prefill + decode loop) on a smoke config.

  PYTHONPATH=src python -m repro.launch.serve --mode rfann --n 8192 --requests 512

``--metrics-path out.prom`` dumps the final metrics snapshot on shutdown:
Prometheus text exposition at the given path plus a JSON sibling
(``out.prom.json``); ``--log-interval S`` turns on the engine's periodic
one-line stats log while serving.

``--index-path DIR`` makes startup stateful: the first run builds the index
and persists it (sharded directory format, ``repro.index.io``) on
shutdown; later runs restore it in seconds instead of rebuilding.
``--build-shards S`` routes a fresh static build through the multi-device
sharded constructor (bit-identical output).

``--wal-dir DIR`` (streaming mode) adds crash durability on top: every
mutation is appended to a checksummed write-ahead log before it is
acknowledged, restart replays the uncompacted tail onto the
``--index-path`` checkpoint, SIGTERM drains gracefully (seal WAL,
checkpoint, persist calibration + metrics), and a WAL write failure
degrades the server to read-only instead of crashing it.  See
``docs/durability.md``.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_smoke_config
from repro.core.rfann import RNSGIndex
from repro.data.ann import (ground_truth, make_attrs, make_vectors,
                            mixed_workload, recall_at_k)
from repro.launch.specs import concrete_batch
from repro.models.lm import Model
from repro.models.params import ShardPlan
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.fault_tolerance import PreemptionHandler
from repro.serving.engine import RFANNEngine
from repro.streaming import ReadOnlyIndexError


def _restore_index(args, streaming: bool):
    """Restore a prebuilt index from ``--index-path`` (sharded directory
    format) when one is there and matches the requested mode/corpus shape;
    returns ``None`` when a fresh build is needed."""
    from repro.index import io
    if not (args.index_path and io.is_index_dir(args.index_path)):
        return None
    t0 = time.perf_counter()
    idx = io.load_index(args.index_path)
    from repro.streaming import StreamingRFANN
    if isinstance(idx, StreamingRFANN) != streaming:
        print(f"[serve] index at {args.index_path} is the wrong kind for "
              f"this mode — rebuilding")
        return None
    d = idx.d if streaming else idx.g.vecs.shape[1]
    n_ok = streaming or idx.g.n == args.n
    if d != args.dim or not n_ok:
        print(f"[serve] index at {args.index_path} does not match the "
              f"requested corpus (n={args.n}, dim={args.dim}) — rebuilding")
        return None
    print(f"[serve] restored index from {args.index_path} "
          f"in {time.perf_counter() - t0:.2f}s (no rebuild)")
    if streaming and getattr(args, "wal_dir", ""):
        # crash-consistent restart: the checkpoint is the floor, the WAL
        # tail on top of it is every acknowledged mutation the previous
        # process did not get to fold in (see docs/durability.md)
        replayed = idx.replay_wal(args.wal_dir)
        print(f"[serve] replayed {replayed} WAL records from "
              f"{args.wal_dir} (lsn watermark {idx.applied_lsn})")
    return idx


def serve_rfann(args):
    vecs = make_vectors(args.n, args.dim, seed=0)
    attrs = make_attrs(args.n, seed=0)
    qv = make_vectors(args.requests, args.dim, seed=7)
    ranges, _ = mixed_workload(attrs, args.requests, seed=3)
    streaming = args.max_delta > 0 or args.compact_every > 0
    rng = np.random.default_rng(0)
    idx = _restore_index(args, streaming)
    if idx is not None and streaming:
        pending_ins = [j for j in range(args.n) if j not in idx._id_loc]
        print(f"[serve] {idx.stats()}")
    elif idx is not None:
        print(f"[serve] {idx.stats()}")
    elif streaming:
        # streaming serve: seed the base with 80% of the corpus, churn the
        # held-out tail (inserts) plus random deletes through the engine
        # while the first half of the requests stream in, then measure
        # recall on the second half against the *final* live set
        from repro.streaming import StreamingRFANN
        n0 = max(args.n * 4 // 5, 256)
        print(f"[serve] building streaming RNSG base (n0={n0}) ...")
        idx = StreamingRFANN(vecs[:n0], attrs[:n0], m=args.m,
                             ef_spatial=32, ef_attribute=48,
                             max_delta=args.max_delta or 1024,
                             compact_every=args.compact_every)
        pending_ins = list(range(n0, args.n))
        print(f"[serve] {idx.stats()}")
    else:
        if args.build_shards:
            print(f"[serve] building RNSG index "
                  f"({args.build_shards} shards) ...")
            idx = RNSGIndex.build_sharded(vecs, attrs,
                                          n_shards=args.build_shards,
                                          m=args.m, ef_spatial=32,
                                          ef_attribute=48)
        else:
            print("[serve] building RNSG index ...")
            idx = RNSGIndex.build(vecs, attrs, m=args.m, ef_spatial=32,
                                  ef_attribute=48)
        print(f"[serve] {idx.stats()}")
    if args.precision != "f32":
        idx.install_quantized(args.precision)   # build quantized corpus once
    warm = idx.search(qv[:8], ranges[:8], k=args.k, ef=args.ef,
                      plan=args.plan, beam_width=args.beam_width,
                      precision=args.precision)             # warm the jit
    assert warm.ids.shape == (8, args.k)                    # SearchResult

    engine = RFANNEngine(idx, k=args.k, ef=args.ef, plan=args.plan,
                         beam_width=args.beam_width,
                         precision=args.precision,
                         max_batch=args.max_batch, max_wait_ms=2.0,
                         calibration_path=args.calibration or None,
                         cache_bytes=args.cache_mb << 20,
                         log_interval_s=args.log_interval,
                         trace_sample_every=args.trace_sample_every,
                         max_delta=args.max_delta or None,
                         compact_every=args.compact_every or None,
                         index_path=args.index_path or None,
                         index_save_shards=args.index_shards,
                         wal_dir=(args.wal_dir or None) if streaming else None,
                         wal_sync=args.wal_sync)
    if streaming and args.wal_dir and not args.index_path:
        print("[serve] note: --wal-dir without --index-path logs mutations "
              "but leaves no checkpoint to recover onto")
    # graceful SIGTERM: stop accepting work, drain in-flight futures, then
    # the normal shutdown path seals the WAL and persists index +
    # calibration + metrics — zero acknowledged mutations lost
    preempt = PreemptionHandler().install()
    futs = []
    churn_until = args.requests // 2
    churn_on = streaming
    t0 = time.perf_counter()
    for i in range(args.requests):
        if preempt.should_stop():
            print(f"[serve] SIGTERM: draining after {len(futs)} submitted "
                  f"requests, then checkpointing")
            break
        futs.append(engine.submit(qv[i], ranges[i]))
        if churn_on and i < churn_until:
            try:
                if pending_ins:
                    j = pending_ins.pop()
                    engine.insert(vecs[j], float(attrs[j]), ext_id=j)
                if i % 4 == 3:      # one delete per four churn steps
                    live = list(engine.index._id_loc)
                    engine.delete(int(live[rng.integers(len(live))]))
            except ReadOnlyIndexError as e:
                # WAL append failed: the index degraded to read-only
                # (stream_read_only gauge = 1).  Searches keep working —
                # stop mutating, keep serving.
                churn_on = False
                print(f"[serve] churn stopped, serving continues: {e}")
        if args.rate > 0:
            time.sleep(rng.exponential(1.0 / args.rate))
    # SIGTERM can land before the first submit — drain an empty futs list
    # without tripping np.stack, so shutdown still seals the WAL below
    results = (np.stack([f.result().ids for f in futs]) if futs
               else np.zeros((0, args.k), np.int64))
    dt = time.perf_counter() - t0
    engine.close()
    if streaming:
        idx.close()     # drain any in-flight compaction, seal the WAL
    if engine.cache is not None:
        print(f"[serve] result cache: {engine.cache.snapshot()}")
    if args.calibration:
        print(f"[serve] cost-model calibration persisted to {args.calibration}")
    if args.index_path:
        print(f"[serve] index persisted to {args.index_path} "
              f"({args.index_shards} shards) — restored on next startup")
    if args.metrics_path:
        # final snapshot on shutdown, alongside the calibration save:
        # Prometheus text at the given path, JSON snapshot as a sibling
        from repro.obs import write_prometheus
        write_prometheus(engine.registry, args.metrics_path)
        with open(args.metrics_path + ".json", "w") as f:
            json.dump(engine.metrics(), f, indent=2, sort_keys=True,
                      default=float)
        print(f"[serve] metrics written to {args.metrics_path} (+.json)")

    served = len(futs)
    if served == 0:
        rec = float("nan")          # drained before any request was served
        if streaming:
            print(f"[serve] streaming: {idx.stats()}")
    elif streaming and served > churn_until:
        # score only the post-churn half against the final live set (the
        # requests that raced mutations have no single ground truth)
        lv, la, li = idx.live_items()
        order = np.argsort(la, kind="stable")
        gt_r, _ = ground_truth(lv[order], la[order], qv[churn_until:served],
                               ranges[churn_until:served], args.k)
        gt = np.where(gt_r >= 0, li[order][np.maximum(gt_r, 0)], -1)
        rec = recall_at_k(results[churn_until:], gt)
        print(f"[serve] streaming: {idx.stats()}")
    elif streaming:
        rec = float("nan")          # drained before the scored half began
        print(f"[serve] streaming: {idx.stats()}")
    else:
        order = np.argsort(attrs, kind="stable")
        gt_r, _ = ground_truth(vecs[order], attrs[order], qv[:served],
                               ranges[:served], args.k)
        gt = np.where(gt_r >= 0, order[np.maximum(gt_r, 0)], -1)
        rec = recall_at_k(results, gt)
    print(f"[serve] served {served} reqs in {dt:.2f}s "
          f"({served/dt:.0f} QPS) recall@{args.k}={rec:.4f}")
    print(f"[serve] {engine.stats.summary()}")
    return rec


def serve_lm(args):
    cfg = get_smoke_config(args.arch)
    model = Model(cfg, ShardPlan())
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    b, s = args.max_batch, 32
    batch = concrete_batch(cfg, "prefill", b, s, rng)
    prefill = jax.jit(lambda p, bb: model.prefill(p, bb, cache_len=s + args.new_tokens))
    decode = jax.jit(model.decode, donate_argnums=(1,))
    cache, logits = prefill(params, batch)
    toks = [jnp.argmax(logits[:, :cfg.vocab_size], -1).astype(jnp.int32)]
    t0 = time.perf_counter()
    for i in range(args.new_tokens):
        logits, cache = decode(params, cache, jnp.asarray(s + i, jnp.int32), toks[-1])
        toks.append(jnp.argmax(logits[:, :cfg.vocab_size], -1).astype(jnp.int32))
    dt = time.perf_counter() - t0
    out = np.stack([np.asarray(t) for t in toks], 1)
    print(f"[serve] {args.arch}: batch={b} decoded {args.new_tokens} tokens "
          f"in {dt:.2f}s ({b*args.new_tokens/dt:.0f} tok/s)")
    print(f"[serve] sample continuation ids: {out[0][:12].tolist()}")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["rfann", "lm"], default="rfann")
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--n", type=int, default=8192)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate (req/s); 0 = as fast as possible")
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--ef", type=int, default=64)
    ap.add_argument("--m", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--plan", choices=["auto", "graph", "scan", "beam"],
                    default="auto", help="query-planner strategy routing")
    ap.add_argument("--beam-width", type=int, default=1,
                    help="batched beam expansion width (1 = legacy "
                         "single-node hops; try 4 for throughput)")
    ap.add_argument("--precision", choices=["f32", "int8", "bf16"],
                    default="f32",
                    help="distance-scoring precision: quantized corpora "
                         "(int8/bf16) scan cheaper and rerank the survivors "
                         "in exact f32 (same ids as f32)")
    ap.add_argument("--index-path", default="",
                    help="index directory: restore the index from here at "
                         "startup (skipping the build) and persist it on "
                         "shutdown (repro.index.io sharded format)")
    ap.add_argument("--index-shards", type=int, default=1,
                    help="row-shard count for --index-path saves (restore "
                         "fills shards with parallel reads)")
    ap.add_argument("--build-shards", type=int, default=0,
                    help="static mode: build the graph with the sharded "
                         "multi-device constructor over this many device "
                         "slabs (0 = single-host build; results are "
                         "bit-identical either way)")
    ap.add_argument("--calibration", default="",
                    help="JSON path: load cost-model calibration at startup, "
                         "persist it on shutdown")
    ap.add_argument("--cache-mb", type=int, default=0,
                    help="result-cache byte budget in MiB (0 = no cache)")
    ap.add_argument("--metrics-path", default="",
                    help="write the final metrics snapshot here on shutdown "
                         "(Prometheus text; JSON sibling at <path>.json)")
    ap.add_argument("--log-interval", type=float, default=0.0,
                    help="seconds between one-line stats logs (0 = off)")
    ap.add_argument("--trace-sample-every", type=int, default=0,
                    help="attach a QueryTrace to every Nth batch (0 = off)")
    ap.add_argument("--max-delta", type=int, default=0,
                    help="streaming mode: compact when the delta segment "
                         "reaches this many rows (0 = static index)")
    ap.add_argument("--compact-every", type=int, default=0,
                    help="streaming mode: compact every N mutations "
                         "(0 = size-triggered only)")
    ap.add_argument("--wal-dir", default="",
                    help="streaming mode: write-ahead-log directory — every "
                         "mutation is logged (checksummed) before it is "
                         "applied, and a crashed server replays the tail "
                         "onto the --index-path checkpoint at restart")
    ap.add_argument("--wal-sync", choices=["always", "batch", "none"],
                    default="batch",
                    help="WAL durability: fsync per record / group commit "
                         "(every N records or T seconds) / OS page cache "
                         "only")
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.mode == "rfann":
        serve_rfann(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
