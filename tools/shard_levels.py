#!/usr/bin/env python
"""Recall per selectivity level of the range-sharded deployment, with each
shard routing its own clip.

Builds the ``deep96x4`` deployment of ``bench/configs/deep96x4.json``
(4 x 2^20 rows unless ``--n`` cuts it) as ``DistributedRFANN``: on a
("data",) mesh when the host has a device per shard, else through the local
path on one device, whose per-shard planners route every clip by the same
ceiling the mesh uses (``repro.search.substrate.shard_routes``) and give the
same ids.  For each level 2^0 .. 2^-9 of the *global* corpus it draws
``--queries`` windows, serves them with ``plan="auto"`` at the configured k
and ef, and compares them with the benchmark's float64 reference:

* ``scan`` / ``beam`` — queries whose answer reports each strategy (scan
  only when every shard the window touches scanned its clip);
* ``pairs_scan`` / ``pairs_beam`` — (query, shard) pairs by route;
* ``beam_recall`` — tie-aware recall@k of the beam-reported answers;
* ``scan_short`` / ``scan_gap`` — the exactness of scan-reported answers.

On a TPU host:

    python tools/shard_levels.py --seed 7 [--n 65536] [--out levels.json]

One JSON line per level goes to stdout, and the whole table to ``--out``.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import data, harness, reference  # noqa: E402
from repro.planner import BEAM, SCAN  # noqa: E402
from repro.search.substrate import (reported_strategy,  # noqa: E402
                                    shard_routes)
from repro.serving.distributed import DistributedRFANN  # noqa: E402

LEVELS = 10


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--n", type=int, default=0,
                    help="corpus rows (default: the configuration's)")
    ap.add_argument("--queries", type=int, default=128,
                    help="queries per level")
    ap.add_argument("--out", default="", help="write the table here")
    args = ap.parse_args(argv)

    cell = harness.resolve_cell(harness.load_spec(), "deep96x4.mixed")
    cfg = dict(cell.cfg, n=args.n or cell.cfg["n"])
    k, ef, nq = int(cfg["k"]), int(cfg["ef"]), args.queries
    shards = int(cfg["layout"]["shards"])
    batch = int(cfg["engine"]["max_batch"])
    corpus = data.make_corpus(cfg, args.seed, nq * LEVELS)
    devs = jax.devices()
    mesh = (jax.sharding.Mesh(np.asarray(devs[:shards]), ("data",))
            if len(devs) >= shards else None)
    t = time.perf_counter()
    dist = DistributedRFANN(corpus.vecs, corpus.attrs, n_shards=shards,
                            mesh=mesh, **cfg["build"])
    print(f"build: {shards} shards, {'mesh' if mesh else 'local path'} on "
          f"{devs[0].device_kind}, {time.perf_counter() - t:.3f}s",
          file=sys.stderr, flush=True)
    ref = reference.HostReference(corpus.vecs, corpus.attrs,
                                  np.arange(cfg["n"]))
    srt = corpus.attrs_sorted
    planner = (dist.mesh_substrate.planner if mesh is not None
               else dist.substrates[0].planner)
    rng = np.random.default_rng(args.seed)
    table = []
    for level in range(LEVELS):
        qv = corpus.queries[level * nq:(level + 1) * nq]
        ranges = data.rank_window(srt, 2.0 ** -level, rng, nq)
        lo, hi = dist.rank_range(ranges)
        routes, _, _ = shard_routes(lo, hi, per=dist.per, n_shards=shards,
                                    planner=planner, k=k, mode="auto")
        strategy = reported_strategy(routes)
        ids = np.zeros((nq, k), np.int64)
        dists = np.zeros((nq, k), np.float32)
        t = time.perf_counter()
        for a in range(0, nq, batch):
            res = dist.search_ranks(qv[a:a + batch], lo[a:a + batch],
                                    hi[a:a + batch], k=k, ef=ef, plan="auto")
            ids[a:a + batch], dists[a:a + batch] = res.ids, res.dists
        wall = time.perf_counter() - t
        chk = reference.compare(
            ref, qv, ranges, ids, dists, strategy, k=k,
            beam_floor=cfg["guarantees"]["beam_routed_recall_floor"],
            gap_limit=cfg["guarantees"]["scan_gap_limit"])
        row = {"level": level, "rows": int(round(2.0 ** -level * cfg["n"])),
               "scan": int((strategy == SCAN).sum()),
               "beam": int((strategy == BEAM).sum()),
               "pairs_scan": int((routes == SCAN).sum()),
               "pairs_beam": int((routes == BEAM).sum()),
               "beam_recall": chk.get("beam_recall", {}).get("value"),
               "scan_short": chk["scan_short"]["value"],
               "scan_gap": chk["scan_gap"]["value"],
               "dist_err": chk["dist_err"]["value"],
               "serve_s": round(wall, 6)}
        table.append(row)
        print(json.dumps(row), flush=True)
    hits = [(r["beam_recall"], r["beam"]) for r in table if r["beam"]]
    mean = sum(v * c for v, c in hits) / max(sum(c for _, c in hits), 1)
    print(json.dumps({"beam_recall_all_levels": mean,
                      "device": devs[0].device_kind}), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(table, indent=1))


if __name__ == "__main__":
    main()
