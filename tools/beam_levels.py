#!/usr/bin/env python
"""Beam recall per selectivity level, and the served beam's parity with the
(n+1,) visited-bitmap search that the tests keep as their oracle.

Builds the ``deep96`` deployment of ``bench/configs/deep96.json`` (2^20
rows unless ``--n`` cuts it), then for each level 2^0 .. 2^-9 of the corpus
draws ``--queries`` range queries and runs the beam alone (``ef`` and ``k``
as configured), whatever the planner would route:

* ``recall`` — recall@k against the exact scan of the same ranges;
* ``same`` — whether ids, distances and hops equal the bitmap oracle's;
* ``ndist`` / ``ndist_oracle`` — mean neighbours scored per query;
* ``evict_pct`` — the visited table's evictions over its inserts;
* ``ms_table`` / ``ms_bitmap`` — wall time of one warm call of each.

This is the reading the planner's scan ceiling rests on
(``repro.planner.planner``): any change to routing, ``ef`` or the graph is
measured per level here first.  On a TPU host:

    python tools/beam_levels.py --seed 7 [--n 65536] [--out levels.json]

One JSON line per level goes to stdout, and the whole table to ``--out``.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(ROOT / "tests")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from _bitmap_beam import bitmap_beam  # noqa: E402
from bench import data, harness  # noqa: E402
from repro.core.beam import beam_search_batch  # noqa: E402
from repro.search import select_entry  # noqa: E402

LEVELS = 10


def timed(fn, *args, **kw):
    """(result, seconds) of one call after a warm-up call."""
    jax.block_until_ready(fn(*args, **kw))
    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t


def recall(found, exact):
    """Recall of ``found`` against the exact ids, both (Q, k), -1 padded."""
    hits = sum(len(set(f[f >= 0]) & set(e[e >= 0]))
               for f, e in zip(found, exact))
    return hits / max(int((exact >= 0).sum()), 1)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--n", type=int, default=0,
                    help="corpus rows (default: the configuration's)")
    ap.add_argument("--queries", type=int, default=64,
                    help="queries per level")
    ap.add_argument("--out", default="", help="write the table here")
    args = ap.parse_args(argv)

    cell = harness.resolve_cell(harness.load_spec(), "deep96.mixed")
    if args.n:
        cell.cfg = dict(cell.cfg, n=args.n)
    k, ef, nq = int(cell.cfg["k"]), int(cell.cfg["ef"]), args.queries
    corpus = data.make_corpus(cell.cfg, args.seed, nq * LEVELS)
    ix = harness.build(cell, corpus)
    g, sub = ix.g, ix.substrate
    vecs, nbrs = jnp.asarray(g.vecs), jnp.asarray(g.nbrs)
    rmq, dist_c = jnp.asarray(g.rmq), jnp.asarray(g.dist_c)
    order = np.asarray(g.order)
    rng = np.random.default_rng(args.seed)
    table = {}
    for level in range(LEVELS):
        ranges = data.rank_window(corpus.attrs_sorted, 2.0 ** -level, rng, nq)
        qv = corpus.queries[level * nq:(level + 1) * nq]
        lo, hi = sub.resolve(ranges)
        lo = jnp.asarray(lo.astype(np.int32))
        hi = jnp.asarray(hi.astype(np.int32))
        entry = select_entry(rmq, dist_c, lo, hi, g.n)
        q = jnp.asarray(qv)
        got, t_table = timed(beam_search_batch, vecs, nbrs, q, lo, hi,
                             entry, k=k, ef=ef)
        want, t_bitmap = timed(bitmap_beam, vecs, nbrs, q, lo, hi, entry,
                               k=k, ef=ef)
        ids = np.asarray(got[0])
        found = np.where(ids >= 0, order[np.maximum(ids, 0)], -1)
        exact = ix.search(qv, ranges, k=k, ef=ef, plan="scan").ids
        nd = np.asarray(got[2]["ndist"])
        table[level] = dict(
            same=all(bool(np.array_equal(np.asarray(a), np.asarray(b)))
                     for a, b in ((got[0], want[0]), (got[1], want[1]),
                                  (got[2]["hops"], want[2]["hops"]))),
            recall=recall(found, exact),
            ndist=float(nd.mean()),
            ndist_oracle=float(np.asarray(want[2]["ndist"]).mean()),
            evict_pct=100.0 * float(np.asarray(got[2]["evictions"]).sum())
            / max(float(nd.sum()), 1.0),
            hops=float(np.asarray(got[2]["hops"]).mean()),
            ms_table=1e3 * t_table, ms_bitmap=1e3 * t_bitmap)
        print(json.dumps(dict(level=level, **table[level])), flush=True)
    if args.out:
        Path(args.out).write_text(json.dumps(table, indent=1))


if __name__ == "__main__":
    main()
