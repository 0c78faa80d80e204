#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``: the plain
reference, computed one precision below what the configuration states
(float32, which the program's exact scan computes at ``Precision.HIGHEST``):
the three-pass ``high`` product, put in the program's place.  The harness's
comparison must call it not correct.

    python3 bench/control.py --workload deep96.narrow --seeds 11,12,13

For each seed it draws the cell's corpus and the queries a window would
send (the traffic's own levels and windows), answers them with the exact
three-pass top-k on the default device, and prints the comparison's numbers
beside their limits.  Every answer is judged as an exact (scan-routed)
one.  The benchmark's own runs never run this.
"""
import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BLOCK_Q = 32


def _bf16(x):
    """``x`` rounded to bfloat16 values, kept in float32: an explicit
    rounding that a cast pair could lose as excess precision."""
    import jax
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def high_topk(vecs, attrs, ids, qv, ranges, k: int):
    """Exact range-filtered top-k whose products are the three-pass
    bfloat16 product ``Precision.HIGH`` computes on the MXU (hi*hi + hi*lo
    + lo*hi, the lo*lo term dropped), spelled out so that it reads the same
    on any backend; on the default device.  Returns external ids and the
    squared distances it computed."""
    import jax
    import jax.numpy as jnp
    order = np.argsort(attrs, kind="stable")
    a = np.asarray(attrs, np.float32)[order]
    x = jnp.asarray(np.asarray(vecs, np.float32)[order])
    xn = jnp.sum(x * x, axis=1)
    lo = np.searchsorted(a, ranges[:, 0], "left").astype(np.int32)
    hi = np.searchsorted(a, ranges[:, 1], "right").astype(np.int32)

    @jax.jit
    def block(q, lo, hi, x, xn):
        def mm(u, v):
            # bfloat16-valued operands: one MXU pass multiplies them exactly
            return jnp.dot(u, v.T, preferred_element_type=jnp.float32)
        qh, xh = _bf16(q), _bf16(x)
        ql, xl = _bf16(q - qh), _bf16(x - xh)
        dot = mm(qh, xh) + mm(qh, xl) + mm(ql, xh)
        d = xn[None, :] - 2.0 * dot
        r = jnp.arange(x.shape[0])[None, :]
        d = jnp.where((r >= lo[:, None]) & (r < hi[:, None]), d, jnp.inf)
        d = d + jnp.sum(q * q, axis=1, keepdims=True)
        neg, idx = jax.lax.top_k(-d, k)
        return jnp.where(jnp.isfinite(neg), idx, -1), -neg

    out = np.full((len(qv), k), -1, np.int64)
    out_d = np.full((len(qv), k), np.inf, np.float32)
    for s in range(0, len(qv), BLOCK_Q):
        e = min(s + BLOCK_Q, len(qv))
        pad = BLOCK_Q - (e - s)
        q = np.pad(qv[s:e], ((0, pad), (0, 0)))
        r, dist = block(jnp.asarray(q), jnp.asarray(np.pad(lo[s:e], (0, pad))),
                        jnp.asarray(np.pad(hi[s:e], (0, pad))), x, xn)
        r = np.asarray(r)[:e - s]
        out[s:e] = np.where(r >= 0, np.asarray(ids)[order][np.maximum(r, 0)],
                            -1)
        out_d[s:e] = np.asarray(dist)[:e - s]
    return out, out_d


def window_queries(cell, seed: int, count: int):
    """The first ``count`` queries of the cell's window under ``seed``,
    with the corpus they see: (qv, ranges, vecs, attrs, row ids)."""
    from bench import data, load, traffic
    cfg, mix = cell.cfg, cell.mix
    n_ops = max(count, traffic.ops_needed(mix, 0))
    corpus = data.make_corpus(cfg, seed, n_ops)
    sched = traffic.make_schedule(mix, seed, n_ops)
    tr = load.Traffic(mix, corpus, seed, cfg["k"])
    tr.prepare(sched)
    first = len(sched) // 2 if mix["loop"] == "closed" else 0
    ops = [(first + j) % len(sched) for j in range(count)]
    qv = np.stack([tr.query(i)[0] for i in ops])
    rg = np.stack([tr.query(i)[2] for i in ops])
    return qv, rg, corpus.vecs, corpus.attrs, np.arange(cfg["n"])


def control(cell, seed: int, count: int) -> dict:
    from bench import reference
    qv, rg, vecs, attrs, ids = window_queries(cell, seed, count)
    k = cell.cfg["k"]
    found, found_d = high_topk(vecs, attrs, ids, qv, rg, k)
    ref = reference.HostReference(vecs, attrs, ids)
    g = cell.cfg["guarantees"]
    return reference.compare(
        ref, qv, rg, found, found_d, np.zeros(len(qv), np.int8), k=k,
        beam_floor=g["beam_routed_recall_floor"],
        gap_limit=g["scan_gap_limit"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--queries", type=int, default=1200)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import harness, reference
    cell = harness.resolve_cell(harness.load_spec(), args.workload)
    dev = jax.devices()[0]
    for seed in (int(s) for s in args.seeds.split(",")):
        nums = control(cell, seed, args.queries)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "device": dev.device_kind,
                          "correct": reference.passes(nums),
                          "numbers": nums}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
