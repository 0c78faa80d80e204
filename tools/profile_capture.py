#!/usr/bin/env python
"""Capture a jax.profiler device trace around a small batched-beam run.

    PYTHONPATH=src python tools/profile_capture.py [--out results/profiles]

Writes a profile directory (viewable with ``tensorboard --logdir`` or
Perfetto) containing the device timeline for a short beam-width sweep.
The serve path's stages (``repro.obs.stage``: ``rnsg.plan``,
``rnsg.scan_dispatch``, ``rnsg.beam_block``, ...) appear in the trace as
host spans, so kernel time lines up with the stages of
docs/observability.md.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np                                         # noqa: E402

from repro.core.rfann import RNSGIndex                     # noqa: E402
from repro.data.ann import make_attrs, make_vectors, mixed_workload  # noqa: E402
from repro.obs import device_trace                         # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="results/profiles")
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--nq", type=int, default=128)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--ef", type=int, default=64)
    args = ap.parse_args()

    log_dir = os.path.join(args.out, time.strftime("%Y%m%d-%H%M%S"))
    os.makedirs(log_dir, exist_ok=True)

    vecs = make_vectors(args.n, args.dim, seed=0)
    attrs = make_attrs(args.n, seed=0)
    qv = make_vectors(args.nq, args.dim, seed=7)
    ranges, _ = mixed_workload(attrs, args.nq, seed=3)
    print(f"[profile] building RNSG index (n={args.n}) ...")
    idx = RNSGIndex.build(vecs, attrs, m=16)

    # warm every dispatch shape OUTSIDE the trace so the capture holds
    # steady-state kernels, not one-off jit compilation
    for bw in (1, 4):
        idx.search(qv, ranges, k=args.k, ef=args.ef, plan="auto",
                   beam_width=bw)

    print(f"[profile] capturing device trace into {log_dir}")
    with device_trace(log_dir):
        for bw in (1, 4):
            res = idx.search(qv, ranges, k=args.k, ef=args.ef, plan="auto",
                             beam_width=bw)
            np.asarray(res.ids)        # block so device work lands in-trace
    print(f"[profile] done — view with: tensorboard --logdir {log_dir}")


if __name__ == "__main__":
    main()
