"""Algorithm 2 — RNSG construction.

Pipeline: (1) approximate-or-exact KNN graph (spatial proximity); (2) ±ef_attribute
rank window (attribute proximity, Alg. 2 line 7 — index-based on the
attribute-sorted order); (3) per-side gap-sorted candidate arrays; (4) the
vectorized Algorithm-1 pruning engine.  Ids are attribute ranks throughout.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.entry import build_rmq, centroid_dists
from repro.core.pruning import prune_all_jax
from repro.index.knn import exact_knn, nndescent


@dataclass
class RNSGGraph:
    vecs: np.ndarray          # (n,d) f32, attribute-sorted
    attrs: np.ndarray         # (n,)  f32, ascending
    nbrs: np.ndarray          # (n,m) int32, -1 padded (attribute-rank ids)
    order: np.ndarray         # (n,)  original ids of each rank
    centroid: np.ndarray      # (d,)
    dist_c: np.ndarray        # (n,)  δ(v, centroid) (entry structure)
    rmq: np.ndarray           # (LOG,n) int32 range-argmin table
    build_seconds: float = 0.0
    meta: dict = dataclasses.field(default_factory=dict)

    @property
    def n(self) -> int:
        return self.vecs.shape[0]

    @property
    def m(self) -> int:
        return self.nbrs.shape[1]

    @property
    def n_edges(self) -> int:
        return int((self.nbrs >= 0).sum())

    @property
    def index_bytes(self) -> int:
        """Graph-structure bytes (adjacency + entry structures), excluding the
        raw vector payload which every method must store."""
        return self.nbrs.nbytes + self.rmq.nbytes + self.dist_c.nbytes

    def save(self, path: str) -> None:
        """Atomic single-file save: the npz is written to a sibling temp
        file, fsynced, and renamed over ``path`` — a crash mid-save never
        corrupts the only copy of the index (same idiom as
        ``QueryPlanner.save_calibration``).  The parent directory is
        fsynced after the rename (``repro.index.io.fsync_dir``) so the
        rename itself survives power failure, not just the file bytes.
        ``meta`` and ``build_seconds`` ride along as a JSON sidecar entry
        so ``load`` round-trips them."""
        from repro.index.io import fsync_dir
        if not path.endswith(".npz"):
            path += ".npz"          # match np.savez's implicit suffix
        arrays = {f.name: np.asarray(getattr(self, f.name))
                  for f in dataclasses.fields(self)
                  if f.name not in ("meta", "build_seconds")}
        info = json.dumps(dict(build_seconds=float(self.build_seconds),
                               meta=self.meta))
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "wb") as f:
                np.savez_compressed(
                    f, __meta__=np.frombuffer(info.encode(), np.uint8),
                    **arrays)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
            fsync_dir(os.path.dirname(os.path.abspath(path)))
        finally:
            if os.path.exists(tmp):
                os.remove(tmp)

    @classmethod
    def load(cls, path: str) -> "RNSGGraph":
        if not os.path.exists(path) and os.path.exists(path + ".npz"):
            path += ".npz"          # save() appends the suffix
        with np.load(path) as z:    # context manager: no leaked npz handle
            arrays = {k: z[k] for k in z.files
                      if k not in ("__meta__", "build_seconds")}
            if "__meta__" in z.files:
                info = json.loads(bytes(z["__meta__"]).decode())
                return cls(**arrays,
                           build_seconds=float(info.get("build_seconds", 0.0)),
                           meta=dict(info.get("meta", {})))
            # legacy layout: build_seconds stored as a 0-d array, no meta
            bs = (float(z["build_seconds"])
                  if "build_seconds" in z.files else 0.0)
            return cls(**arrays, build_seconds=bs, meta={})


def gap_sorted_side(ids, n: int, knn_ids, ef_attribute: int, side: str):
    """Per-node candidate ids of one side, ascending rank-gap, -1 padded.
    Side candidates = attribute window ∪ same-side KNN neighbors.

    Traceable (jnp) over any block of rows — ``ids``: (B, 1) global
    attribute ranks of the rows, ``knn_ids``: (B, k) their KNN ids — so
    the single-device build and every slab of the sharded build
    (``core.build_sharded``) run this same code.  Both sorts are stable,
    and a stable sort's permutation is unique given its keys (gaps fit
    int32: |cand - id| < n < 2³¹)."""
    big = np.iinfo(np.int32).max // 2
    win_off = jnp.arange(1, ef_attribute + 1, dtype=jnp.int32)[None, :]
    win = ids - win_off if side == "l" else ids + win_off
    win_ok = (win >= 0) & (win < n)
    kn = knn_ids
    # kn < n guards against out-of-range candidates (e.g. pad-row ids from a
    # k >= n exact_knn, or a caller-supplied approximate KNN graph): an id
    # >= n would flow into prune_all_jax's vector gathers and the final
    # adjacency, corrupting the index
    kn_ok = ((kn >= 0) & (kn < n)
             & ((kn < ids) if side == "l" else (kn > ids)))
    cand = jnp.concatenate([jnp.where(win_ok, win, -1),
                            jnp.where(kn_ok, kn, -1)], axis=1)
    gap = jnp.where(cand >= 0, jnp.abs(cand - ids), big)
    order = jnp.argsort(gap, axis=1, stable=True)
    cand = jnp.take_along_axis(cand, order, axis=1)
    gap = jnp.take_along_axis(gap, order, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((cand.shape[0], 1), bool),
         (cand[:, 1:] == cand[:, :-1]) & (cand[:, 1:] >= 0)], axis=1)
    cand = jnp.where(dup, -1, cand)
    gap = jnp.where(dup, big, gap)
    order = jnp.argsort(gap, axis=1, stable=True)
    return jnp.take_along_axis(cand, order, axis=1).astype(jnp.int32)


@partial(jax.jit, static_argnames=("n", "ef_attribute", "side"))
def _gap_sorted_side_jit(knn_ids, n: int, ef_attribute: int, side: str):
    ids = jnp.arange(n, dtype=jnp.int32)[:, None]
    return gap_sorted_side(ids, n, knn_ids, ef_attribute, side)


def _gap_sorted_side(n: int, knn_ids, ef_attribute: int, side: str):
    """``gap_sorted_side`` over all n rows, on the device: (n, ef+k) i32."""
    return _gap_sorted_side_jit(jnp.asarray(knn_ids, jnp.int32), n,
                                ef_attribute, side)


def build_rnsg(vectors: np.ndarray, attrs: np.ndarray, *, m: int = 32,
               ef_spatial: int = 32, ef_attribute: int = 48,
               knn_method: str = "exact", knn_iters: int = 6,
               seed: int = 0, knn_ids: Optional[np.ndarray] = None,
               reverse_edges: bool = False,
               reverse_cap: Optional[int] = None) -> RNSGGraph:
    """Algorithm 2.  ``reverse_edges=True`` adds NSG-style reverse edges
    (beyond-paper knob).  Heredity note: with an UNSATURATED cap the
    augmentation commutes with range induction (a reverse edge's endpoints
    share the original edge's range), so heredity is exact; once the degree
    cap saturates, boundary slots may differ between a global and an induced
    build — the default cap 1.25·m therefore makes heredity approximate
    (tested both ways in tests/test_search.py)."""
    t0 = time.perf_counter()
    vectors = np.asarray(vectors, np.float32)
    attrs = np.asarray(attrs, np.float32)
    n = len(attrs)
    order = np.argsort(attrs, kind="stable")
    vs, as_ = vectors[order], attrs[order]

    marks = [time.perf_counter()]   # stage boundaries: knn|sides|prune|rmq
    if knn_ids is None:
        # a corpus has at most n-1 true neighbors per node; asking for more
        # only returns pad/duplicate rows (tiny-corpus regression)
        k_eff = min(ef_spatial, n - 1)
        if k_eff < 1:
            knn_ids = np.full((n, 0), -1, np.int32)
        elif knn_method == "exact":
            _, knn_ids = exact_knn(vs, k_eff)
        else:
            _, knn_ids = nndescent(vs, k_eff, iters=knn_iters, seed=seed)
    marks.append(time.perf_counter())
    cand_l, cand_r = jax.block_until_ready(
        [_gap_sorted_side(n, knn_ids, ef_attribute, s) for s in "lr"])
    marks.append(time.perf_counter())
    nbrs = prune_all_jax(vs, cand_l, cand_r, m)
    if reverse_edges:
        from repro.index.baselines import add_reverse_edges
        nbrs = add_reverse_edges(nbrs, reverse_cap or int(m * 1.25))
    marks.append(time.perf_counter())
    c, dist_c = centroid_dists(vs)
    rmq = build_rmq(dist_c)
    marks.append(time.perf_counter())
    stages = {name: b - a for name, a, b in
              zip(("knn", "sides", "prune", "rmq"), marks, marks[1:])}
    dt = time.perf_counter() - t0
    return RNSGGraph(vecs=vs, attrs=as_, nbrs=nbrs, order=order.astype(np.int32),
                     centroid=c.astype(np.float32), dist_c=dist_c, rmq=rmq,
                     build_seconds=dt,
                     meta=dict(m=m, ef_spatial=ef_spatial,
                               ef_attribute=ef_attribute, knn=knn_method,
                               stage_seconds=stages))
