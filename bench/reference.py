"""Plain reference for range-filtered k-NN and the comparison that decides
``correct``.

``HostReference`` is exact range-filtered top-k over every row a query
could see, in numpy and independent of the program: it sorts the rows by
attribute itself and resolves each inclusive attribute range with its own
``searchsorted``.  A float32 pass over the query's rows picks ``8k``
candidates; float64 distances of those decide the top-k, ties broken
toward the lower id.  ``hit_counts`` is a copy of the program's tie-aware
benchmark recall (``benchmarks/common.recall_at_k``).

Two rows are ties when their float64 distances differ by less than the
worst-case float32 rounding error of the expansion-form distance the
program computes, ``(d + 2) * 2^-24 * (|q| + |x|)^2``: a float32 program may
order such rows either way and still be exact.
"""
from __future__ import annotations

import numpy as np

F32_EPS = 2.0 ** -24


def hit_counts(found, gt, *, gt_dists=None, found_dists=None, eps=0.0):
    """Per-query (hits, valid-gt count) of tie-aware recall@k.

    Ground-truth rows are ``-1``-padded when a range holds fewer than k
    rows; only valid entries count.  With both distance arrays, a found id
    outside the gt set still counts when its distance is within ``eps``
    (scalar or per-query) of the row's worst valid gt distance; per-row
    hits stay capped at the valid-gt count."""
    found = np.asarray(found)
    gt = np.asarray(gt)
    eps = np.broadcast_to(np.asarray(eps, np.float64), (len(gt),))
    hits = np.zeros(len(gt), np.int64)
    total = np.zeros(len(gt), np.int64)
    for i in range(len(gt)):
        gs = {int(x) for x in gt[i] if x >= 0}
        if not gs:
            continue
        fs = {int(x) for x in found[i] if x >= 0}
        row_hit = len(gs & fs)
        if gt_dists is not None and found_dists is not None:
            kth = max(float(d) for d, g in zip(gt_dists[i], gt[i]) if g >= 0)
            seen = set()
            for j, x in enumerate(found[i]):
                x = int(x)
                if x >= 0 and x not in gs and x not in seen \
                        and float(found_dists[i][j]) <= kth + eps[i]:
                    row_hit += 1
                seen.add(x)
            row_hit = min(row_hit, len(gs))
        hits[i] = row_hit
        total[i] = len(gs)
    return hits, total


class HostReference:
    """Exact range-filtered top-k on the host over rows with external ids."""

    def __init__(self, vecs: np.ndarray, attrs: np.ndarray,
                 ids: np.ndarray):
        order = np.argsort(np.asarray(attrs, np.float32), kind="stable")
        self.x = np.ascontiguousarray(np.asarray(vecs, np.float32)[order])
        self.a = np.asarray(attrs, np.float32)[order]
        self.ids = np.asarray(ids, np.int64)[order]
        self.xn = np.einsum("ij,ij->i", self.x, self.x)
        self.row_of = np.full(int(self.ids.max()) + 1, -1, np.int64)
        self.row_of[self.ids] = np.arange(len(self.ids))

    def bounds(self, ranges) -> tuple:
        """Inclusive attribute ranges -> half-open row spans [lo, hi)."""
        r = np.asarray(ranges, np.float32)
        lo = np.searchsorted(self.a, r[:, 0], side="left")
        hi = np.searchsorted(self.a, r[:, 1], side="right")
        return lo, np.maximum(hi, lo)

    def dist64(self, q, rows) -> np.ndarray:
        diff = self.x[rows].astype(np.float64) - np.asarray(q, np.float64)
        return np.einsum("ij,ij->i", diff, diff)

    def tie_eps(self, qv, gd) -> np.ndarray:
        """Per-query tie width: the float32 rounding bound of the
        expansion-form distance at the k-th neighbour's norm."""
        qn = np.sqrt(np.einsum("ij,ij->i", np.asarray(qv, np.float64),
                               np.asarray(qv, np.float64)))
        kth = np.where(np.isfinite(gd), gd, 0.0).max(axis=1)
        xn = qn + np.sqrt(kth)          # |x| <= |q| + |x - q|
        d = self.x.shape[1]
        return (d + 2) * F32_EPS * (qn + xn) ** 2

    def topk(self, qv, ranges, k: int) -> tuple:
        """(ids (Q, k) -1 padded, float64 dists (Q, k) +inf padded)."""
        qv = np.asarray(qv, np.float32)
        lo, hi = self.bounds(ranges)
        nq = len(qv)
        gt = np.full((nq, k), -1, np.int64)
        gd = np.full((nq, k), np.inf)
        for blk in _blocks(lo, hi):
            s0, s1 = int(lo[blk].min()), int(hi[blk].max())
            if s1 <= s0:
                continue
            dots = qv[blk] @ self.x[s0:s1].T            # float32 pass
            d32 = self.xn[None, s0:s1] - 2.0 * dots
            for j, i in enumerate(blk):
                a, b = int(lo[i]) - s0, int(hi[i]) - s0
                if b <= a:
                    continue
                row = d32[j, a:b]
                c = min(8 * k, b - a)
                cand = a + s0 + np.argpartition(row, c - 1)[:c]
                d64 = self.dist64(qv[i], cand)
                o = np.lexsort((self.ids[cand], d64))[:k]
                gt[i, :len(o)] = self.ids[cand[o]]
                gd[i, :len(o)] = d64[o]
        return gt, gd

    def found_dists(self, qv, ranges, found) -> np.ndarray:
        """float64 distance of each returned id; +inf for an id that does
        not exist or whose attribute lies outside its query's range, so the
        tie rule can never count it as a hit."""
        r = np.asarray(ranges, np.float32)
        found = np.asarray(found, np.int64)
        out = np.full(found.shape, np.inf)
        ok = (found >= 0) & (found < len(self.row_of))
        rows = np.where(ok, self.row_of[np.where(ok, found, 0)], -1)
        ok &= rows >= 0
        a = self.a[np.maximum(rows, 0)]
        ok &= (a >= r[:, :1]) & (a <= r[:, 1:2])
        for i in np.flatnonzero(ok.any(axis=1)):
            sel = np.flatnonzero(ok[i])
            out[i, sel] = self.dist64(qv[i], rows[i, sel])
        return out


def _blocks(lo, hi, max_elems: int = 1 << 24, max_q: int = 256):
    """Group queries so that one float32 matmul per group covers every
    query's rows: sorted by width then start, a group grows while its span
    times its size stays small against the rows its queries need."""
    width = hi - lo
    order = np.lexsort((lo, width))
    out, cur, s0, s1, need = [], [], 0, 0, 0
    for i in order:
        a, b, w = int(lo[i]), int(hi[i]), int(width[i])
        if cur:
            n0, n1 = min(s0, a), max(s1, b)
            cost = (n1 - n0) * (len(cur) + 1)
            if (len(cur) < max_q and cost <= max_elems
                    and cost <= max(4 * (need + w), 1 << 20)):
                cur.append(i)
                s0, s1, need = n0, n1, need + w
                continue
            out.append(np.asarray(cur))
        cur, s0, s1, need = [i], a, b, w
    if cur:
        out.append(np.asarray(cur))
    return out


def compare(ref: HostReference, qv, ranges, found, found_d, strategy, *,
            k: int, beam_floor: float, gap_limit: float) -> dict:
    """The numbers that decide ``correct`` for one run, each beside its
    limit.  ``strategy`` holds 0 (exact scan) or 1 (beam) per query;
    ``found_d`` holds the distances the program returned with its ids.

    Gaps are shares of ``(2|q| + sqrt(kth))^2``, a bound on ``(|q| + |x|)^2``
    and so the scale of the expansion-form distance's rounding error.

    * ``dist_err`` — over every answer, the widest gap between a returned
      distance and the float64 distance of the row it names: the precision
      of the timed path's arithmetic (float32 reads its rounding, a lower
      precision reads more).
    * ``scan_gap`` — over scan-routed answers, the widest gap by which a
      returned row's float64 distance exceeds the reference's k-th: an
      exact scan may only trade rows whose order the arithmetic cannot
      tell apart, so it shares ``dist_err``'s limit.
    * ``scan_short`` — reference neighbours that scan-routed answers did
      not fill (missing or repeated ids); limit 0.
    * ``foreign_ids`` — returned ids that do not exist or lie outside their
      query's range; limit 0.
    * ``beam_recall`` — recall@k of beam-routed answers (ties within
      float32 rounding allowed); the limit is the configuration's floor.
    """
    found = np.asarray(found, np.int64)
    strategy = np.asarray(strategy)
    gt, gd = ref.topk(qv, ranges, k)
    fd = ref.found_dists(qv, ranges, found)
    ok = (found >= 0) & np.isfinite(fd)
    distinct = np.zeros(len(found), np.int64)
    for i in range(len(found)):
        distinct[i] = len(set(found[i][ok[i]].tolist()))
    n_gt = (gt >= 0).sum(axis=1)
    kth = np.where(n_gt > 0, gd[np.arange(len(gd)), np.maximum(n_gt - 1,
                                                                0)], 0.0)
    qn = np.sqrt(np.einsum("ij,ij->i", np.asarray(qv, np.float64),
                           np.asarray(qv, np.float64)))
    scale = (2 * qn + np.sqrt(kth)) ** 2
    excess = np.where(ok, fd, 0.0) - kth[:, None]
    excess = np.where(ok, excess, 0.0).max(axis=1, initial=0.0)
    gap = np.maximum(excess, 0.0) / scale
    err = np.zeros(fd.shape)
    err[ok] = np.abs(np.asarray(found_d, np.float64)[ok] - fd[ok])
    scan, beam = strategy == 0, strategy != 0
    out = {
        "scan_queries": {"value": int(scan.sum())},
        "beam_queries": {"value": int(beam.sum())},
        "dist_err": {"value": float((err.max(axis=1, initial=0.0)
                                     / scale).max(initial=0.0)),
                     "limit": gap_limit, "ok": "<="},
        "scan_gap": {"value": float(gap[scan].max(initial=0.0)),
                     "limit": gap_limit, "ok": "<="},
        "scan_short": {"value": int(np.maximum(n_gt - distinct, 0)[scan]
                                    .sum()), "limit": 0, "ok": "<="},
        "foreign_ids": {"value": int(((found >= 0) & ~np.isfinite(fd))
                                     .sum()), "limit": 0, "ok": "<="},
    }
    if beam.any():
        hits, total = hit_counts(found[beam], gt[beam], gt_dists=gd[beam],
                                 found_dists=fd[beam],
                                 eps=ref.tie_eps(qv[beam], gd[beam]))
        out["beam_recall"] = {"value": float(hits.sum()) / max(
            int(total.sum()), 1), "limit": beam_floor, "ok": ">="}
    return out


def passes(numbers: dict) -> bool:
    ok = True
    for v in numbers.values():
        if "limit" not in v:
            continue
        if v["ok"] == "<=":
            ok &= v["value"] <= v["limit"]
        else:
            ok &= v["value"] >= v["limit"]
    return bool(ok)
