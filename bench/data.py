"""Seeded corpora, attributes and query windows for the benchmark cells.

Everything a cell serves is drawn here from ``--seed`` and the cell's
configuration file; the program under test only ever sees the arrays.
Generators are selected by name from the configuration (``generator.kind``).

The selectivity windows follow the RNSG paper's Exp-1 protocol (a window
covering ``round(2^-i * n)`` consecutive attribute ranks, placed uniformly);
the arithmetic is a copy of the program's ``data/ann.selectivity_ranges`` so
that a later change to the program cannot move the yardstick.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

#: stream ids: one independent random stream per purpose, so that e.g. the
#: number of queries a run draws never shifts the corpus it serves
STREAM_PARAMS, STREAM_BASE, STREAM_QUERIES = 1, 2, 3
STREAM_ATTRS, STREAM_SCHEDULE = 5, 6


CHUNK = 4096             # rows drawn per independent sub-stream


def rng(seed: int, stream: int, part: int = 0) -> np.random.Generator:
    """Generator for one (seed, purpose, part) triple; any integer seed
    (negative or above 2^64 included) maps to a valid entropy word."""
    return np.random.default_rng([int(seed) % (1 << 64), int(stream),
                                  int(part)])


# ------------------------------------------------------------- vectors
class LatentMixture:
    """A Gaussian mixture drawn in ``latent`` dimensions and mapped linearly
    into ``d``: clusters of ``spread``-scaled centres with unit noise, so
    the local intrinsic dimension is ``latent``, not ``d`` (real embedding
    sets such as DEEP1M have a low intrinsic dimension).  Base rows and
    queries are draws from one mixture.
    The mixture itself (centres and map) is the deployment's and comes from
    the configuration's ``generator.seed``; a run's ``--seed`` draws rows
    from it, so every seed serves the same distribution."""

    def __init__(self, d: int, latent: int, clusters: int, spread: float,
                 seed: int):
        r = rng(seed, STREAM_PARAMS)     # the configuration's, not the run's
        self.d, self.latent = d, latent
        self.centers = r.standard_normal((clusters, latent)) * spread
        self.mix = (r.standard_normal((latent, d))
                    / np.sqrt(latent)).astype(np.float32)

    def draw(self, count: int, seed: int, stream: int) -> np.ndarray:
        """``count`` rows; the first ``m`` rows are the same for any
        ``count >= m`` (rows come in chunks of independent sub-streams)."""
        parts = []
        for c in range(-(-count // CHUNK)):
            r = rng(seed, stream, c + 1)
            m = min(CHUNK, count - c * CHUNK)
            z = self.centers[r.integers(0, len(self.centers), CHUNK)]
            parts.append((z + r.standard_normal((CHUNK, self.latent)))[:m])
        z = (np.concatenate(parts) if parts
             else np.zeros((0, self.latent))).astype(np.float32)
        return np.ascontiguousarray(z @ self.mix, np.float32)


def make_generator(cfg: dict) -> LatentMixture:
    g = cfg["generator"]
    if g["kind"] != "latent_mixture":
        raise ValueError(f"unknown generator {g['kind']!r}")
    return LatentMixture(cfg["d"], g["latent"], g["clusters"], g["spread"],
                         g["seed"])


# ---------------------------------------------------------- attributes
def make_attrs(cfg: dict, n: int, seed: int) -> np.ndarray:
    """The base rows' attribute.  ``uniform_rank``: a uniformly random
    permutation of 0..n-1 (distinct values, as the paper assumes)."""
    a = cfg["attribute"]
    if a["kind"] != "uniform_rank":
        raise ValueError(f"unknown attribute kind {a['kind']!r}")
    return rng(seed, STREAM_ATTRS).permutation(n).astype(np.float32)


# -------------------------------------------------------------- windows
def rank_window(attrs_sorted: np.ndarray, frac: float,
                r: np.random.Generator, count: int) -> np.ndarray:
    """``count`` windows of ``round(frac * n)`` consecutive ranks, placed
    uniformly (the paper's selectivity protocol): (count, 2) inclusive
    attribute bounds."""
    n = len(attrs_sorted)
    w = max(1, int(round(frac * n)))
    lo = r.integers(0, n - w + 1, count)
    return np.stack([attrs_sorted[lo], attrs_sorted[lo + w - 1]],
                    axis=1).astype(np.float32)


# ------------------------------------------------------------ a corpus
@dataclass
class Corpus:
    vecs: np.ndarray            # (n, d) base rows
    attrs: np.ndarray           # (n,) base attributes
    queries: np.ndarray         # (pool, d) query vectors, used in order

    @property
    def attrs_sorted(self) -> np.ndarray:
        return np.sort(self.attrs)


def make_corpus(cfg: dict, seed: int, n_queries: int) -> Corpus:
    gen = make_generator(cfg)
    n = cfg["n"]
    return Corpus(gen.draw(n, seed, STREAM_BASE), make_attrs(cfg, n, seed),
                  gen.draw(n_queries, seed, STREAM_QUERIES))


# ----------------------------------------------- stratified schedules
def stratified_counts(weights, total: int) -> np.ndarray:
    """Integer counts summing to ``total`` in proportion to ``weights``
    (largest remainder), so every seed serves the same multiset."""
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    raw = w * total
    c = np.floor(raw).astype(np.int64)
    short = total - int(c.sum())
    c[np.argsort(-(raw - c), kind="stable")[:short]] += 1
    return c


def exponential_gaps(count: int, rate: float,
                     r: np.random.Generator) -> np.ndarray:
    """Poisson inter-arrival gaps at ``rate``: the exponential's quantiles
    at ``(j + 1/2) / count`` in a seeded order — each seed offers the same
    gaps, in another order."""
    u = (np.arange(count) + 0.5) / count
    return r.permutation(-np.log1p(-u) / rate)
