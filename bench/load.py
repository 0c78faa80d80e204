"""Load generators: offer a schedule of queries to ``RFANNEngine`` and time
every request from the client's side.

Open loop: one generator thread sends each query when it is due, whatever
the engine is doing; a query's latency runs from when it was due (so a
stall charges every request that waits behind it), and how late the
generator itself ran is recorded beside it.

Closed loop: ``clients`` callers each send their next query the moment the
last one is answered (a completion callback, so no client thread competes
with the engine's for the interpreter); latency runs from the send.

Timestamps and answers go into preallocated numpy arrays (``Log``), not
into an object per request: once a request is answered the load driver
holds nothing of it on the Python heap, so it adds no work for the cyclic
collector of the process it shares with the system under test.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import List

import numpy as np

from bench import data
from bench.traffic import Schedule

ANSWER_WAIT_S = 60.0        # an answer due in the window may come this late
CHUNK = 4096                # requests per preallocated block of the log
MAX_ERRORS = 8              # failure messages kept, the first ones


COLUMNS = ("op", "qidx", "rng", "due", "sent", "done", "failed", "ids",
           "dists", "strategy", "ndist")


def _block(k: int) -> dict:
    return {"op": np.full(CHUNK, -1, np.int64),     # position in schedule
            "qidx": np.full(CHUNK, -1, np.int64),   # query vector index
            "rng": np.zeros((CHUNK, 2), np.float32),
            "due": np.zeros(CHUNK), "sent": np.zeros(CHUNK),
            "done": np.zeros(CHUNK),                # 0: never answered
            "failed": np.zeros(CHUNK, bool),
            "ids": np.full((CHUNK, k), -1, np.int64),
            "dists": np.full((CHUNK, k), np.inf, np.float32),
            "strategy": np.full(CHUNK, -1, np.int8),
            "ndist": np.zeros(CHUNK, np.int64)}


class Log:
    """Per-request client timestamps and answers, in send order, in blocks
    of ``CHUNK`` preallocated rows (a block is added when one fills; rows
    are never moved, so answer callbacks write without a lock)."""

    def __init__(self, k: int):
        self.k = k
        self.n = 0
        self.answered = 0
        self.errors: List[str] = []
        self._blocks: List[dict] = []
        self._lock = threading.Lock()

    def claim(self, op: int, qidx: int, rng, due: float) -> int:
        with self._lock:
            j = self.n
            self.n += 1
            if j // CHUNK == len(self._blocks):
                self._blocks.append(_block(self.k))
        b, i = self._blocks[j // CHUNK], j % CHUNK
        b["op"][i], b["qidx"][i], b["due"][i] = op, qidx, due
        b["rng"][i] = rng
        return j

    def sent(self, j: int, t: float) -> None:
        self._blocks[j // CHUNK]["sent"][j % CHUNK] = t

    def done(self, j: int) -> float:
        return float(self._blocks[j // CHUNK]["done"][j % CHUNK])

    def answer(self, j: int, fut) -> bool:
        """Record request ``j``'s answer from its future; ``False`` when
        it failed."""
        t = time.perf_counter()
        b, i = self._blocks[j // CHUNK], j % CHUNK
        ok = True
        try:
            res = fut.result()
            b["ids"][i] = res.ids
            b["dists"][i] = res.dists
            b["strategy"][i] = int(res.stats["strategy"])
            b["ndist"][i] = int(res.stats.get("ndist", 0))
        except Exception as e:          # noqa: BLE001 — recorded, judged later
            ok = False
            b["failed"][i] = True
            with self._lock:
                if len(self.errors) < MAX_ERRORS:
                    self.errors.append(f"{type(e).__name__}: {e}")
        b["done"][i] = t
        with self._lock:
            self.answered += 1
        return ok

    def column(self, name: str) -> np.ndarray:
        if not self._blocks:
            return _block(self.k)[name][:0]
        return np.concatenate([b[name] for b in self._blocks])[:self.n]


@dataclass
class Measured:
    """The window's requests, one row each, in send order (the columns of
    ``Log``)."""
    op: np.ndarray
    qidx: np.ndarray
    rng: np.ndarray
    due: np.ndarray
    sent: np.ndarray
    done: np.ndarray
    failed: np.ndarray
    ids: np.ndarray
    dists: np.ndarray
    strategy: np.ndarray
    ndist: np.ndarray
    start: float = 0.0      # the window opens
    end: float = 0.0        # the window closes (open loop: last due time)
    drained: float = 0.0    # closed loop: last answer of the window's sends
    errors: List[str] = field(default_factory=list)

    @classmethod
    def of(cls, log: Log, **kw) -> "Measured":
        return cls(**{c: log.column(c) for c in COLUMNS}, errors=log.errors,
                   **kw)

    def __len__(self) -> int:
        return len(self.op)

    @property
    def ok(self) -> np.ndarray:
        """Requests answered without an error."""
        return (self.done > 0) & ~self.failed


class Traffic:
    """What a load generator needs to turn schedule entries into queries:
    every window of the schedule is drawn up front, so the window a query
    gets does not depend on thread timing."""

    def __init__(self, mix: dict, corpus: data.Corpus, seed: int, k: int):
        self.mix, self.corpus, self.k = mix, corpus, k
        self.attrs_sorted = corpus.attrs_sorted
        self.r = data.rng(seed, data.STREAM_SCHEDULE + 100)
        self.ranges = None

    def prepare(self, sched: Schedule) -> None:
        self.ranges = np.zeros((len(sched), 2), np.float32)
        for lv in np.unique(sched.level):
            sel = np.flatnonzero(sched.level == lv)
            self.ranges[sel] = data.rank_window(self.attrs_sorted,
                                                2.0 ** -int(lv), self.r,
                                                len(sel))

    def query(self, i: int):
        """(vector, its pool index, attribute range) of operation ``i``."""
        qidx = i % len(self.corpus.queries)
        return self.corpus.queries[qidx], qidx, self.ranges[i]


def run_open(engine, traffic: Traffic, sched: Schedule, t0: float,
             settle_s: float, seconds: float, on_open=None) -> Measured:
    """Offer ``sched`` from ``t0`` on; the window holds the queries due in
    ``[t0 + settle_s, t0 + settle_s + seconds)``.  Returns once every query
    due in the window is answered or ``ANSWER_WAIT_S`` passed.  ``on_open``
    is called once, just before the window's first send."""
    start, end = t0 + settle_s, t0 + settle_s + seconds
    log = Log(traffic.k)
    for i in range(len(sched)):
        due = t0 + float(sched.due[i])
        if due >= end:
            break
        lag = due - time.perf_counter()
        if lag > 0:
            time.sleep(lag)
        qv, qidx, rng = traffic.query(i)
        if due < start:                 # settle: served, not recorded
            engine.submit(qv, rng)
            continue
        if on_open is not None:
            on_open()
            on_open = None
        j = log.claim(i, qidx, rng, due)
        log.sent(j, time.perf_counter())
        engine.submit(qv, rng).add_done_callback(
            lambda f, j=j: log.answer(j, f))
    else:
        raise RuntimeError("schedule ran out before the window closed")
    limit = max(end, time.perf_counter()) + ANSWER_WAIT_S
    while log.answered < log.n and time.perf_counter() < limit:
        time.sleep(0.005)
    return Measured.of(log, start=start, end=end)


def run_closed(engine, traffic: Traffic, sched: Schedule, clients: int,
               seconds: float, first_op: int = 0) -> Measured:
    """``clients`` callers, from an idle engine, each sending its next
    query on the last one's answer until ``seconds`` have passed; the
    window then lasts until the last of its queries is answered."""
    lock = threading.Lock()
    nxt = [first_op]
    log = Log(traffic.k)
    all_done = threading.Event()
    outstanding = [0]
    end = [0.0]

    def send():
        with lock:
            i = nxt[0]
            nxt[0] += 1
            outstanding[0] += 1
        i %= len(sched)
        qv, qidx, rng = traffic.query(i)
        t = time.perf_counter()
        j = log.claim(i, qidx, rng, t)
        log.sent(j, t)
        engine.submit(qv, rng).add_done_callback(
            lambda f, j=j: answered(j, f))

    def answered(j, fut):
        if log.answer(j, fut) and log.done(j) < end[0]:
            send()
        with lock:
            outstanding[0] -= 1
            if outstanding[0] == 0:
                all_done.set()

    start = time.perf_counter()
    end[0] = start + seconds
    for _ in range(clients):
        send()
    all_done.wait(timeout=seconds + ANSWER_WAIT_S)
    ses = Measured.of(log, start=start, end=end[0])
    ses.drained = float(ses.done.max(initial=start))
    ses.end = max(ses.end, ses.drained)
    return ses
