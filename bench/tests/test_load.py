"""The load generators time requests from the client's side: an open loop
from when each request was due, a closed loop from its send; the answers go
into preallocated arrays."""
import threading
import time
from concurrent.futures import Future
from types import SimpleNamespace

import numpy as np
import pytest

from bench import data, load, traffic

K = 3


def _answer(qidx):
    return SimpleNamespace(ids=np.arange(K) + qidx,
                           dists=np.full(K, 0.5, np.float32),
                           stats={"strategy": np.int8(qidx % 2),
                                  "ndist": np.int64(7)})


class StallingEngine:
    """Answers each query after ``service`` seconds, one at a time, and
    stalls ``stall`` seconds before the first answer.  A query's answer
    names its vector, so the log can be checked against what was sent."""

    def __init__(self, service=0.002, stall=0.0, fail_every=0):
        self.service, self.stall, self.fail_every = service, stall, fail_every
        self.q = []
        self.sent = []
        self.cv = threading.Condition()
        self.stop = False
        self.t = threading.Thread(target=self.loop, daemon=True)
        self.t.start()

    def submit(self, qv, rg):
        f = Future()
        with self.cv:
            self.sent.append((float(qv[0]), tuple(rg)))
            self.q.append((f, len(self.sent)))
            self.cv.notify()
        return f

    def loop(self):
        first = True
        while True:
            with self.cv:
                while not self.q and not self.stop:
                    self.cv.wait(0.05)
                if self.stop and not self.q:
                    return
                f, nth = self.q.pop(0)
            if first:
                time.sleep(self.stall)
                first = False
            time.sleep(self.service)
            if self.fail_every and nth % self.fail_every == 0:
                f.set_exception(RuntimeError("injected"))
            else:
                f.set_result(_answer(nth))

    def close(self):
        self.stop = True
        self.t.join(timeout=5)


def _traffic(mix, n_ops, seed=3):
    cfg = {"n": 64, "d": 4, "generator": {"kind": "latent_mixture",
           "latent": 2, "clusters": 2, "spread": 1.0, "seed": 1},
           "attribute": {"kind": "uniform_rank"}}
    corpus = data.make_corpus(cfg, seed, n_ops)
    sched = traffic.make_schedule(mix, seed, n_ops)
    tr = load.Traffic(mix, corpus, seed, K)
    tr.prepare(sched)
    return tr, sched


OPEN = {"loop": "open", "rate": 200.0, "levels": [1, 2], "weights": [1, 1],
        "settle_s": 0.0}


def test_open_loop_times_from_due():
    tr, sched = _traffic(OPEN, 400)
    eng = StallingEngine(service=0.0005, stall=0.3)
    try:
        ses = load.run_open(eng, tr, sched, time.perf_counter() + 0.02,
                            0.0, 1.0)
    finally:
        eng.close()
    assert ses.ok.all() and len(ses) > 150
    # the generator kept to the schedule while the engine stalled ...
    assert (ses.sent - ses.due).max() < 0.15
    # ... so every request due during the stall is charged the wait
    stalled = ses.due < ses.due[0] + 0.25
    assert stalled.sum() > 20
    assert (ses.done - ses.due)[stalled].min() > 0.03
    assert (ses.done >= ses.sent).all() and (ses.sent >= ses.due).all()


def test_open_loop_records_the_window_only():
    mix = dict(OPEN, rate=400.0)
    tr, sched = _traffic(mix, 1200)
    eng = StallingEngine()
    try:
        t0 = time.perf_counter() + 0.02
        ses = load.run_open(eng, tr, sched, t0, 0.25, 0.5)
    finally:
        eng.close()
    assert ((t0 + 0.25 <= ses.due) & (ses.due < t0 + 0.75)).all()
    # settle queries were served but not recorded
    assert len(eng.sent) > len(ses) > 100
    np.testing.assert_array_equal(ses.rng, tr.ranges[ses.op])
    np.testing.assert_array_equal(ses.due, t0 + sched.due[ses.op])
    # each row holds the answer to its own query
    first = len(eng.sent) - len(ses) + 1
    np.testing.assert_array_equal(ses.ids[:, 0], first + np.arange(len(ses)))
    assert (ses.ndist == 7).all()


def test_closed_loop_keeps_clients_busy_and_drains():
    mix = {"loop": "closed", "clients": 4, "levels": [1], "weights": [1],
           "settle_s": 0.0, "pool": 1000}
    tr, sched = _traffic(mix, 1000)
    eng = StallingEngine(service=0.002)
    try:
        ses = load.run_closed(eng, tr, sched, 4, 0.4)
    finally:
        eng.close()
    assert ses.ok.all()
    assert ses.drained >= ses.done.max()
    # one engine serving 2 ms per query: at most 500 queries per second,
    # and the four clients kept it busy
    assert 20 < len(ses) <= 500 * 0.4 + 4
    np.testing.assert_array_equal(ses.sent, ses.due)


def test_failed_answers_are_recorded_and_end_a_client():
    mix = {"loop": "closed", "clients": 2, "levels": [1], "weights": [1],
           "settle_s": 0.0, "pool": 100}
    tr, sched = _traffic(mix, 100)
    eng = StallingEngine(service=0.001, fail_every=5)
    try:
        ses = load.run_closed(eng, tr, sched, 2, 5.0)
    finally:
        eng.close()
    # the fifth request fails and its client stops; the other fails at the
    # tenth: two failures, both recorded as answered but not ok
    assert ses.failed.sum() == 2 and (ses.done > 0).all()
    assert ses.ok.sum() == len(ses) - 2
    assert ses.errors == ["RuntimeError: injected"] * 2


@pytest.mark.parametrize("count", [1, load.CHUNK, load.CHUNK + 3])
def test_log_grows_in_blocks_without_moving_rows(count):
    log = load.Log(K)
    for j in range(count):
        assert log.claim(j, j, (j, j + 1), float(j)) == j
    f = Future()
    f.set_result(_answer(1))
    log.answer(count - 1, f)
    assert log.answered == 1
    op = log.column("op")
    np.testing.assert_array_equal(op, np.arange(count))
    np.testing.assert_array_equal(log.column("rng")[:, 1], np.arange(count) + 1)
    assert log.column("done")[-1] > 0 and log.column("ids")[-1, 0] == 1
    assert log.column("strategy")[-1] == 1
