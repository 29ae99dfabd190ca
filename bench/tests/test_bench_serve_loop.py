"""The open-loop serve loop: the offered work is fixed, and each request
is timed from when it was due, not from when it was submitted."""
import time

import numpy as np
import pytest

from bench.loops import serve_open


def test_every_seed_offers_the_same_arrivals_in_another_order():
    a = serve_open.arrivals(50.0, 10.0, seed=1)
    b = serve_open.arrivals(50.0, 10.0, seed=2**33)
    assert len(a) == len(b) == 500
    np.testing.assert_allclose(np.sort(np.diff(a, prepend=0.0)),
                               np.sort(np.diff(b, prepend=0.0)))
    assert not np.allclose(a, b)
    assert 0.0 < a.min() and a.max() < 10.0
    np.testing.assert_array_equal(a, serve_open.arrivals(50.0, 10.0, 1))


def test_tenant_shares_follow_zipf():
    seq = serve_open.tenant_sequence(1000, 8, 1.1, seed=4)
    counts = np.bincount(seq, minlength=8)
    w = 1.0 / np.arange(1, 9) ** 1.1
    assert counts.sum() == 1000
    assert np.all(np.abs(counts - 1000 * w / w.sum()) <= 1.0)
    assert np.array_equal(np.sort(seq),
                          np.sort(serve_open.tenant_sequence(1000, 8, 1.1,
                                                             seed=5)))


class _Ticket:
    def __init__(self):
        self.admitted = True
        self.result = None


class _Result:
    coalesce_size = 1
    theta = np.zeros(3)


class _SlowServer:
    """Serves one queued request per pump, each pump taking ``cost``."""

    def __init__(self, cost):
        self.cost, self.queue = cost, []
        self.recorder = None

    def submit(self, tenant, X):
        t = _Ticket()
        self.queue.append(t)
        return t

    @property
    def queue_depth(self):
        return len(self.queue)

    def pump(self):
        time.sleep(self.cost)
        t = self.queue.pop(0)
        t.result = _Result()
        return [t]


def test_latency_counts_from_the_due_time():
    drv = serve_open.Loop({"serve_families": ["ising"]},
                            {"tenants": 1, "rate_per_s": 1.0, "zipf_s": 1.1,
                             "pool_sets": 1}, seed=0)
    drv.pools = [[np.zeros((2, 3))]]
    drv.server = _SlowServer(cost=0.2)
    # two requests due 10 ms apart; the second waits behind the first pump
    drv.due = np.array([0.0, 0.01])
    drv.tenant = np.zeros(2, dtype=np.int64)
    drv.set_idx = np.zeros(2, dtype=np.int64)
    w = drv.window(0.05)
    assert w.attempted == 2 and w.failed == 0
    waits = w.samples["queue_wait_s"]
    # the second request is submitted only after the first pump (~0.2 s
    # after it was due) and served ~0.2 s later: from its due time it
    # waited ~0.19 s for the pump and took ~0.39 s in all
    assert waits[1] == pytest.approx(0.19, abs=0.05)
    p95 = w.end_to_end["request_p95_ms"]
    assert p95 == pytest.approx(0.95 * 390 + 0.05 * 200, abs=40)
    assert w.stats["generator_late_max_ms"] > 150
