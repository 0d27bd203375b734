"""The load generators against a fake system whose service time is
known."""

import threading
import time
from concurrent.futures import Future

from benchmark.harness import loadgen, stats


class FakeSystem:
    """Resolves each request `service_s` after it was submitted; `stall`
    makes the n-th submit block its caller (a starved sender)."""

    def __init__(self, service_s, stall=None):
        self.service_s = service_s
        self.stall = stall or {}
        self.n = 0

    def submit(self, payload):
        idx, self.n = self.n, self.n + 1
        if idx in self.stall:
            time.sleep(self.stall[idx])
        fut = Future()
        threading.Timer(self.service_s, fut.set_result, args=(payload,)).start()
        return fut


def test_closed_loop_counts_every_request_inside_the_window():
    system = FakeSystem(0.02)
    res = loadgen.closed_loop(system.submit, lambda rng: rng.randint(10),
                              n_clients=4, seconds=0.3, seed=3)
    assert res.failed == 0 and res.attempted == res.completed > 8
    assert res.window_s >= 0.3
    assert min(res.latencies) >= 0.02
    # same seed, same first payloads
    a = loadgen.np.random.RandomState([3, 0]).randint(10)
    b = loadgen.np.random.RandomState([3, 0]).randint(10)
    assert a == b


def test_closed_loop_counts_failures():
    def submit(_payload):
        raise RuntimeError("refused")

    res = loadgen.closed_loop(submit, lambda rng: 0, n_clients=2,
                              seconds=0.05, seed=0)
    assert res.completed == 0 and res.failed == res.attempted > 0


def test_open_loop_times_from_the_due_instant_and_reports_lateness():
    # the 3rd submit stalls the sender for 0.2 s: the requests due during
    # the stall are sent late, and their latency counts from when they
    # were due, so it exceeds the service time by the lateness
    system = FakeSystem(0.01, stall={2: 0.2})
    res = loadgen.open_loop(system.submit, lambda rng: 0, rate_per_s=100.0,
                            seconds=0.5, seed=5)
    assert res.failed == 0 and res.completed == res.attempted > 20
    assert len(res.lateness) == res.attempted
    assert max(res.lateness) > 0.1
    assert max(res.latencies) > 0.1 + 0.01
    # requests sent on time take the service time
    assert stats.median(res.latencies) < 0.1
    assert min(res.latencies) >= 0.01


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.samples_beyond(100, 95) == 5
    assert stats.samples_beyond(640, 95) == 32
    assert stats.median([3, 1, 2]) == 2 and stats.median([]) is None
    assert stats.percentile([], 95) is None
