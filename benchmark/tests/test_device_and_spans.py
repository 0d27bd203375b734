"""The device's memory books and the span helpers, on fakes."""

from benchmark.harness import device, spans


class FakeDevice:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_peak_is_in_use_plus_reserved_on_the_fullest_device():
    # PR 22, BERT-base at batch 64 on a v5e: the allocator's high-water
    # mark beside what the runtime reserved for programs' temporaries
    a = FakeDevice({"peak_bytes_in_use": 2418672128,
                    "peak_bytes_reserved": 8013071360})
    b = FakeDevice({"peak_bytes_in_use": 1810143744,
                    "peak_bytes_reserved": 7989010432})
    assert device.peak_bytes([a, b]) == 2418672128 + 8013071360
    assert device.peak_bytes([FakeDevice({"peak_bytes_in_use": 5})]) == 5
    assert device.peak_bytes([FakeDevice(None)]) is None


def span(name, ts, dur, tid=1, **args):
    return {"name": name, "ts": ts, "dur": dur, "tid": tid, "args": args}


def test_inside_keeps_spans_of_the_same_thread_within_the_outer_span():
    rows = [
        span("serving.decode_loop", 100, 1000, tid=7),
        span("executor.step", 50, 40, tid=7),      # the prefill, before
        span("executor.step", 120, 50, tid=7),
        span("executor.step", 900, 100, tid=7),
        span("executor.step", 150, 50, tid=8),     # another thread
        span("executor.step", 1090, 50, tid=7),    # runs past the end
    ]
    got = spans.inside(rows, "executor.step", "serving.decode_loop")
    assert [(s["ts"], s["dur"]) for s in got] == [(120, 50), (900, 100)]
    assert spans.durations_ms(rows, "serving.decode_loop") == [1.0]


def test_executor_host_ms_is_the_call_that_waited_least():
    from benchmark.harness.context import Run
    from benchmark.layer_metrics import executor_host_ms

    # the window's first call finds the device idle (the host alone,
    # ~9 ms); the others wait for room in the queue, up to a device step
    rows = [span("executor.step", 1000 * i, 160000 - 100 * i)
            for i in range(1, 10)]
    rows.insert(0, span("executor.step", 0, 9400))
    rows.insert(3, span("serving.batch", 10, 5))
    run = Run(attempted=10, failed=0, checks={}, end_to_end={},
              facts={}, spans=list(reversed(rows)))
    assert executor_host_ms.read(run) == 9.4
    run.spans = []
    assert executor_host_ms.read(run) is None


def test_median_rate_ignores_a_stall():
    from benchmark.harness import stats

    # a read every 2.0 s of 1000 tokens; the host stalls 1.9 s once
    stamps = [0.0, 2.0, 4.0, 7.9, 9.9, 11.9]
    assert stats.median_rate(stamps, 1000.0) == 500.0
    assert abs(1000.0 * 5 / 11.9 - 420.17) < 0.01   # what the mean shows
    assert stats.median_rate(stamps[:1], 1000.0) is None
    assert stats.median_rate([], 1000.0) is None


def test_forward_check_needs_the_logits_and_the_loss():
    import numpy as np

    from benchmark.builders import common

    ref = np.linspace(-8.0, 8.0, 40, dtype=np.float32).reshape(4, 10)
    traffic = {"loss_rtol": 2.0 ** -8, "logits_tol": 0.02}
    ok = common.forward_check(5.001, 5.0, ref + 0.1, ref, traffic)
    assert ok["ok"] and abs(ok["logits_err"] - 0.0125) < 1e-6
    # one wrong row of logits that the mean loss would not show
    off = ref.copy()
    off[2] += 1.0
    assert not common.forward_check(5.001, 5.0, off, ref, traffic)["ok"]
    assert not common.forward_check(5.1, 5.0, ref, ref, traffic)["ok"]
