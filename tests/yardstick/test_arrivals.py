"""The open-loop generator: a schedule that repeats for a seed, latency
from the due time, and its own lateness reported."""

import threading
import time
from concurrent.futures import Future

import numpy as np

from yardstick import arrivals

MIX = [{"share": 0.6, "low": 1, "high": 1},
       {"share": 0.3, "low": 2, "high": 8},
       {"share": 0.1, "low": 9, "high": 32}]


def test_same_seed_same_schedule_other_seed_another():
    a = arrivals.schedule(7, 500.0, 4.0, MIX)
    b = arrivals.schedule(7, 500.0, 4.0, MIX)
    c = arrivals.schedule(8, 500.0, 4.0, MIX)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert len(a[0]) != len(c[0]) or not np.array_equal(a[0], c[0])


def test_schedule_is_poisson_at_the_rate_with_the_mixture():
    due, rows, where = arrivals.schedule(1, 1000.0, 20.0, MIX)
    assert np.all(np.diff(due) > 0) and 0 < due[0] and due[-1] < 20.0
    assert abs(len(due) / 20.0 - 1000.0) < 30          # 3 sigma is 21
    gaps = np.diff(due)
    assert abs(np.std(gaps) / np.mean(gaps) - 1.0) < 0.05   # exponential
    assert rows.min() == 1 and rows.max() == 32
    assert abs(np.mean(rows == 1) - 0.6) < 0.02
    assert abs(np.mean(rows >= 9) - 0.1) < 0.01
    assert np.all((0 <= where) & (where < 1))


def _server(service_s, stall_at=None, stall_s=0.0, fail=()):
    """One worker thread that answers in order, ``service_s`` apart."""
    inbox, threads = [], []
    lock = threading.Condition()

    def work():
        i = 0
        while True:
            with lock:
                while not inbox:
                    lock.wait()
                x, fut = inbox.pop(0)
            if x is None:
                return
            time.sleep(service_s + (stall_s if i == stall_at else 0.0))
            if i in fail:
                fut.set_exception(RuntimeError("refused"))
            else:
                fut.set_result(x * 2)
            i += 1

    t = threading.Thread(target=work, daemon=True)
    t.start()

    def submit(x):
        fut = Future()
        with lock:
            inbox.append((x, fut))
            lock.notify()
        return fut

    def stop():
        submit(None)
        t.join(timeout=5)
        assert not t.is_alive()

    return submit, stop


def test_latency_counts_from_the_due_time_so_a_stall_reaches_later_requests():
    due = np.arange(20) * 0.01                   # one every 10 ms
    payloads = [np.full(1, i, np.float32) for i in range(20)]
    submit, stop = _server(0.001, stall_at=5, stall_s=0.1)
    sent = arrivals.drive(submit, payloads, due, keep=[3, 7])
    stop()
    lat = sent.latency_ms
    assert sent.failed == 0 and len(lat) == 20
    assert np.all(lat[:5] < 30)
    # request 5 stalls 100 ms; 6..14 were due while it did and waited for
    # it, which a clock started at submit-return would still see, but a
    # generator that waited for replies (a closed loop) would not
    assert lat[5] > 100 and lat[6] > 85 and lat[9] > 55 and lat[13] > 15
    assert np.all(lat[16:] < 30)
    assert sent.within(30.0) == int(np.sum(lat <= 30.0)) < 20
    assert np.array_equal(sent.results[3], payloads[3] * 2)
    assert set(sent.results) == {3, 7}


def test_generator_lag_is_reported_when_submit_blocks():
    due = np.arange(10) * 0.005
    payloads = [np.zeros(1, np.float32)] * 10
    submit, stop = _server(0.0)

    def slow_submit(x):
        time.sleep(0.02)                         # a full queue, say
        return submit(x)

    sent = arrivals.drive(slow_submit, payloads, due)
    stop()
    assert sent.lag_ms[0] < 5                    # the first left on time
    assert sent.lag_ms[-1] > 100                 # 10 x 20 ms against 45 ms
    assert np.all(sent.latency_ms >= sent.lag_ms - 1e-6)


def test_a_refused_request_is_failed_and_has_no_latency():
    due = np.arange(6) * 0.002
    submit, stop = _server(0.0, fail={2, 4})
    sent = arrivals.drive(submit, [np.ones(1, np.float32)] * 6, due)
    stop()
    assert sent.failed == 2 and len(sent.latency_ms) == 4
