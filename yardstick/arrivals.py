"""The open-loop load generator: a schedule drawn from the seed, requests
sent when they are due whether or not earlier ones have come back, each
timed from its due time, and the generator's own lateness reported.

Written for the yardstick and not copied from
``benchmarks/serving.py open_loop``, which times a request from when it
was actually submitted (so a stall hides the wait it imposes on later
requests), draws its gaps with ``time.sleep(expovariate)`` (so the
schedule drifts with every late wake-up), and sends one fixed size.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import Future
from typing import Callable, Dict, List, Sequence

import numpy as np


def schedule(seed: int, rate_per_s: float, seconds: float,
             rows_mix: Sequence[Dict[str, float]]):
    """Poisson arrivals at ``rate_per_s`` over ``seconds``, and rows per
    request from a mixture of uniform ranges (``share``, ``low``, ``high``
    inclusive). Returns (due times in seconds from the start, rows,
    row offsets as a fraction of the payload pool): the same seed gives
    the same three arrays."""
    rng = np.random.default_rng(seed)
    draws = int(rate_per_s * seconds * 1.2) + 64
    due = np.cumsum(rng.exponential(1.0 / rate_per_s, draws))
    while due[-1] < seconds:                      # not in practice
        due = np.concatenate([due, due[-1] + np.cumsum(
            rng.exponential(1.0 / rate_per_s, draws))])
    due = due[due < seconds]
    shares = np.array([m["share"] for m in rows_mix], float)
    part = rng.choice(len(rows_mix), size=len(due), p=shares / shares.sum())
    low = np.array([m["low"] for m in rows_mix])[part]
    high = np.array([m["high"] for m in rows_mix])[part]
    rows = rng.integers(low, high + 1)
    return due, rows, rng.random(len(due))


@dataclasses.dataclass
class Sent:
    """What the generator saw, one entry per request, times in seconds on
    ``perf_counter``. ``finished`` is NaN for a request that failed or had
    not come back when the generator stopped waiting."""
    due: np.ndarray
    sent: np.ndarray
    finished: np.ndarray
    results: Dict[int, np.ndarray]      # the answers that were asked for

    @property
    def failed(self) -> int:
        return int(np.sum(np.isnan(self.finished)))

    @property
    def latency_ms(self) -> np.ndarray:
        """Delivered minus due, completed requests only."""
        done = ~np.isnan(self.finished)
        return (self.finished[done] - self.due[done]) * 1e3

    @property
    def lag_ms(self) -> np.ndarray:
        return (self.sent - self.due) * 1e3

    def within(self, limit_ms: float) -> int:
        return int(np.sum(self.latency_ms <= limit_ms))


def drive(submit: Callable[[np.ndarray], Future], payloads: List[np.ndarray],
          due: np.ndarray, keep: Sequence[int] = (),
          drain_s: float = 30.0) -> Sent:
    """Send ``payloads[i]`` at ``due[i]`` seconds after now, from this
    thread, sleeping (never spinning: a spinning generator would take the
    interpreter from the server's own threads). Waits up to ``drain_s``
    after the last send for stragglers. ``keep`` names the requests whose
    answers are wanted back."""
    n = len(due)
    sent = np.full(n, np.nan)
    finished = np.full(n, np.nan)
    results: Dict[int, np.ndarray] = {}
    keep = set(int(i) for i in keep)
    futures: List[Future] = []
    called = [0]
    lock = threading.Lock()     # a callback runs in the thread that completes
                                # the future, or here if it is already done

    def on_done(i: int, fut: Future):
        if fut.exception() is None:
            finished[i] = time.perf_counter()
            if i in keep:
                results[i] = fut.result()
        with lock:
            called[0] += 1

    t0 = time.perf_counter()
    for i in range(n):
        wait = t0 + due[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        sent[i] = time.perf_counter()
        fut = submit(payloads[i])
        fut.add_done_callback(lambda f, i=i: on_done(i, f))
        futures.append(fut)
    give_up = time.perf_counter() + drain_s
    for fut in futures:
        try:
            fut.exception(timeout=max(0.0, give_up - time.perf_counter()))
        except TimeoutError:
            break
    # a waiter can wake before the future's callbacks have run
    while (called[0] < sum(f.done() for f in futures)
           and time.perf_counter() < give_up + 1.0):
        time.sleep(0.001)
    return Sent(due=t0 + due, sent=sent, finished=finished, results=results)
