"""The general load generator: every traffic mix is parameters for it.

A mix file (``traffic/<mix>.json``) names its ``loop``:

* ``solve``: whole solves from ``w = 0``, back to back, on resident data;
* ``open``: requests due on a fixed schedule at ``rate`` per second,
  whatever the server does (independent users);
* ``closed``: ``clients`` callers, each sending its next request when its
  last one returns.

Arrival gaps are the exact quantiles of an exponential distribution,
shuffled by the seed: every seed offers the same gaps, in another order.
Each call into a layer runs inside a ``TraceAnnotation`` named after it.
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

LOOPS = ("solve", "open", "closed")


def validate(mix: dict) -> None:
    loop = mix.get("loop")
    if loop not in LOOPS:
        raise ValueError(f"traffic loop must be one of {LOOPS}, got {loop!r}")
    if loop == "open" and not mix.get("rate", 0) > 0:
        raise ValueError("an open loop needs rate > 0")
    if loop == "closed" and not int(mix.get("clients", 0)) > 0:
        raise ValueError("a closed loop needs clients > 0")


def arrivals(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the window's start) of ``round(rate * seconds)``
    requests."""
    count = max(int(round(rate * seconds)), 1)
    q = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-q) / rate
    gaps = gaps[np.random.default_rng(seed).permutation(count)]
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def solve_loop(solve, seconds: float, clock=time.perf_counter) -> dict:
    """Whole solves until the first one that ends after ``seconds``."""
    results = []
    t0 = clock()
    while True:
        with TraceAnnotation("solve"):
            results.append(solve())
        if clock() - t0 >= seconds:
            break
    return dict(elapsed_s=clock() - t0, solves=results)


def _collect(sched, t, owner, done):
    for rid, comp in sched.take_finished().items():
        done.append((owner.pop(rid), t, comp.margin))


def open_loop(sched, request, due: np.ndarray, drain_s: float = 60.0,
              clock=time.perf_counter) -> dict:
    """Submit each request when it is due, tick while any waits, and
    drain after the last is due. ``request(i)`` is the i-th request.
    Returns, per answered request, ``(i, done_s, margin)``, and each
    request's submit lateness."""
    owner: dict[int, int] = {}
    done: list = []
    late = np.full(len(due), np.nan)
    i, count = 0, len(due)
    t0 = clock()
    limit = float(due[-1]) + drain_s
    while True:
        now = clock() - t0
        if i < count and due[i] <= now:
            with TraceAnnotation("submit"):
                while i < count and due[i] <= now:
                    owner[sched.submit(request(i))] = i
                    late[i] = now - due[i]
                    i += 1
        if sched.waiting:
            with TraceAnnotation("tick"):
                sched.tick()
            _collect(sched, clock() - t0, owner, done)
        elif i < count:
            time.sleep(max(float(due[i]) - (clock() - t0), 0.0))
        else:
            break
        if now > limit:
            break
    return dict(done=done, lateness_s=late, elapsed_s=clock() - t0)


def closed_loop(sched, request, clients: int, seconds: float,
                drain_s: float = 60.0, clock=time.perf_counter) -> dict:
    """``clients`` callers in a loop until ``seconds``; the requests still
    waiting then are drained and answered, outside the window."""
    owner: dict[int, int] = {}
    done: list = []
    sent = 0

    def send():
        nonlocal sent
        owner[sched.submit(request(sent))] = sent
        sent += 1

    with TraceAnnotation("submit"):
        for _ in range(clients):
            send()
    t0 = clock()
    while True:
        with TraceAnnotation("tick"):
            sched.tick()
        t = clock() - t0
        before = len(done)
        _collect(sched, t, owner, done)
        if t >= seconds:
            break
        with TraceAnnotation("submit"):
            for _ in range(len(done) - before):
                send()
    window_s, in_window = t, len(done)
    while sched.waiting and clock() - t0 < seconds + drain_s:
        sched.tick()
        _collect(sched, clock() - t0, owner, done)
    return dict(done=done, sent=sent, window_s=window_s,
                in_window=in_window)
