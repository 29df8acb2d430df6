"""Open-loop load generation (the arithmetic is ``bench.py
run_serve_bench``'s, copied: Poisson schedule, latency from the instant a
request was *due*, sender lateness reported).

The schedule is a function of the seed and the mix alone.  ``drive`` sends
it from a few threads of one process; each request's latency runs from its
due time, so a stall makes every request behind it late instead of
lowering the offered rate, and how late the generator itself ran is
returned beside the latencies so that a starved generator is not read as
a slow server.
"""
import threading
import time

import numpy as np


def schedule(seed, rate, seconds, rows_mix):
    """(due seconds from the window's start, rows per request), both
    arrays, for Poisson arrivals at `rate` requests/s over `seconds`.
    `rows_mix` maps a row count (as str or int) to its probability."""
    rng = np.random.RandomState(seed)
    n = int(rate * seconds * 1.5) + 16
    due = np.cumsum(rng.exponential(1.0 / rate, n))
    due = due[due < seconds]
    kinds = sorted((int(k), float(p)) for k, p in rows_mix.items())
    probs = np.array([p for _, p in kinds])
    rows = rng.choice([k for k, _ in kinds], size=len(due),
                      p=probs / probs.sum())
    return due, rows


def drive(due, send, threads, timeout_s, clock=time.perf_counter,
          sleep=time.sleep):
    """Send request i (``send(i)``, which raises on a refusal, an error or
    a timeout) as close to ``due[i]`` as the threads allow.

    Returns a dict of per-request lists, in schedule order:
    ``latency_s`` (answer − due; `timeout_s` for a failed request),
    ``late_s`` (actual send − due), ``ok``; and ``t0``/``t_end`` on
    `clock`."""
    n = len(due)
    latency = [timeout_s] * n
    late = [0.0] * n
    ok = [False] * n
    errors = [None] * n
    next_i = [0]
    lock = threading.Lock()
    t0 = clock() + 0.05          # every thread is waiting by then

    def sender():
        while True:
            with lock:
                i = next_i[0]
                next_i[0] += 1
            if i >= n:
                return
            t_due = t0 + due[i]
            wait = t_due - clock()
            if wait > 0:
                sleep(wait)
            sent = clock()
            late[i] = max(0.0, sent - t_due)
            try:
                send(i)
            except Exception as e:      # boundary: the request failed
                errors[i] = "%s: %s" % (type(e).__name__, e)
                continue
            latency[i] = clock() - t_due
            ok[i] = True

    pool = [threading.Thread(target=sender, name="loadgen-%d" % k,
                             daemon=True) for k in range(threads)]
    for t in pool:
        t.start()
    limit = (due[-1] if n else 0.0) + timeout_s * 4 + 60
    for t in pool:
        t.join(timeout=max(0.0, t0 + limit - clock()))
    alive = sum(t.is_alive() for t in pool)
    return {"latency_s": latency, "late_s": late, "ok": ok,
            "errors": [e for e in errors if e], "t0": t0,
            "t_end": clock(), "senders_stuck": alive}
