"""A stream of ``op.apply_original(x)`` calls, kept ``ahead_s`` seconds ahead.

Each call is dispatched from the host as a caller makes it; the driver waits
for the oldest answer only once ``ahead`` calls are in flight, so that a
stall of the host shorter than ``ahead_s`` leaves the device busy.
``ahead`` is ``ahead_s`` over a warm call's time, timed in set-up, and at
most ``max_ahead``.  When the window's time is up nothing more is sent,
every call in flight is waited for, and the clock is read after that wait:
``spmv_ms`` is the whole window over every call sent in it.

``x`` is drawn from a pool of ``pool`` seeded standard-normal vectors, in a
seeded order.  A seeded reservoir of ``sample`` answers, and the last, are
checked after the window.
"""
from __future__ import annotations

import collections
import contextlib
import math
import time

import numpy as np

from bench.drivers import rng, worst


class Driver:
    def __init__(self, op, mat, traffic: dict, seed: int, solver=None):
        import jax

        self.op = op
        self.limit = float(traffic["checks"]["row_error"])
        self.sample = int(traffic["sample"])
        self.ahead_s = float(traffic["ahead_s"])
        self.max_ahead = int(traffic["max_ahead"])
        self.ahead = 1
        gen = rng(seed, 0)
        self.pool_host = [gen.standard_normal(mat.shape[1]).astype(np.float32)
                          for _ in range(int(traffic["pool"]))]
        self.pool = [jax.device_put(x) for x in self.pool_host]
        self.order = rng(seed, 1).integers(0, len(self.pool), size=1 << 16)
        self._pick = rng(seed, 2)
        self.done = 0        # answers waited for
        self.kept = []       # reservoir of (pool index, device answer)
        self.last = None

    def warm(self) -> None:
        for x in self.pool:
            self.op.apply_original(x).block_until_ready()
        t = time.perf_counter()
        for x in self.pool:
            self.op.apply_original(x).block_until_ready()
        per_call = (time.perf_counter() - t) / len(self.pool)
        self.ahead = max(1, min(self.max_ahead, math.ceil(self.ahead_s / per_call)))

    def window(self, seconds: float, span=None) -> dict:
        span = span or (lambda name: contextlib.nullcontext())
        apply = self.op.apply_original
        order, pool = self.order, self.pool
        flight = collections.deque()
        disp = []
        t_start = time.perf_counter()
        deadline = t_start + seconds
        with span("bench.window"):
            while time.perf_counter() < deadline:
                i = int(order[len(disp) % len(order)])
                t_a = time.perf_counter()
                with span("bench.dispatch"):
                    y = apply(pool[i])
                disp.append(time.perf_counter() - t_a)
                flight.append((i, y))
                if len(flight) >= self.ahead:
                    with span("bench.wait"):
                        self._done(*flight.popleft())
            with span("bench.drain"):
                while flight:
                    self._done(*flight.popleft())
            t_end = time.perf_counter()
        calls = len(disp)
        return {
            "attempted": calls,
            "calls": calls,
            "ahead": self.ahead,
            "elapsed_s": t_end - t_start,
            "spmv_ms": (t_end - t_start) / max(calls, 1) * 1e3,
            "dispatch_ms": float(np.mean(disp)) * 1e3 if disp else None,
            "dispatch_max_ms": float(np.max(disp)) * 1e3 if disp else None,
        }

    def _done(self, i: int, y) -> None:
        """Wait for one answer and offer it to the reservoir."""
        y.block_until_ready()
        self.done += 1
        self.last = (i, y)
        if len(self.kept) < self.sample:
            self.kept.append((i, y))
            return
        j = int(self._pick.integers(0, self.done))
        if j < self.sample:
            self.kept[j] = (i, y)

    def answers(self) -> list:
        kept = self.kept + ([self.last] if self.last is not None else [])
        out = [(i, np.asarray(y)) for i, y in kept]
        self.kept, self.last, self.pool, self.op = [], None, [], None
        return out

    def checks(self, ref, answers) -> tuple:
        errors = [ref.row_error(self.pool_host[i], y) for i, y in answers]
        failed = sum(not (e <= self.limit) for e in errors)
        return {"row_error": (worst(errors), self.limit)}, failed
