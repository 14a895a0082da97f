"""A stream of conjugate-gradient solves, back to back, ``ahead`` in flight.

``repro.core.solvers.cg(op.apply_original, b, tol, maxiter)`` with
``b = A x_true`` for ``pool`` seeded standard-normal ``x_true``, formed on
the host from the benchmark's own f32 arrays, and taken in seeded
permutations of the pool, so that every ``pool`` solves use each ``b`` once.
The solve is jitted once, with the operator's device arrays passed as
arguments, so every solve in the window runs the one compiled program.  The
driver waits for the oldest solve only once ``ahead`` are in flight, so a
stall of the host shorter than a solve leaves the device busy; when the
window's time is up nothing more is sent, every solve in flight is waited
for, and the clock is read after that wait.  Every solve is checked.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import time

import numpy as np
import scipy.sparse as sp

from bench.drivers import rng, worst


class Driver:
    def __init__(self, op, mat, traffic: dict, seed: int, solver=None):
        import jax
        import jax.numpy as jnp

        if solver is None:
            from repro.core.solvers import cg as solver

        self.tol = float(traffic["tol"])
        self.maxiter = int(traffic["maxiter"])
        self.limit = float(traffic["checks"]["residual"])
        self.ahead = int(traffic["ahead"])
        gen = rng(seed, 0)
        A32 = sp.csr_matrix((mat.data, mat.indices, mat.indptr), shape=mat.shape)
        self.b_host = [np.asarray(A32 @ gen.standard_normal(mat.shape[1]).astype(np.float32),
                                  np.float32)
                       for _ in range(int(traffic["pool"]))]
        self.pool = [jax.device_put(b) for b in self.b_host]
        perm = rng(seed, 1)
        self.order = np.concatenate([perm.permutation(len(self.pool)) for _ in range(256)])
        # The operator's device arrays become arguments of the jitted solve
        # (closed over, jit would copy them into the program as constants).
        closed = jax.make_jaxpr(op.apply_original)(self.pool[0])
        self.consts = [jnp.asarray(c) for c in closed.consts]
        jaxpr = closed.jaxpr

        def matvec(consts, x):
            return jax.core.eval_jaxpr(jaxpr, consts, x)[0]

        def solve(consts, b):
            res = solver(functools.partial(matvec, consts), b,
                         tol=self.tol, maxiter=self.maxiter)
            return res.x, res.iters

        self.solve = jax.jit(solve)
        self.results = []

    def warm(self) -> None:
        import jax.numpy as jnp

        # b = 0 meets the tolerance before the first iteration: the same
        # compiled program as every solve, at no iteration cost.
        x, _ = self.solve(self.consts, jnp.zeros_like(self.pool[0]))
        x.block_until_ready()

    def window(self, seconds: float, span=None) -> dict:
        span = span or (lambda name: contextlib.nullcontext())
        flight = collections.deque()
        sent = 0
        t_start = time.perf_counter()
        deadline = t_start + seconds
        with span("bench.window"):
            while time.perf_counter() < deadline:
                i = int(self.order[sent % len(self.order)])
                with span("bench.dispatch"):
                    x, iters = self.solve(self.consts, self.pool[i])
                sent += 1
                flight.append((i, x, iters))
                if len(flight) >= self.ahead:
                    with span("bench.wait"):
                        self._done(*flight.popleft())
            with span("bench.drain"):
                while flight:
                    self._done(*flight.popleft())
            t_end = time.perf_counter()
        iters = [int(k) for _, _, k in self.results]
        return {
            "attempted": sent,
            "solves": sent,
            "iterations": sum(iters),
            "elapsed_s": t_end - t_start,
            "solve_s": (t_end - t_start) / max(sent, 1),
            "cg_iters": float(np.mean(iters)) if iters else None,
        }

    def _done(self, i: int, x, iters) -> None:
        x.block_until_ready()
        self.results.append((i, x, iters))

    def answers(self) -> list:
        out = [(i, np.asarray(x)) for i, x, _ in self.results]
        self.results, self.pool, self.consts = [], [], []
        return out

    def checks(self, ref, answers) -> tuple:
        res = [ref.residual(x, self.b_host[i]) for i, x in answers]
        failed = sum(not (r <= self.limit) for r in res)
        return {"residual": (worst(res), self.limit)}, failed
