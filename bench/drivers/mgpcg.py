"""A stream of multigrid-preconditioned CG solves, back to back, ``ahead`` in flight.

HPCG's benchmark phase: ``repro.core.solvers.cg(op.apply_original, b,
tol=0, maxiter=iterations, precond=h.vcycle)`` from x = 0, so every solve
runs ``iterations`` iterations whatever its b, where ``h`` is
``repro.core.multigrid.hierarchy`` over the cell's matrix and its
``levels - 1`` halvings, each a ``bench.matrices.hpcg_27pt`` matrix on the
halved grid, with the library's injection maps.  The harness prepared the
fine operator; the coarse ones are prepared as it was: the configuration's
device and value dtype, and ``format="auto"`` where the fine operator was
routed by it (it then carries its stats), so each level picks its backend.
One line of output gives each level's grid, backend and tile geometry.

``b = A x_true`` for ``pool`` seeded standard-normal ``x_true``, taken in
seeded permutations of the pool, and the window, are the ``cg`` driver's;
the solve is jitted once with every level's device arrays as arguments.
Checks: ``residual``, the float64 ‖b − A x‖ / ‖b‖ of every solve, and
``vcycle_error``, the program's V-cycle under its own jit on a seeded r
against ``bench.mg_reference``'s.

``solver`` replaces the library's ``cg`` (same signature, ``precond``
included) and ``vcycle`` the library's hierarchy (a function r -> z): the
control (``bench/mg_control.py``) runs no library code.
"""
from __future__ import annotations

import json

import numpy as np
import scipy.sparse as sp

from bench.drivers import cg, rng, worst
from bench.matrices import hpcg_27pt
from bench.mg_reference import MgReference, grids, vcycle_error


def lift(fn, x):
    """``fn`` traced at ``x``'s shape, as ``(consts, call)``: ``call(consts, x)``
    takes the device arrays ``fn`` closes over as arguments (closed over,
    jit would copy them into the program as constants)."""
    import jax
    import jax.numpy as jnp

    closed = jax.make_jaxpr(fn)(x)
    consts = [jnp.asarray(c) for c in closed.consts]
    return consts, lambda cs, v: jax.core.eval_jaxpr(closed.jaxpr, cs, v)[0]


class Driver(cg.Driver):
    def __init__(self, op, mat, traffic: dict, seed: int, solver=None, vcycle=None):
        import jax

        if solver is None:
            from repro.core.solvers import cg as solver

        self.mat = mat
        self.levels = int(traffic["levels"])
        self.nu, self.omega = int(traffic["nu"]), float(traffic["omega"])
        self.tol = float(traffic["tol"])
        self.maxiter = int(traffic["iterations"])
        self.limits = {k: float(v) for k, v in traffic["checks"].items()}
        self.ahead = int(traffic["ahead"])
        gen = rng(seed, 0)
        A32 = sp.csr_matrix((mat.data, mat.indices, mat.indptr), shape=mat.shape)
        self.b_host = [np.asarray(A32 @ gen.standard_normal(mat.shape[1]).astype(np.float32),
                                  np.float32)
                       for _ in range(int(traffic["pool"]))]
        self.pool = [jax.device_put(b) for b in self.b_host]
        perm = rng(seed, 1)
        self.order = np.concatenate([perm.permutation(len(self.pool)) for _ in range(256)])
        self.r_host = rng(seed, 2).standard_normal(mat.shape[0]).astype(np.float32)
        if vcycle is None:
            vcycle = self._hierarchy(op).vcycle
        mv_consts, matvec = lift(op.apply_original, self.pool[0])
        vc_consts, precond = lift(vcycle, self.pool[0])
        self.consts = (mv_consts, vc_consts)

        def solve(consts, b):
            res = solver(lambda x: matvec(consts[0], x), b, tol=self.tol,
                         maxiter=self.maxiter, precond=lambda r: precond(consts[1], r))
            return res.x, res.iters

        self.solve = jax.jit(solve)
        self.vcycle = jax.jit(precond)
        self.results = []

    def _hierarchy(self, op):
        import jax.numpy as jnp

        from bench import harness
        from repro.core import multigrid
        from repro.core.formats import CSRMatrix

        gs = grids(self.mat.grid, self.levels)
        mats = [self.mat] + [hpcg_27pt.build(*g) for g in gs[1:]]
        kwargs = dict(device=op.device, value_dtype=op.value_dtype,
                      format="auto" if op.stats is not None else op.backend)
        h = multigrid.hierarchy(
            [CSRMatrix(jnp.asarray(m.indptr), jnp.asarray(m.indices), jnp.asarray(m.data),
                       tuple(m.shape)) for m in mats],
            [multigrid.injection(g) for g in gs[:-1]],
            nu=self.nu, omega=self.omega, prepared=[op], **kwargs)
        print(json.dumps({"mg_levels": [
            dict(level=l, grid=g, rows=m.shape[0], nnz=m.nnz, **harness.geometry(lev.op))
            for l, (g, m, lev) in enumerate(zip(gs, mats, h.levels))]}), flush=True)
        return h

    def window(self, seconds: float, span=None) -> dict:
        host = super().window(seconds, span)
        # one V-cycle before each solve's loop and one per iteration
        host["vcycles"] = host["iterations"] + host["solves"]
        return host

    def answers(self) -> dict:
        import jax

        z = self.vcycle(self.consts[1], jax.device_put(self.r_host))
        out = {"solves": [(i, np.asarray(x)) for i, x, _ in self.results],
               "vcycle": np.asarray(z)}
        self.results, self.pool, self.consts = [], [], ()
        return out

    def checks(self, ref, answers) -> tuple:
        res = [ref.residual(x, self.b_host[i]) for i, x in answers["solves"]]
        z_ref = MgReference(self.mat, self.levels).vcycle(
            self.r_host, nu=self.nu, omega=self.omega)
        err = vcycle_error(answers["vcycle"], z_ref)
        lim_r, lim_v = self.limits["residual"], self.limits["vcycle_error"]
        failed = sum(not (r <= lim_r) for r in res) + int(not (err <= lim_v))
        return {"residual": (worst(res), lim_r), "vcycle_error": (err, lim_v)}, failed
