"""Iterative solvers on top of SpMV — the paper's motivating workload (CG).

The solvers are written against an abstract ``matvec`` so they run identically
over the plain CSR oracle, the Pallas CSR-k operator, or the sharded
``prepare(A, mesh=...)`` operator (docs/distributed.md); that
interchangeability is itself a test of the format's "no conversion needed"
claim.  Block variants (``block_cg``, ``block_power_iteration``) issue one
*batched* matvec per iteration, so they ride the [n, B] SpMM fast path on
every backend, single-device or sharded.

Telemetry: every solver carries a per-iteration residual-norm history in its
loop state (always — the recurrence is identical whether telemetry is on or
off, so enabling observation can never change a solution bit).  When the
solve runs *eagerly*, the history and iteration count are concrete on exit
and are recorded into the :mod:`repro.obs` registry as a
``solvers.<name>.residual`` series plus iteration/time metrics; under an
outer ``jit`` they are tracers and the tracer-safe registry skips them.
"""
from __future__ import annotations

import time
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.obs import annotated, concrete, get_registry

MatVec = Callable[[jax.Array], jax.Array]


def _record_solve(name: str, iters, residuals, t_start: float) -> None:
    """Record one finished solve (no-op when disabled or inside a trace).

    ``iters`` / ``residuals`` are outputs of the solver's ``while_loop``: if
    ``iters`` is concrete the solve ran eagerly and the history is real data;
    if it is a tracer the whole record is skipped (nothing partial).  The
    clock is read once ``concrete`` has waited for ``iters``, so
    ``<name>.time_s`` is the solve from ``t_start`` to its result, not the
    time to enqueue it.
    """
    reg = get_registry()
    if not reg.enabled:
        return
    k = concrete(iters)
    if k is None:
        return
    seconds = time.perf_counter() - t_start
    import numpy as np

    reg.counter("solvers", f"{name}.solves")
    reg.observe("solvers", f"{name}.iters", k, unit="count")
    reg.observe("solvers", f"{name}.time_s", seconds, unit="s")
    hist = np.asarray(residuals)[: int(k)]
    reg.series("solvers", f"{name}.residual", hist.tolist())


class CGResult(NamedTuple):
    x: jax.Array
    iters: jax.Array
    residual: jax.Array


@annotated("repro.cg")
def cg(
    matvec: MatVec,
    b: jax.Array,
    x0: jax.Array | None = None,
    *,
    tol: float = 1e-6,
    maxiter: int = 500,
    precond: MatVec | None = None,
) -> CGResult:
    """Conjugate gradients for SPD A (paper Sec. 1: the SpMV consumer).

    Args:
      matvec: y = A x for x of shape [n] (any prepared/sharded operator or
        oracle closure works).
      b: right-hand side, shape [n].
      x0: optional initial guess, shape [n] (defaults to zeros).
      tol: relative residual tolerance (on ‖r‖ / ‖b‖).
      maxiter: iteration cap.
      precond: optional z = M⁻¹ r for an SPD M (e.g.
        :meth:`repro.core.multigrid.Hierarchy.vcycle`); given, the loop is
        preconditioned CG, one ``precond`` per iteration and one before it.
        None runs plain CG, the same operations as before ``precond``
        existed, so its iterates are unchanged bit for bit.

    Returns:
      :class:`CGResult` with the solution ``x`` [n], iteration count and the
      final residual norm (of r, not of M⁻¹ r).
    """
    t_start = time.perf_counter()
    x0 = jnp.zeros_like(b) if x0 is None else x0
    r0 = b - matvec(x0)
    rs0 = jnp.vdot(r0, r0)
    # PCG carries ⟨r, z⟩ after k and hist; plain CG has z = r and ⟨r, z⟩ = rs
    if precond is None:
        p0, extra = r0, ()
    else:
        p0 = precond(r0)
        extra = (jnp.vdot(r0, p0),)
    tol2 = jnp.asarray(tol, b.dtype) ** 2 * jnp.maximum(jnp.vdot(b, b), 1e-30)
    hist0 = jnp.zeros((maxiter,), jnp.float32)

    def cond(state):
        _, _, _, rs, k, _, *_ = state
        return jnp.logical_and(rs > tol2, k < maxiter)

    def body(state):
        x, r, p, rs, k, hist, *rz = state
        rz = rz[0] if rz else rs
        Ap = matvec(p)
        alpha = rz / jnp.maximum(jnp.vdot(p, Ap), 1e-30)
        x = x + alpha * p
        r = r - alpha * Ap
        rs_new = jnp.vdot(r, r)
        if precond is None:
            z, rz_new, extra = r, rs_new, ()
        else:
            z = precond(r)
            rz_new = jnp.vdot(r, z)
            extra = (rz_new,)
        p = z + (rz_new / jnp.maximum(rz, 1e-30)) * p
        hist = hist.at[k].set(jnp.sqrt(rs_new).astype(jnp.float32))
        return (x, r, p, rs_new, k + 1, hist, *extra)

    x, r, _, rs, k, hist, *_ = jax.lax.while_loop(
        cond, body, (x0, r0, p0, rs0, 0, hist0, *extra)
    )
    _record_solve("cg", k, hist, t_start)
    return CGResult(x=x, iters=k, residual=jnp.sqrt(rs))


class BlockCGResult(NamedTuple):
    X: jax.Array         # [n, B] solution block
    iters: jax.Array     # scalar — iterations until every column converged
    residual: jax.Array  # [B] per-column residual norms


def block_cg(
    matvec: MatVec,
    B: jax.Array,
    X0: jax.Array | None = None,
    *,
    tol: float = 1e-6,
    maxiter: int = 500,
) -> BlockCGResult:
    """Conjugate gradients for SPD A with multiple right-hand sides.

    Solves A X = B with one *batched* matvec per iteration: each column runs
    its own CG recurrence (per-column α/β keep the method exactly CG, so
    converged columns simply freeze), but all columns share a single SpMM
    A·P per step — the matrix is streamed once per iteration instead of once
    per column, which is the whole point of the multi-vector fast path.

    Args:
      matvec: Y = A X for X of shape [n, nrhs] (batched-capable operator).
      B: right-hand-side block, shape [n, nrhs] (raises otherwise).
      X0: optional initial guess, shape [n, nrhs] (defaults to zeros).
      tol: per-column relative residual tolerance.
      maxiter: iteration cap (counts until *every* column converged).

    Returns:
      :class:`BlockCGResult` with the solution block ``X`` [n, nrhs], the
      shared iteration count and per-column residual norms [nrhs].
    """
    if B.ndim != 2:
        raise ValueError(f"block_cg expects B of shape [n, nrhs], got {B.shape}")
    t_start = time.perf_counter()
    X0 = jnp.zeros_like(B) if X0 is None else X0
    R0 = B - matvec(X0)
    P0 = R0
    rs0 = jnp.sum(R0 * R0, axis=0)                               # [nrhs]
    tol2 = jnp.asarray(tol, B.dtype) ** 2 * jnp.maximum(
        jnp.sum(B * B, axis=0), 1e-30
    )
    hist0 = jnp.zeros((maxiter,), jnp.float32)     # worst column per iter

    def cond(state):
        _, _, _, rs, k, _ = state
        return jnp.logical_and(jnp.any(rs > tol2), k < maxiter)

    def body(state):
        X, R, P, rs, k, hist = state
        AP = matvec(P)                                           # one SpMM
        active = (rs > tol2).astype(B.dtype)                     # freeze done cols
        alpha = active * rs / jnp.maximum(jnp.sum(P * AP, axis=0), 1e-30)
        X = X + alpha[None, :] * P
        R = R - alpha[None, :] * AP
        rs_new = jnp.sum(R * R, axis=0)
        beta = rs_new / jnp.maximum(rs, 1e-30)
        P = jnp.where(active[None, :] > 0, R + beta[None, :] * P, P)
        rs_new = jnp.where(active > 0, rs_new, rs)
        hist = hist.at[k].set(jnp.sqrt(jnp.max(rs_new)).astype(jnp.float32))
        return (X, R, P, rs_new, k + 1, hist)

    X, R, _, rs, k, hist = jax.lax.while_loop(
        cond, body, (X0, R0, P0, rs0, 0, hist0)
    )
    _record_solve("block_cg", k, hist, t_start)
    return BlockCGResult(X=X, iters=k, residual=jnp.sqrt(rs))


def power_iteration(
    matvec: MatVec, n: int, *, iters: int = 50, seed: int = 0
) -> jax.Array:
    """Dominant eigenvalue estimate — a second SpMV-bound consumer."""
    v = jax.random.normal(jax.random.PRNGKey(seed), (n,))
    v = v / jnp.linalg.norm(v)

    def body(_, v):
        w = matvec(v)
        return w / jnp.maximum(jnp.linalg.norm(w), 1e-30)

    v = jax.lax.fori_loop(0, iters, body, v)
    return jnp.vdot(v, matvec(v))


def block_power_iteration(
    matvec: MatVec, n: int, k: int, *, iters: int = 50, seed: int = 0
) -> jax.Array:
    """Top-k eigenvalue estimates via subspace (orthogonal) iteration.

    One batched matvec (SpMM over a [n, k] block) per sweep followed by a QR
    re-orthonormalisation.  Generalises :func:`power_iteration` (k = 1)
    while streaming the matrix once per sweep for the whole subspace.

    Args:
      matvec: Y = A X for X of shape [n, k] (batched-capable operator).
      n: problem size (rows of A).
      k: subspace dimension — how many leading eigenvalues to estimate.
      iters: number of sweeps.
      seed: PRNG seed for the random initial subspace.

    Returns:
      [k] Rayleigh-quotient eigenvalue estimates, descending.
    """
    t_start = time.perf_counter()
    V = jax.random.normal(jax.random.PRNGKey(seed), (n, k))
    V, _ = jnp.linalg.qr(V)

    def body(_, V):
        W = matvec(V)                                            # one SpMM
        Q, _ = jnp.linalg.qr(W)
        return Q

    V = jax.lax.fori_loop(0, iters, body, V)
    H = V.T @ matvec(V)                                          # [k, k] Rayleigh
    evals = jnp.linalg.eigvalsh((H + H.T) / 2)[::-1]
    reg = get_registry()
    if reg.enabled and concrete(evals[0]) is not None:
        reg.counter("solvers", "block_power_iteration.solves")
        reg.observe("solvers", "block_power_iteration.iters", iters,
                    unit="count")
        reg.observe("solvers", "block_power_iteration.time_s",
                    time.perf_counter() - t_start, unit="s")
    return evals


def jacobi_smoother(
    matvec: MatVec, diag: jax.Array, b: jax.Array, x0: jax.Array | None = None, *,
    iters: int = 10, omega: float = 0.67
) -> jax.Array:
    """Weighted-Jacobi relaxation of A x = b from ``x0`` (zeros when None):
    ``iters`` sweeps x ← x + ω D⁻¹ (b − A x), one SpMV each — the smoother of
    :mod:`repro.core.multigrid`."""
    x = jnp.zeros_like(b) if x0 is None else x0

    def body(_, x):
        return x + omega * (b - matvec(x)) / diag

    return jax.lax.fori_loop(0, iters, body, x)
