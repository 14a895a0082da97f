"""Sharded PreparedSpMV: prepare(A, mesh=...) must be bit-for-bit identical
to the single-device operator, for both backends, [n] and [n, B] inputs, and
all three x strategies.

Multi-device behaviour runs via subprocesses (the parent process must keep
seeing exactly 1 device), same pattern as test_distributed.py.
"""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# shared preamble: 4 host devices, a regular and two irregular matrices
PRELUDE = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core.spmv import prepare
from repro.configs.spmv_suite import grid_laplacian_2d
from repro.sparse import csr_from_coo
from repro.sparse.coo import COOMatrix

def banded_irregular(n, band=48, seed=7):
    # nnz/row variance >> 10 (routes to SELL-C-sigma) but banded, so every
    # x strategy including halo is genuinely exercised
    rng = np.random.default_rng(seed)
    rows, cols = [], []
    for i in range(n):
        deg = int(rng.integers(1, 24))
        lo, hi = max(0, i - band), min(n, i + band)
        cs = rng.choice(np.arange(lo, hi), size=min(deg, hi - lo), replace=False)
        rows += [i] * len(cs); cols += list(cs)
    r, c = np.array(rows), np.array(cols)
    return csr_from_coo(COOMatrix(
        jnp.asarray(r, jnp.int32), jnp.asarray(c, jnp.int32),
        jnp.asarray(rng.standard_normal(len(r)), jnp.float32), (n, n)))

def scattered_irregular(n, seed=3):
    # irregular AND unbanded: columns anywhere -> halo must demote
    rng = np.random.default_rng(seed)
    rows, cols = [], []
    for i in range(n):
        deg = int(rng.integers(1, 24))
        cs = rng.choice(n, size=deg, replace=False)
        rows += [i] * deg; cols += list(cs)
    r, c = np.array(rows), np.array(cols)
    return csr_from_coo(COOMatrix(
        jnp.asarray(r, jnp.int32), jnp.asarray(c, jnp.int32),
        jnp.asarray(rng.standard_normal(len(r)), jnp.float32), (n, n)))

mesh = Mesh(np.asarray(jax.devices()).reshape(4, 1), ('data', 'model'))
rng = np.random.default_rng(0)
"""


def run_script(body: str, devices: int = 4, timeout: int = 560) -> str:
    script = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={devices}'\n"
        + PRELUDE
        + body
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_compute_shard_stats_partitions():
    """Host-side helper (no mesh needed): trailing shards whose start row
    exceeds m must yield empty stats, not crash, and an explicit
    rows_per_shard must drive the partition."""
    import numpy as np

    from repro.configs.spmv_suite import grid_laplacian_2d
    from repro.sparse import compute_shard_stats
    from repro.sparse.csr import CSRMatrix
    import jax.numpy as jnp

    A = CSRMatrix(
        jnp.asarray(np.arange(10, dtype=np.int32)),   # 9 rows, 1 nnz each
        jnp.asarray(np.arange(9, dtype=np.int32)),
        jnp.asarray(np.ones(9, np.float32)),
        (9, 9),
    )
    stats = compute_shard_stats(A, 8)                 # ceil(9/8)=2 -> d=5 empty
    assert len(stats) == 8
    assert sum(s.nnz for s in stats) == 9
    assert stats[-1].m == 0 and stats[-1].nnz == 0

    # explicit (tile-granular) rows_per_shard drives the block boundaries
    B = grid_laplacian_2d(16, 16)
    st = compute_shard_stats(B, 2, rows_per_shard=200)
    assert st[0].m == 200 and st[1].m == 56
    assert sum(s.nnz for s in st) == B.nnz


def test_sharded_matches_single_device_regular():
    """Regular matrix (CSR-k backend): bit-for-bit vs single-device prepare,
    [n] and [n, B], all three x strategies + auto."""
    out = run_script("""
A = grid_laplacian_2d(40, 40)
base = prepare(A, format="auto")
assert base.backend == "csrk", base.backend
x = jnp.asarray(rng.standard_normal(A.n), jnp.float32)
X = jnp.asarray(rng.standard_normal((A.n, 5)), jnp.float32)
y_ref, Y_ref = base(x), base(X)
for strat in ("auto", "replicated", "allgather", "halo"):
    op = prepare(A, format="auto", mesh=mesh, x_strategy=strat)
    assert op.backend == "csrk"
    assert op.num_shards == 4
    assert bool(jnp.all(op(x) == y_ref)), (strat, "vector")
    assert bool(jnp.all(op(X) == Y_ref)), (strat, "block")
    assert op(X).shape == (A.m, 5)
# apply_original matches the single-device operator's
op = prepare(A, format="auto", mesh=mesh)
assert bool(jnp.all(op.apply_original(x) == base.apply_original(x)))
assert bool(jnp.all(op.apply_original(X) == base.apply_original(X)))
# matmat guard matches PreparedSpMV's
try:
    op.matmat(x)
    raise SystemExit("matmat should reject [n]")
except ValueError:
    pass
print('OK')
""")
    assert "OK" in out


def test_sharded_apply_original_follows_the_base_perm():
    """Natural order kept (identity perm): apply_original is the sharded call
    itself, no gathers; a Band-k perm still round-trips as the base's does."""
    out = run_script("""
A = grid_laplacian_2d(40, 40)
x = jnp.asarray(rng.standard_normal(A.n), jnp.float32)
X = jnp.asarray(rng.standard_normal((A.n, 3)), jnp.float32)
op = prepare(A, format="auto", mesh=mesh)
assert op.base._identity_perm
for v in (x, X):
    assert bool(jnp.all(op.apply_original(v) == op(v)))
    assert str(jax.make_jaxpr(op.apply_original)(v)) == str(jax.make_jaxpr(op.__call__)(v))
B = A.symmetric_permute(np.random.default_rng(2).permutation(A.m))
sb, base = prepare(B, format="auto", mesh=mesh), prepare(B, format="auto")
assert not sb.base._identity_perm and np.array_equal(sb.perm, base.perm)
for v in (x, X):
    assert bool(jnp.all(sb.apply_original(v) == base.apply_original(v)))
print('OK')
""")
    assert "OK" in out


def test_sharded_matches_single_device_irregular():
    """Irregular matrix (auto-routes to SELL-C-σ): bit-for-bit vs
    single-device, [n] and [n, B], all three strategies."""
    out = run_script("""
A = banded_irregular(1024)
base = prepare(A, format="auto")
assert base.backend == "sellcs", base.backend
x = jnp.asarray(rng.standard_normal(A.n), jnp.float32)
X = jnp.asarray(rng.standard_normal((A.n, 4)), jnp.float32)
y_ref, Y_ref = base(x), base(X)
for strat in ("auto", "replicated", "allgather", "halo"):
    op = prepare(A, format="auto", mesh=mesh, x_strategy=strat)
    assert op.backend == "sellcs"
    assert all(b == "sellcs" for b in op.shard_backends), op.shard_backends
    assert bool(jnp.all(op(x) == y_ref)), (strat, "vector")
    assert bool(jnp.all(op(X) == Y_ref)), (strat, "block")
# dense cross-check (guards against a wrong-but-consistent pair)
yd = np.asarray(A.todense()) @ np.asarray(x)
assert float(jnp.abs(base(x) - yd).max()) < 1e-3
print('OK')
""")
    assert "OK" in out


def test_strategy_selector_and_introspection():
    """O(1) strategy selection, halo demotion, per-shard registry decisions,
    and the collective-bytes model."""
    out = run_script("""
from repro.core.distributed import select_x_strategy, REPLICATE_N_MAX

# banded regular matrix -> auto picks halo, O(band) < O(n) collective
A = grid_laplacian_2d(40, 40)
op = prepare(A, mesh=mesh)                 # x_strategy defaults to auto
assert op.x_strategy == "halo", op.x_strategy
assert op.halo >= 128 and op.halo <= op.rows_per_shard
assert op.collective_bytes_per_call() < \
    prepare(A, mesh=mesh, x_strategy="allgather").collective_bytes_per_call()
assert op.collective_bytes_per_call(B=8) == 8 * op.collective_bytes_per_call()

# scattered irregular matrix: halo request demotes to allgather
A2 = scattered_irregular(1024)
op2 = prepare(A2, mesh=mesh, x_strategy="halo")
assert op2.x_strategy == "allgather", op2.x_strategy
assert op2.x_strategy_requested == "halo"
assert op2.halo == 0
x = jnp.asarray(rng.standard_normal(A2.n), jnp.float32)
assert bool(jnp.all(op2(x) == prepare(A2)(x)))

# per-shard stats + registry decisions are recorded
assert len(op.shard_stats) == 4 and len(op.shard_backends) == 4
assert all(s.m > 0 for s in op.shard_stats)
assert sum(s.nnz for s in op.shard_stats) == A.nnz
assert set(op.shard_backends) == {"csrk"}
assert set(op2.shard_backends) == {"sellcs"}

# pure selector: wide band + large n -> allgather; small n -> replicated
st = op2.base.stats
assert select_x_strategy(st, 4, 256) in ("replicated", "allgather")
import dataclasses
wide = dataclasses.replace(st, n=REPLICATE_N_MAX + 1, bandwidth=st.n - 1)
assert select_x_strategy(wide, 4, 256) == "allgather"
banded = dataclasses.replace(st, bandwidth=4)
assert select_x_strategy(banded, 4, 256) == "halo"
assert select_x_strategy(st, 1, st.m) == "replicated"
print('OK')
""")
    assert "OK" in out


def test_sharded_solvers_and_cpu_fallback():
    """block_cg / cg / block_power_iteration run unchanged against a sharded
    operator; the CSR-2 (CPU-device) oracle path matches single-device too."""
    out = run_script("""
from repro.core.solvers import cg, block_cg, block_power_iteration

A = grid_laplacian_2d(32, 32)

# CSR-2 / cpu-device fallback (no tile view): oracle inside shard_map
base = prepare(A, device="cpu")
assert base.tiles is None
x = jnp.asarray(rng.standard_normal(A.n), jnp.float32)
X = jnp.asarray(rng.standard_normal((A.n, 3)), jnp.float32)
for strat in ("replicated", "allgather", "halo"):
    o = prepare(A, device="cpu", mesh=mesh, x_strategy=strat)
    assert bool(jnp.all(o(x) == base(x))), strat
    assert bool(jnp.all(o(X) == base(X))), strat

# solvers consume the sharded operator through the same MatVec interface
op = prepare(A, mesh=mesh)
Xt = rng.standard_normal((A.m, 4)).astype(np.float32)
Bmat = jnp.asarray(np.asarray(A.todense()) @ Xt)
res = block_cg(op.apply_original, Bmat, maxiter=2000)
assert float(jnp.abs(res.X - Xt).max()) < 5e-2, float(jnp.abs(res.X - Xt).max())
r = cg(op.apply_original, Bmat[:, 0], maxiter=2000)
assert float(jnp.abs(r.x - Xt[:, 0]).max()) < 5e-2
lams = block_power_iteration(op.apply_original, A.n, 2, iters=60)
w = np.sort(np.linalg.eigvalsh(np.asarray(A.todense())))[::-1][:2]
assert abs(float(lams[0]) - w[0]) < 0.2, (np.asarray(lams), w)
print('OK')
""")
    assert "OK" in out
