"""ShardPlan: interior/boundary classification, halo edge schedule, and the
staged (overlapped) executor's bit-for-bit contract.

Host-side pieces — per-tile column reach, :func:`classify_tile_reach`, the
edge builder and the plan's byte model — are pinned on hand-built inputs with
no mesh at all.  Executor behaviour (overlap vs blocking vs single-device,
degenerate plans) runs on a 4-device host mesh via subprocesses, same pattern
as test_sharded_prepare.py.
"""
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PRELUDE = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core.spmv import prepare
from repro.configs.spmv_suite import grid_laplacian_2d
from repro.sparse import csr_from_coo
from repro.sparse.coo import COOMatrix

def scattered_irregular(n, seed=3):
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


# ---------------------------------------------------------------------------
# host-side: classification, reach, edges, byte model (no mesh, no jit)
# ---------------------------------------------------------------------------


def test_classify_tile_reach_hand_pinned():
    """Banded layout, hand-pinned: 2 shards × 3 tiles, rows_per_shard=300.

    Shard 0 owns x[0, 300): tile 0 [0, 90] interior, tile 1 [80, 250]
    interior, tile 2 [190, 310] reaches right -> boundary.  Shard 1 owns
    x[300, 600): tile 3 [290, 420] reaches left -> boundary, tile 4
    [350, 560] interior, tile 5 empty (padding) -> inert interior.
    """
    from repro.sparse import classify_tile_reach

    lo = np.array([0, 80, 190, 290, 350, 2**31 - 1])
    hi = np.array([90, 250, 310, 420, 560, -1])
    interior, boundary, frac = classify_tile_reach(
        lo, hi, tiles_per_shard=3, rows_per_shard=300, num_shards=2
    )
    assert [list(i) for i in interior] == [[0, 1], [1, 2]]
    assert [list(b) for b in boundary] == [[2], [0]]
    # 5 real tiles, 3 interior (the empty tile is excluded from the fraction)
    assert frac == 3 / 5

    # all-interior and all-boundary degenerate fractions
    _, _, f1 = classify_tile_reach(
        np.array([0, 310]), np.array([100, 640]),
        tiles_per_shard=1, rows_per_shard=300, num_shards=2)
    assert f1 == 0.5
    _, _, f_empty = classify_tile_reach(
        np.array([2**31 - 1]), np.array([-1]),
        tiles_per_shard=1, rows_per_shard=300, num_shards=1)
    assert f_empty == 1.0


def test_col_reach_csrk_and_sellcs():
    """col_reach reports real (val != 0) column extents per kernel tile."""
    import jax.numpy as jnp

    from repro.configs.spmv_suite import grid_laplacian_2d
    from repro.core.spmv import prepare

    A = grid_laplacian_2d(24, 24)
    op = prepare(A, format="csrk", tile_layout="monolithic")
    lo, hi = op.tiles.col_reach()
    assert lo.shape == (op.tiles.num_tiles,) and hi.shape == lo.shape
    R = op.tiles.rows_per_tile
    rp = np.asarray(op.csrk.csr.row_ptr)
    ci = np.asarray(op.csrk.csr.col_idx)
    m = op.csrk.shape[0]
    for t in range(op.tiles.num_tiles):
        r0, r1 = t * R, min((t + 1) * R, m)
        cols = ci[rp[r0]:rp[r1]]
        if len(cols):
            assert lo[t] == cols.min() and hi[t] == cols.max(), t
        else:
            assert hi[t] < lo[t], t
    # the banded structure bounds every tile's reach by the bandwidth
    from repro.sparse.stats import compute_stats

    bw = compute_stats(op.csrk.csr).bandwidth
    t_rows = np.arange(op.tiles.num_tiles) * R
    real = hi >= lo
    assert (lo[real] >= np.maximum(t_rows[real] - bw, 0)).all()

    op2 = prepare(A, format="sellcs", tile_layout="monolithic")
    lo2, hi2 = op2.sell_tiles.col_reach()
    v = np.asarray(op2.sell_tiles.vals)
    c = np.asarray(op2.sell_tiles.col_idx)
    for t in range(v.shape[0]):
        cols = c[t][v[t] != 0]
        if len(cols):
            assert lo2[t] == cols.min() and hi2[t] == cols.max(), t
        else:
            assert hi2[t] < lo2[t], t


def test_halo_edges_and_byte_model():
    """Need-based schedule: only sides with reach get an edge; bytes follow."""
    from repro.core.distributed import ShardPlan, _halo_edges, _required_halo

    # block-diagonal reach: nobody needs anything
    reach = [(0, 299), (300, 599), (600, 899)]
    left, right = _halo_edges(reach, 300, 3)
    assert left == () and right == ()
    assert _required_halo(reach, 300, 3) == 0
    p0 = ShardPlan("halo", 3, 300, halo=128)
    assert p0.collective_bytes() == 0

    # middle shard reaches both ways; edge shards reach inward only
    reach = [(0, 310), (290, 610), (590, 899)]
    left, right = _halo_edges(reach, 300, 3)
    assert left == ((0, 1), (1, 2)) and right == ((1, 0), (2, 1))
    assert _required_halo(reach, 300, 3) == 11
    plan = ShardPlan("halo", 3, 300, halo=128,
                     left_edges=left, right_edges=right)
    assert plan.collective_bytes() == 128 * 4 * 4          # 4 edges, f32
    assert plan.collective_bytes(B=8) == 8 * plan.collective_bytes()
    assert not plan.is_degenerate

    # empty shards schedule nothing; degenerate plans have no edges
    left, right = _halo_edges([None, (250, 640), None], 300, 3)
    assert left == ((0, 1),) and right == ((2, 1),)
    ag = ShardPlan("allgather", 4, 256)
    assert ag.is_degenerate
    assert ag.collective_bytes() == 3 * 256 * 4 * 4
    assert ShardPlan("replicated", 4, 256).collective_bytes() == 0


def test_estimate_interior_fraction():
    """O(1) bandwidth-based prediction brackets the plan's measured value."""
    import dataclasses

    from repro.sparse.stats import MatrixStats

    from repro.core.distributed import estimate_interior_fraction

    st = MatrixStats(m=4096, n=4096, nnz=20000, rdensity=5.0, row_var=0.1,
                     row_max=5, bandwidth=65)
    assert estimate_interior_fraction(st, 1, 4096) == 1.0
    f = estimate_interior_fraction(st, 4, 1024)        # 1 - 2*128/1024
    assert abs(f - 0.75) < 1e-9
    wide = dataclasses.replace(st, bandwidth=4000)
    assert estimate_interior_fraction(wide, 4, 1024) == 0.0


def test_combine_tile_rows_scatter():
    """Subset outputs land at home rows; dump-slot ids are dropped."""
    import jax.numpy as jnp

    from repro.kernels.ops import combine_tile_rows

    R, T = 4, 5
    y_a = jnp.arange(2 * R, dtype=jnp.float32) + 100      # tiles 3, 0
    y_b = jnp.arange(2 * R, dtype=jnp.float32) + 200      # tile 2, pad->dump
    out = combine_tile_rows(
        [y_a, y_b],
        [jnp.asarray([3, 0], jnp.int32), jnp.asarray([2, T], jnp.int32)],
        T, R,
    )
    assert out.shape == (T * R,)
    out = np.asarray(out)
    assert (out[3 * R:4 * R] == np.arange(R) + 100).all()
    assert (out[0:R] == np.arange(R, 2 * R) + 100).all()
    assert (out[2 * R:3 * R] == np.arange(R) + 200).all()
    assert (out[R:2 * R] == 0).all() and (out[4 * R:] == 0).all()

    # batched outputs ride the trailing dim through the same scatter
    Yb = jnp.ones((R, 3), jnp.float32)
    out2 = combine_tile_rows([Yb], [jnp.asarray([1], jnp.int32)], 3, R)
    assert out2.shape == (3 * R, 3)
    assert np.asarray(out2)[R:2 * R].sum() == R * 3


# ---------------------------------------------------------------------------
# mesh-side: plan resolution + executor bit-for-bit (4 host devices)
# ---------------------------------------------------------------------------


def test_plan_resolution_on_mesh():
    """Banded -> staged halo plan with need-based edges; scattered -> demoted
    degenerate plan; halo_overlap=False forces the blocking schedule."""
    out = run_script("""
from repro.core.distributed import OVERLAP_MIN_INTERIOR

A = grid_laplacian_2d(48, 48)
op = prepare(A, mesh=mesh)                       # auto -> halo -> overlap
plan = op.plan
assert plan.strategy == "halo" and plan.overlap
assert plan.interior_fraction >= OVERLAP_MIN_INTERIOR
assert 0.0 < plan.interior_fraction < 1.0
assert plan.num_interior > 0 and plan.num_boundary > 0
assert len(plan.interior_ids) == 4 and len(plan.boundary_ids) == 4
# every tile is scheduled exactly once
for ii, bb in zip(plan.interior_ids, plan.boundary_ids):
    both = np.concatenate([np.asarray(ii), np.asarray(bb)])
    assert len(np.unique(both)) == len(both) <= plan.tiles_per_shard
# the banded band never wraps: no (3, 0) or (0, 3) edges
assert (0, 1) not in plan.left_edges or True
assert all(dst == src + 1 for src, dst in plan.left_edges)
assert all(dst == src - 1 for src, dst in plan.right_edges)
assert plan.collective_bytes() == op.collective_bytes_per_call()

# blocking schedule: same plan geometry, overlap off, same bytes
bl = prepare(A, mesh=mesh, halo_overlap=False)
assert not bl.plan.overlap and bl.plan.strategy == "halo"
assert bl.plan.left_edges == plan.left_edges
assert bl.collective_bytes_per_call() == op.collective_bytes_per_call()

# scattered matrix: halo request demotes -> degenerate plan, no schedule
A2 = scattered_irregular(1024)
op2 = prepare(A2, mesh=mesh, x_strategy="halo", halo_overlap=True)
assert op2.plan.is_degenerate and not op2.plan.overlap
assert op2.plan.left_edges == () and op2.halo == 0
assert op2.x_strategy_requested == "halo"

# degenerate plans for the explicit strategies
for strat in ("replicated", "allgather"):
    o = prepare(A, mesh=mesh, x_strategy=strat)
    assert o.plan.is_degenerate and not o.plan.overlap, strat
print('OK')
""")
    assert "OK" in out


def test_overlap_bit_for_bit_on_mesh():
    """Overlapped, blocking, degenerate and single-device executions agree
    bit-for-bit for [n] and [n, B], on both tile backends."""
    out = run_script("""
A = grid_laplacian_2d(48, 48)
single = prepare(A, tile_layout="monolithic")
x = jnp.asarray(rng.standard_normal(A.n), jnp.float32)
X = jnp.asarray(rng.standard_normal((A.n, 5)), jnp.float32)
ov = prepare(A, mesh=mesh, x_strategy="halo", halo_overlap=True)
bl = prepare(A, mesh=mesh, x_strategy="halo", halo_overlap=False)
assert ov.overlap and not bl.overlap
for op in (ov, bl):
    assert bool(jnp.all(op(x) == single(x)))
    assert bool(jnp.all(op(X) == single(X)))
assert bool(jnp.all(ov(x) == bl(x))) and bool(jnp.all(ov(X) == bl(X)))
for strat in ("replicated", "allgather"):
    o = prepare(A, mesh=mesh, x_strategy=strat)
    assert bool(jnp.all(o(x) == single(x))), strat
    assert bool(jnp.all(o(X) == single(X))), strat
    assert "cblk" in o.shard_arrays, strat
# every tile subset carries its rows of the chunk table, and the single
# launch equals a sweep of every chunk of every window
assert {"i_cblk", "b_cblk"} <= set(ov.shard_arrays) and "cblk" in bl.shard_arrays
import dataclasses
T, nb = single.tiles.num_tiles, 2 * single.tiles.window // 128
every = np.concatenate([np.full((T, 1), nb), np.tile(np.arange(nb), (T, 1))], 1)
full = dataclasses.replace(single, tiles=dataclasses.replace(
    single.tiles, col_blocks=jnp.asarray(every, jnp.int32)))
assert bool(jnp.all(full(x) == single(x))) and bool(jnp.all(full(X) == single(X)))

# sellcs: banded but row-irregular, so the SELL-C-sigma backend gets a
# staged plan of its own (C-row chunks instead of SSR tiles)
m = 2048
rows, cols, vals = [], [], []
for i in range(m):
    deg = 1 + (i * 37) % 12 + (30 if i % 61 == 0 else 0)
    for k in range(deg):
        j = min(max(i + ((k * 53) % 129) - 64, 0), m - 1)
        rows.append(i); cols.append(j); vals.append(1.0 + 0.01 * k)
A2 = csr_from_coo(COOMatrix(
    jnp.asarray(np.array(rows), jnp.int32), jnp.asarray(np.array(cols), jnp.int32),
    jnp.asarray(np.array(vals), jnp.float32), (m, m)))
s_single = prepare(A2, format="sellcs", tile_layout="monolithic")
xs = jnp.asarray(rng.standard_normal(m), jnp.float32)
Xs = jnp.asarray(rng.standard_normal((m, 3)), jnp.float32)
s_ov = prepare(A2, format="sellcs", mesh=mesh, x_strategy="halo", halo_overlap=True)
s_bl = prepare(A2, format="sellcs", mesh=mesh, x_strategy="halo", halo_overlap=False)
assert s_ov.backend == "sellcs" and s_ov.overlap and not s_bl.overlap
for op in (s_ov, s_bl):
    assert bool(jnp.all(op(xs) == s_single(xs)))
    assert bool(jnp.all(op(Xs) == s_single(Xs)))
# dense cross-check (guards against a wrong-but-consistent set)
yd = np.asarray(A2.todense()) @ np.asarray(xs)
assert float(jnp.abs(s_ov(xs) - yd).max()) < 1e-3
print('OK')
""")
    assert "OK" in out
