"""The sharded operator equals the single-device one bit for bit.

Every combination of shard count {1, 2, 4}, ``x_strategy`` {replicated,
allgather, halo}, right-hand-side width B {1, 3} and backend {csrk, sellcs}
is one case: ``prepare(A, mesh=)`` applied to x must give exactly the bits of
``prepare(A)`` on one device, with the requested strategy in force (the
matrices are banded enough that no shard demotes halo to allgather).  The
overlapped and blocking halo schedules are compared in
``tests/test_shard_plan.py``.

All combinations are computed in two subprocesses with four forced host
devices (the parent process keeps seeing one device), one per backend and
run side by side, so the file pays for one JAX start-up's wall time; each
test reads its combination's verdict.
"""
import itertools
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SHARDS = (1, 2, 4)
STRATEGIES = ("replicated", "allgather", "halo")
WIDTHS = (1, 3)
BACKENDS = ("csrk", "sellcs")

SCRIPT = """
import itertools, json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.configs.spmv_suite import grid_laplacian_2d
from repro.core.spmv import prepare
from repro.sparse import csr_from_coo
from repro.sparse.coo import COOMatrix

def banded_irregular(n, band=48, seed=7):
    # nnz/row variance >> 10 (routes to SELL-C-sigma) yet banded, so the
    # halo strategy stays legal
    rng = np.random.default_rng(seed)
    rows, cols = [], []
    for i in range(n):
        lo, hi = max(0, i - band), min(n, i + band)
        deg = min(int(rng.integers(1, 24)), hi - lo)
        rows += [i] * deg
        cols += list(rng.choice(np.arange(lo, hi), size=deg, replace=False))
    r, c = np.array(rows), np.array(cols)
    return csr_from_coo(COOMatrix(
        jnp.asarray(r, jnp.int32), jnp.asarray(c, jnp.int32),
        jnp.asarray(rng.standard_normal(len(r)), jnp.float32), (n, n)))

matrices = {"csrk": lambda: grid_laplacian_2d(32, 32),
            "sellcs": lambda: banded_irregular(768)}
rng = np.random.default_rng(0)
out = {}
for backend in BACKENDS:
    A = matrices[backend]()
    single = prepare(A, format=backend)
    assert single.backend == backend, single.backend
    xs = {1: jnp.asarray(rng.standard_normal(A.n), jnp.float32),
          3: jnp.asarray(rng.standard_normal((A.n, 3)), jnp.float32)}
    want = {B: single(x) for B, x in xs.items()}
    for D, strategy in itertools.product(SHARDS, STRATEGIES):
        mesh = Mesh(np.asarray(jax.devices()[:D]).reshape(D, 1), ("data", "model"))
        op = prepare(A, format=backend, mesh=mesh, x_strategy=strategy)
        for B, x in xs.items():
            out[f"{backend}.D{D}.{strategy}.B{B}"] = {
                "resolved": op.x_strategy,
                "shard_backends": sorted(set(op.shard_backends)),
                "exact": bool(jnp.array_equal(op(x), want[B])),
            }
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def verdicts():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    procs = []
    for backend in BACKENDS:
        script = (
            "import os\n"
            "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'\n"
            f"SHARDS, STRATEGIES, BACKENDS = {SHARDS!r}, {STRATEGIES!r}, {(backend,)!r}\n"
            + SCRIPT
        )
        procs.append(subprocess.Popen(
            [sys.executable, "-c", script], stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, env=env,
        ))
    out = {}
    for proc in procs:
        stdout, stderr = proc.communicate(timeout=560)
        assert proc.returncode == 0, f"stdout:\n{stdout}\nstderr:\n{stderr}"
        line = [l for l in stdout.splitlines() if l.startswith("RESULT ")][-1]
        out.update(json.loads(line[len("RESULT "):]))
    return out


@pytest.mark.parametrize(
    "backend,shards,strategy,B",
    list(itertools.product(BACKENDS, SHARDS, STRATEGIES, WIDTHS)),
)
def test_sharded_equals_single_device(verdicts, backend, shards, strategy, B):
    v = verdicts[f"{backend}.D{shards}.{strategy}.B{B}"]
    assert v["resolved"] == strategy, v
    assert v["shard_backends"] == [backend], v
    assert v["exact"], v
