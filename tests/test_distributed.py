"""Multi-device behaviour via subprocesses (the parent process must keep
seeing exactly 1 device, so each test spawns a fresh interpreter with
--xla_force_host_platform_device_count=8)."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_script(body: str, devices: int = 8, timeout: int = 560) -> str:
    script = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={devices}'\n"
        + body
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_dist_spmv_allgather_and_halo():
    out = run_script("""
import numpy as np, jax, jax.numpy as jnp
from repro.core.distributed import shard_csr, dist_spmv_allgather, dist_spmv_halo
from repro.configs.spmv_suite import grid_laplacian_2d
from repro.core.ordering import bandk
from repro.launch.mesh import make_host_mesh

A = grid_laplacian_2d(32, 32)
A = A.symmetric_permute(bandk(A))
mesh = make_host_mesh()
S = shard_csr(A, mesh.shape['data'])
x = jnp.asarray(np.random.default_rng(0).standard_normal(A.m), jnp.float32)
y_ref = np.asarray(A.todense()) @ np.asarray(x)
y1 = dist_spmv_allgather(S, x, mesh)
y2 = dist_spmv_halo(S, x, mesh)
print('ag_err', float(jnp.abs(y1 - y_ref).max()))
print('halo_err', float(jnp.abs(y2 - y_ref).max()))
print('halo', S.halo, 'rows_per_shard', S.rows_per_shard)
""")
    for line in out.splitlines():
        if line.startswith(("ag_err", "halo_err")):
            assert float(line.split()[1]) < 1e-3, out


def test_dist_cg_on_mesh():
    out = run_script("""
import numpy as np, jax, jax.numpy as jnp
from repro.core.distributed import shard_csr, dist_spmv_halo
from repro.core.solvers import cg
from repro.configs.spmv_suite import grid_laplacian_2d
from repro.core.ordering import bandk
from repro.launch.mesh import make_host_mesh

A = grid_laplacian_2d(24, 24)
A = A.symmetric_permute(bandk(A))
mesh = make_host_mesh()
S = shard_csr(A, mesh.shape['data'])
rng = np.random.default_rng(0)
x_true = rng.standard_normal(A.m).astype(np.float32)
b = jnp.asarray(np.asarray(A.todense()) @ x_true)
res = cg(lambda v: dist_spmv_halo(S, v, mesh), b, maxiter=2000)
err = float(jnp.abs(res.x - x_true).max())
print('cg_err', err, 'iters', int(res.iters))
""")
    err = [l for l in out.splitlines() if l.startswith("cg_err")][0]
    assert float(err.split()[1]) < 5e-2, out
