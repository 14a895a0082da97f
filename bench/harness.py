"""One benchmark run of one cell, from ``BENCHMARK.json`` to the result line.

:func:`run_cell` does everything after the look for a chip: build the
matrix and the operator, warm up, measure the window (traced or not), check
the window's answers against the reference, and compute the metrics.  Tests
call it on the CPU at a tiny size; :func:`main` adds the chip check, the
device record and the output.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Optional

from bench import drivers, matrices, trace_reduce
from bench.reference import Reference

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_DIR = os.path.join(BENCH, ".traces")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def plan(workload: str, root: str = ROOT) -> dict:
    """The cell, its configuration, traffic and metric definitions, by name."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json (have {sorted(cells)})")
    cell = cells[workload]
    config = next(c for c in spec["configs"] if c["name"] == cell["config"])

    def mine(defs):
        return [m for m in defs if workload in m.get("workloads", [workload])]

    return {
        "cell": cell,
        "config": load_json(os.path.join(root, config["file"])),
        "traffic": load_json(os.path.join(BENCH, "traffic", f"{cell['traffic']}.json")),
        "end_to_end": mine(spec["end_to_end"]),
        "per_layer": mine(spec["per_layer"]),
    }


def load_reader(name: str):
    """The ``read(run)`` function of the per-layer metric ``name``."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no reader for per-layer metric {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


class CompileCount:
    """Lowerings and backend compiles JAX reports while the context is open."""

    EVENTS = {"/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        self.counts = {"lowerings": 0, "compiles": 0}

    def _listen(self, event, duration, **kwargs):
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listen)
        return False


def geometry(op) -> dict:
    """Tile geometry of a prepared operator, as far as it has one."""
    geo = {"backend": getattr(op, "backend", None)}
    tb = getattr(op, "tile_buckets", None)
    try:
        chunk = op.params.gather_chunk
        geo["gather_chunk_param"] = chunk
        if tb is not None:
            from repro.kernels.gather import pick_chunk

            geo.update(window=tb.window, rows_per_tile=tb.rows_per_tile,
                       num_tiles=tb.num_tiles, bucket_slots=list(tb.bucket_slots()),
                       bucket_tiles=[int(b.vals.shape[0]) for b in tb.buckets],
                       remainder_nnz=tb.remainder_nnz,
                       gather_chunk=pick_chunk(tb.window, chunk))
    except (AttributeError, ImportError, TypeError) as e:
        geo["incomplete"] = repr(e)
    return geo


def prepare_operator(mat, config: dict):
    """``repro.core.spmv.prepare`` on the matrix; returns (op, obs records)."""
    import jax.numpy as jnp

    from repro.core.formats import CSRMatrix
    from repro.core.spmv import prepare
    from repro.obs import MetricsRegistry, using_registry

    reg = MetricsRegistry()
    with using_registry(reg):
        A = CSRMatrix(jnp.asarray(mat.indptr), jnp.asarray(mat.indices),
                      jnp.asarray(mat.data), tuple(mat.shape))
        op = prepare(A, **config["prepare"])
    obs = {f"{r['section']}/{r['name']}": r["value"] for r in reg.records()}
    return op, obs


def log(**rec) -> None:
    print(json.dumps(rec, default=float), flush=True)


class Run:
    """What a per-layer metric reader sees of one run.

    ``host``: the driver's host-clock figures (``calls`` or ``solves``,
    ``iterations`` of CG, ``dispatch_ms``, ``cg_iters``, ...);
    ``obs``: the library's ``repro.obs`` records from ``prepare``, keyed
    ``section/name``; ``trace``: the :class:`~bench.trace_reduce.TraceSummary`
    of a traced window, else None; ``mat``: the benchmark's own matrix;
    ``peaks``: the device's row of ``peaks.json``, else None.
    """

    def __init__(self, host, obs, trace, mat, peaks):
        self.host, self.obs, self.trace, self.mat, self.peaks = host, obs, trace, mat, peaks


def run_cell(p: dict, seed: int, seconds: float, trace: bool, t0: float,
             peaks: Optional[dict] = None) -> dict:
    """Build, warm, measure, check and reduce one run; returns the result line."""
    import jax

    mat = matrices.generate(p["config"]["matrix"], seed)
    op, obs = prepare_operator(mat, p["config"])
    log(geometry=geometry(op), rows=mat.shape[0], nnz=mat.nnz)
    driver = drivers.make_driver(op, mat, p["traffic"], seed)
    del op
    driver.warm()
    setup_s = time.perf_counter() - t0

    span = (lambda name: jax.profiler.TraceAnnotation(name)) if trace else None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    with CompileCount() as cc:
        host = driver.window(seconds, span)
    if trace:
        jax.profiler.stop_trace()
    log(compiles_in_window=cc.counts, window=host)
    device = {"memory_peak_bytes": max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices())}

    answers = driver.answers()          # host copies; the device state is dropped
    checks, failed = driver.checks(Reference(mat), answers)
    correct = failed == 0 and all(v <= lim for v, lim in checks.values())

    summary = None
    if trace:
        path = trace_reduce.find_xplane(TRACE_DIR)
        summary = trace_reduce.reduce(trace_reduce.load(path)) if path else None
    result = {"correct": bool(correct), "attempted": host["attempted"], "failed": failed}
    if not trace:
        values = dict(host, setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                             for m in p["end_to_end"]}
    else:
        run = Run(host, obs, summary, mat, peaks)
        metrics = {}
        for m in p["per_layer"]:
            value = load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        if summary is not None:
            device.update(busy_s=summary.busy_s, window_s=summary.window_s)
            result["breakdown"] = {"device_ops": summary.top_ops(10),
                                   "idle_gaps": summary.top_gaps(10)}
    result["device"] = device
    # a NaN or inf reading is written as text, so the line stays strict JSON
    result["checks"] = {k: {"value": v if math.isfinite(v) else str(v), "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(args, t0: float) -> int:
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    p = plan(args.workload)
    devices = jax.devices()
    chips = int(p["cell"]["chips"])
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in table:
        print(f"bench: no peaks for device kind {kind!r} in peaks.json", file=sys.stderr)
        return 2
    result = run_cell(p, args.seed, args.seconds, bool(args.trace), t0, peaks=table[kind])
    result["device"] = {"platform": devices[0].platform, "kind": kind,
                        "count": len(devices), **result["device"]}
    result["checks"] = result.pop("checks")    # the compared numbers come last
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
