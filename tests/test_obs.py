"""Telemetry subsystem tests (ISSUE 6 acceptance).

Covers, in order: registry counter/gauge/timer/series semantics; the
disabled registry being a true no-op; tracer safety under ``jit`` (nothing
abstract is ever stored); ``prepare()`` phase timings and structural gauges
on both the csrk and sellcs routes; the sharded operator's decision
counters; solver residual series; the profiler spans on the trace's clock;
and the contract that underwrites all of it — enabling telemetry changes
no computed bit.
"""
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.obs import MetricsRegistry, using_registry
from repro.configs.spmv_suite import grid_laplacian_2d
from repro.core.solvers import block_cg, cg
from repro.core.spmv import prepare
from repro.sparse import CSRMatrix


def powerlaw_csr(rng, m=128, scale=4.0):
    lengths = np.minimum((rng.pareto(1.0, m) * scale + 1).astype(int), m)
    dense = np.zeros((m, m), np.float32)
    for i, L in enumerate(lengths):
        dense[i, rng.choice(m, size=L, replace=False)] = rng.standard_normal(L)
    return CSRMatrix.fromdense(dense)


# --- registry semantics ------------------------------------------------------


def test_counter_accumulates_and_gauge_overwrites():
    reg = MetricsRegistry()
    reg.counter("s", "c")
    reg.counter("s", "c", 2)
    assert reg.get("s", "c") == 3.0
    reg.gauge("s", "g", 1.5)
    reg.gauge("s", "g", 2.5)
    assert reg.get("s", "g") == 2.5
    recs = reg.records()
    assert all(set(r) == {"section", "name", "value", "unit"} for r in recs)
    assert all(isinstance(r["value"], float) for r in recs)


def test_timer_aggregates_without_per_call_storage():
    reg = MetricsRegistry()
    for _ in range(3):
        with reg.timer("s", "t"):
            time.sleep(0.002)
    by_name = {r["name"]: r for r in reg.records()}
    assert by_name["t_calls"]["value"] == 3.0
    assert by_name["t_ms"]["value"] >= 3 * 2.0 * 0.5  # total, generous floor
    assert by_name["t_ms"]["unit"] == "ms"


def test_series_capped_with_drop_counter():
    reg = MetricsRegistry()
    reg.series("s", "r", list(range(obs.SERIES_CAP + 5)))
    assert len(reg.get_series("s", "r")) == obs.SERIES_CAP
    by_name = {r["name"]: r["value"] for r in reg.records()}
    assert by_name["r.dropped"] == 5.0


def test_disabled_registry_is_noop():
    reg = MetricsRegistry(enabled=False)
    reg.counter("s", "c")
    reg.gauge("s", "g", 1.0)
    reg.observe("s", "o", 1.0)
    with reg.timer("s", "t"):
        pass
    assert reg.records() == []
    # disabled timers hand out one shared null context: provably zero-alloc
    assert reg.timer("a", "b") is reg.timer("c", "d")


def test_annotate_noop_when_disabled_and_transparent_when_enabled():
    with using_registry(MetricsRegistry(enabled=False)):
        ctx = obs.annotate("x")
        assert ctx is obs.annotate("y")          # shared null context
    with using_registry(MetricsRegistry()):
        with obs.annotate("region"):
            v = jnp.sum(jnp.arange(4.0))
        assert float(v) == 6.0


# --- tracer safety -----------------------------------------------------------


def test_no_tracer_leaks_under_jit():
    with using_registry(MetricsRegistry()) as reg:

        @jax.jit
        def f(x):
            s = jnp.sum(x)
            reg.gauge("s", "traced", s)          # tracer: must be skipped
            reg.observe("s", "traced_series", s)  # tracer: must be skipped
            reg.counter("s", "trace_events")     # python int: fine
            return s * 2

        out = f(jnp.ones(8))
        assert float(out) == 16.0
        assert reg.get("s", "traced") is None
        assert reg.get_series("s", "traced_series") == []
        assert reg.get("s", "trace_events") == 1.0
        for r in reg.records():
            assert isinstance(r["value"], float)


def test_solver_skips_recording_under_jit():
    A = grid_laplacian_2d(8, 8)
    op = prepare(A, format="csrk", device="cpu")
    with using_registry(MetricsRegistry()) as reg:
        f = jax.jit(lambda b: cg(op, b, maxiter=5).x)
        f(jnp.ones((A.n,), jnp.float32))
        assert reg.get_series("solvers", "cg.residual") == []
        assert reg.get("solvers", "cg.solves") is None


# --- prepare() instrumentation ----------------------------------------------


@pytest.mark.parametrize("build,want_backend", [
    (lambda rng: grid_laplacian_2d(16, 16), "csrk"),
    (lambda rng: powerlaw_csr(rng, m=128), "sellcs"),
])
def test_prepare_phase_timings_both_routes(rng, build, want_backend):
    A = build(rng)
    with using_registry(MetricsRegistry()) as reg:
        op = prepare(A, device="tpu_v5e", format="auto")
        assert op.backend == want_backend
        names = {r["name"] for r in reg.records() if r["section"] == "prepare"}
        for phase in ("phase.stats", "phase.tile_build", "phase.device_upload"):
            assert f"{phase}_ms" in names, (want_backend, phase, names)
            assert f"{phase}_calls" in names
        if want_backend == "csrk":
            assert "phase.reorder_ms" in names
            assert "phase.tune_ms" in names
        assert reg.get("prepare", f"backend.{want_backend}") == 1.0
        assert reg.get("prepare", "tile_count") > 0


def test_prepare_overhead_gauges_match_operator_properties(rng):
    A = grid_laplacian_2d(16, 16)
    with using_registry(MetricsRegistry()) as reg:
        op = prepare(A, device="tpu_v5e", format="auto")
        assert reg.get("prepare", "padding_overhead") == pytest.approx(
            op.padding_overhead()
        )
        assert reg.get("prepare", "overhead_fraction") == pytest.approx(
            op.overhead_fraction()
        )
        units = {r["name"]: r["unit"] for r in reg.records()}
        assert units["padding_overhead"] == "fraction"
        assert units["overhead_fraction"] == "fraction"


def test_sharded_prepare_records_decision_metrics():
    from jax.sharding import Mesh

    A = grid_laplacian_2d(16, 16)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    with using_registry(MetricsRegistry()) as reg:
        op = prepare(A, mesh=mesh, x_strategy="auto")
        assert reg.get("distributed", "num_shards") == 1.0
        assert reg.get("distributed", "halo_rows") == float(op.halo)
        assert reg.get("distributed", f"x_strategy.{op.x_strategy}") == 1.0
        total_shard_decisions = sum(
            r["value"] for r in reg.records()
            if r["section"] == "distributed"
            and r["name"].startswith("shard_backend.")
        )
        assert total_shard_decisions == 1.0


# --- solver series -----------------------------------------------------------


def _spd_op(n=64):
    A = grid_laplacian_2d(8, 8)
    return A, prepare(A, format="csrk", device="cpu")


def test_cg_emits_residual_series_eagerly(rng):
    A, op = _spd_op()
    b = jnp.asarray(rng.standard_normal(A.n), jnp.float32)
    with using_registry(MetricsRegistry()) as reg:
        res = cg(op, b, maxiter=100)
        hist = reg.get_series("solvers", "cg.residual")
        assert len(hist) == int(res.iters)
        assert hist[-1] == pytest.approx(float(res.residual), rel=1e-4)
        assert hist[-1] < hist[0]  # it converged, the series shows it
        assert reg.get("solvers", "cg.solves") == 1.0
        assert reg.get_series("solvers", "cg.time_s")[0] > 0


def test_cg_time_s_covers_the_solve_not_its_enqueue(rng):
    """Each matvec sleeps on the host while the loop runs, so the solve lasts
    at least iterations x the sleep; ``time_s`` must cover it, and no more
    than the caller's wall time until the result is ready."""
    A, op = _spd_op()
    pause = 0.004

    def slow(v):
        def host(a):
            time.sleep(pause)
            return np.asarray(a)

        return op(jax.pure_callback(host, jax.ShapeDtypeStruct(v.shape, v.dtype), v))

    b = jnp.asarray(rng.standard_normal(A.n), jnp.float32)
    cg(slow, b, maxiter=8)                              # compile outside the timing
    with using_registry(MetricsRegistry()) as reg:
        t0 = time.perf_counter()
        res = cg(slow, b, maxiter=8)
        jax.block_until_ready(res)
        wall = time.perf_counter() - t0
        time_s = reg.get_series("solvers", "cg.time_s")[0]
    assert int(res.iters) * pause <= time_s <= wall


def test_record_solve_reads_the_clock_once_the_result_is_ready():
    """A result still running on the device when the solver returns: the
    recorded time is at least its blocked wall time, not its dispatch."""
    from repro.core.solvers import _record_solve

    a = jnp.ones((1500, 1500), jnp.float32)
    heavy = jax.jit(lambda a: jnp.sum(jnp.sin(a) @ jnp.cos(a) @ jnp.sin(a)) * 0.0 + 3.0)
    jax.block_until_ready(heavy(a))
    t0 = time.perf_counter()
    jax.block_until_ready(heavy(a))
    blocked = time.perf_counter() - t0
    with using_registry(MetricsRegistry()) as reg:
        t_start = time.perf_counter()
        iters = heavy(a)                                   # dispatched, still running
        _record_solve("probe", iters, np.zeros(3), t_start)
        assert reg.get_series("solvers", "probe.iters") == [3.0]
        assert reg.get_series("solvers", "probe.time_s")[0] >= 0.25 * blocked


def test_block_cg_emits_worst_column_series(rng):
    A, op = _spd_op()
    B = jnp.asarray(rng.standard_normal((A.n, 4)), jnp.float32)
    with using_registry(MetricsRegistry()) as reg:
        res = block_cg(op, B, maxiter=100)
        hist = reg.get_series("solvers", "block_cg.residual")
        assert len(hist) == int(res.iters)
        assert hist[-1] == pytest.approx(float(res.residual.max()), rel=1e-3)


# --- the contract: telemetry changes nothing ---------------------------------


@pytest.mark.parametrize("fmt", ["csrk", "sellcs"])
def test_bit_for_bit_with_telemetry_on_vs_off(rng, fmt):
    A = grid_laplacian_2d(16, 16)
    x = jnp.asarray(rng.standard_normal(A.n), jnp.float32)
    b = jnp.asarray(rng.standard_normal(A.n), jnp.float32)

    with using_registry(MetricsRegistry(enabled=True)):
        op_on = prepare(A, format=fmt)
        y_on = np.asarray(op_on(x))
        cg_on = np.asarray(cg(op_on, b, maxiter=30).x)
    with using_registry(MetricsRegistry(enabled=False)):
        op_off = prepare(A, format=fmt)
        y_off = np.asarray(op_off(x))
        cg_off = np.asarray(cg(op_off, b, maxiter=30).x)

    assert np.array_equal(y_on, y_off)       # bit-for-bit, not allclose
    assert np.array_equal(cg_on, cg_off)


@pytest.mark.parametrize("layout", ["bucketed", "monolithic"])
def test_apply_original_bit_for_bit_with_spans_on_vs_off(rng, layout):
    """The operator-call spans change no bit, eager or under ``jit``."""
    A = grid_laplacian_2d(16, 16)
    x = jnp.asarray(rng.standard_normal(A.n), jnp.float32)
    out = {}
    for on in (True, False):
        with using_registry(MetricsRegistry(enabled=on)):
            op = prepare(A, device="tpu_v5e", format="csrk", tile_layout=layout)
            out[on] = (np.asarray(op.apply_original(x)),
                       np.asarray(jax.jit(op.apply_original)(x)))
    assert np.array_equal(out[True][0], out[False][0])
    assert np.array_equal(out[True][1], out[False][1])


# --- spans in a profiler capture -----------------------------------------------


def _host_spans(log_dir, prefix):
    """(name, start_ns, end_ns) of every host event whose name starts with prefix."""
    from jax.profiler import ProfileData

    paths = [os.path.join(d, f) for d, _, fs in os.walk(log_dir) for f in fs
             if f.endswith(".xplane.pb")]
    data = ProfileData.from_file(paths[0])
    return sorted((e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for p in data.planes if p.name == "/host:CPU"
                  for ln in p.lines for e in ln.events if e.name.startswith(prefix))


def test_apply_original_spans_nest_in_order_in_a_capture(rng, tmp_path):
    A = grid_laplacian_2d(16, 16)
    with using_registry(MetricsRegistry()):
        op = prepare(A, device="tpu_v5e", format="csrk", reorder="bandk")
        assert op.tile_buckets is not None
        x = jnp.asarray(rng.standard_normal(A.n), jnp.float32)
        op.apply_original(x).block_until_ready()
        jax.profiler.start_trace(str(tmp_path))
        op.apply_original(x).block_until_ready()
        jax.profiler.stop_trace()
    spans = sorted(_host_spans(str(tmp_path), "repro."), key=lambda s: (s[1], -s[2]))
    names = [s[0] for s in spans]
    want = ["repro.apply_original", "repro.permute_in", "repro.spmv_csrk_bucketed",
            "repro.pad_x", "repro.combine"]
    if op.tile_buckets.remainder_nnz:
        want.append("repro.remainder")
    assert names == want + ["repro.permute_out"]
    by = dict((s[0], s[1:]) for s in spans)

    def inside(child, parent):
        return by[parent][0] <= by[child][0] and by[child][1] <= by[parent][1]

    for child in names[1:]:
        assert inside(child, "repro.apply_original"), child
    for child in want[3:]:
        assert inside(child, "repro.spmv_csrk_bucketed"), child
    assert by["repro.permute_in"][1] <= by["repro.spmv_csrk_bucketed"][0]
    assert by["repro.spmv_csrk_bucketed"][1] <= by["repro.permute_out"][0]


def test_identity_perm_apply_original_opens_no_permute_span(tmp_path):
    A = grid_laplacian_2d(16, 16)
    with using_registry(MetricsRegistry()) as reg:
        op = prepare(A, device="tpu_v5e", format="csrk")
        assert reg.get("prepare", "reorder.kept_input_order") == 1.0
        assert reg.get("prepare", "phase.reorder.symperm_calls") is None
        x = jnp.asarray(np.random.default_rng(8).standard_normal(A.n), jnp.float32)
        op.apply_original(x).block_until_ready()
        jax.profiler.start_trace(str(tmp_path))
        op.apply_original(x).block_until_ready()
        jax.profiler.stop_trace()
    names = [s[0] for s in sorted(_host_spans(str(tmp_path), "repro."),
                                  key=lambda s: (s[1], -s[2]))]
    want = ["repro.apply_original", "repro.spmv_csrk_bucketed", "repro.pad_x",
            "repro.combine"]
    assert names == want + ["repro.remainder"] * bool(op.tile_buckets.remainder_nnz)


def test_the_compilation_cache_keys_programs_with_their_region_names():
    # a program compiled under other scope names must not come back from the cache
    assert jax.config.jax_compilation_cache_include_metadata_in_key


def test_registry_timer_opens_a_host_span(tmp_path):
    reg = MetricsRegistry()
    jax.profiler.start_trace(str(tmp_path))
    with reg.timer("prepare", "phase.demo"):
        time.sleep(0.002)
    jax.profiler.stop_trace()
    (name, start, end), = _host_spans(str(tmp_path), "prepare.")
    assert name == "prepare.phase.demo"
    assert (end - start) * 1e-9 == pytest.approx(
        reg.records()[0]["value"] * 1e-3, rel=0.5)


def test_bandk_phase_timers_sum_within_the_reorder_phase():
    A = grid_laplacian_2d(24, 24)
    with using_registry(MetricsRegistry()) as reg:
        prepare(A, device="tpu_v5e", format="auto", reorder="bandk")
        ms = {r["name"]: r["value"] for r in reg.records() if r["section"] == "prepare"}
    parts = ["phase.reorder.graph", "phase.reorder.coarsen", "phase.reorder.order",
             "phase.reorder.symperm"]
    for part in parts:
        assert ms[f"{part}_calls"] >= 1, part
    assert ms["phase.reorder.coarsen_calls"] == 2          # k = 3: two levels
    assert ms["phase.reorder.order_calls"] == 3            # every level ordered
    assert 0 < sum(ms[f"{p}_ms"] for p in parts) <= ms["phase.reorder_ms"]
