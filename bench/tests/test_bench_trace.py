"""The trace reduction: busy union, idle share, kernel and glue, idle-gap names."""
import jax
import jax.numpy as jnp
import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Event, Line, Plane

US = 1000.0  # ns


def ev(name, start_us, dur_us, **stats):
    return Event(name, start_us * US, dur_us * US, tuple(stats.items()))


def synthetic():
    """A 100 us window: two calls, each a dispatch, a kernel and glue ops."""
    host = Plane("/host:CPU", (
        Line("python", (
            ev("bench.window", 0, 100),
            ev("bench.dispatch", 0, 10), ev("bench.wait", 10, 40),
            ev("bench.dispatch", 50, 20), ev("bench.wait", 70, 30),
        )),
        Line("other thread", (ev("bench.window", 0, 1000),)),
    ))
    device = Plane("/device:TPU:0", (
        Line("XLA Modules", (ev("jit_everything", 0, 100),)),
        Line("XLA Ops", (
            ev("gather.1", 10, 5, hlo_module="jit_gather"),
            # the Pallas call, named through a stat as a TPU trace does
            ev("custom-call.3", 15, 20, hlo_module="jit_spmv_csrk_tiles_pallas",
               long_name="custom-call(...), kernel_name=spmv_csrk"),
            # inside the kernel's wrapper, but not the kernel
            ev("transpose.2", 35, 5, hlo_module="jit_spmv_csrk_tiles_pallas",
               tf_op="jit(spmv_csrk_tiles_pallas)/transpose"),
            ev("gather.1", 70, 5, hlo_module="jit_gather"),
            ev("spmv_csrk", 75, 20),
            ev("spmv_csrk", 80, 10),          # overlaps: counted once
            ev("copy.9", 95, 10),             # runs past the window: clipped
            ev("copy.9", -20, 10),            # before the window: dropped
        )),
    ))
    return [host, device]


def test_busy_idle_kernel_glue():
    s = tr.reduce(synthetic())
    assert s.chips == 1
    assert s.window_s == pytest.approx(100e-6)
    # busy: [10, 40) and [70, 100) -> 60 us
    assert s.busy_s == pytest.approx(60e-6)
    assert s.idle_share == pytest.approx(0.4)
    # kernels: [15, 35) and [75, 95) -> 40 us; glue the other 20 us
    assert s.kernel_s == pytest.approx(40e-6)
    assert s.glue_s == pytest.approx(20e-6)


def test_idle_gaps_are_named_by_the_host_span():
    s = tr.reduce(synthetic())
    gaps = dict((name, sec) for name, sec in s.top_gaps())
    # [0, 10) dispatch, [40, 50) wait, [50, 70) dispatch
    assert gaps == pytest.approx({"bench.dispatch": 30e-6, "bench.wait": 10e-6})


def test_op_totals_and_names():
    s = tr.reduce(synthetic())
    ops = dict((name, sec) for name, sec in s.top_ops())
    assert ops["jit_gather/gather.1"] == pytest.approx(10e-6)
    assert ops["spmv_csrk"] == pytest.approx(30e-6)   # per event, not the union
    assert ops["copy.9"] == pytest.approx(5e-6)


@pytest.mark.parametrize("text,kernel", [
    ("spmv_csrk", "spmv_csrk"), ("fusion(spmv_sellcs)", "spmv_sellcs"),
    ("spmv_csrk_tiles_pallas", None), ("repro.spmv_csrk_bucketed", None),
    ("spmv_dia", "spmv_dia"), ("spmv_diahybrid", None), ("copy.3", None),
    ('custom_call_target="tpu_custom_call"', "pallas"),
])
def test_kernel_names_match_whole_words(text, kernel):
    assert tr.kernel_of(Event(text, 0, 1)) == kernel
    assert tr.kernel_of(Event("custom-call.1", 0, 1, (("long_name", text),))) == kernel


def test_merge_and_clip():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3), (5, 8)]
    assert tr.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


def test_no_device_plane_gives_nothing():
    host, _ = synthetic()
    assert tr.reduce([host]) is None
    assert tr.reduce([synthetic()[1]]) is None       # no window span


def test_busy_device_without_a_kernel_is_an_error():
    host, _ = synthetic()
    dev = Plane("/device:TPU:0", (Line("XLA Ops", (ev("custom-call.7", 10, 30),
                                                  ev("gather.1", 50, 10))),))
    with pytest.raises(tr.NoKernelFound, match="custom-call.7"):
        tr.reduce([host, dev])
    idle = Plane("/device:TPU:0", (Line("XLA Ops", (ev("gather.1", 200, 10),)),))
    assert tr.reduce([host, idle]).busy_s == 0


def test_two_chips_average():
    host, dev = synthetic()
    dev1 = Plane("/device:TPU:1", (Line("XLA Ops", (ev("spmv_csrk", 0, 100),)),))
    s = tr.reduce([host, dev, dev1])
    assert s.chips == 2
    assert s.busy_s == pytest.approx((60e-6 + 100e-6) / 2)
    assert s.kernel_s == pytest.approx((40e-6 + 100e-6) / 2)


def test_recorded_trace_loads(tmp_path):
    """A real profiler trace (CPU: no device plane) loads with its host spans."""
    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones(256)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    planes = tr.load(tr.find_xplane(str(tmp_path)))
    found = tr.window_span(planes)
    assert found is not None
    line, win = found
    assert win.dur_ns > 0
    assert any(e.name == "bench.dispatch" and win.start_ns <= e.start_ns < win.end_ns
               for e in line.events)
    assert tr.reduce(planes) is None
