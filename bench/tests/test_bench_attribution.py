"""Attribution of a trace's time to the program's ``repro.*`` spans."""
import os
import re

import pytest

from bench import attribution as at
from bench import harness
from bench import trace_reduce as tr
from bench.trace_reduce import Event, Line, Plane

US = 1000.0  # ns
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SCOPE = "jit(solve)/repro.cg/while/body/repro.apply_original"


def ev(name, start_us, dur_us, **stats):
    return Event(name, start_us * US, dur_us * US, tuple(stats.items()))


def jitted():
    """A 100 us window: one jitted solve whose operations carry their scopes."""
    host = Plane("/host:CPU", (Line("python", (
        ev("bench.window", 0, 100),
        ev("PjitFunction(solve)", 0, 5), ev("ExecuteHelper", 1, 3, run_id=7),
    )),))
    device = Plane("/device:TPU:0", (
        Line("XLA Modules", (ev("jit_solve", 5, 90, run_id=7),)),
        Line("XLA Ops", (
            ev("while.1", 5, 90, tf_op="jit(solve)/repro.cg/while"),
            ev("fusion.23", 10, 10, tf_op=f"{SCOPE}/repro.permute_in/gather"),
            ev("spmv_csrk.1", 20, 40,
               tf_op=f"{SCOPE}/repro.spmv_csrk_bucketed/pallas_call"),
            # glue inside the kernel wrapper's scope: not a kernel
            ev("scatter.4", 60, 10,
               tf_op=f"{SCOPE}/repro.spmv_csrk_bucketed/repro.combine/scatter"),
            ev("fusion.28", 70, 10, tf_op=f"{SCOPE}/repro.permute_out/gather"),
            ev("fusion.2", 80, 5, tf_op="jit(solve)/repro.cg/while/body/add"),
            ev("copy.6", 85, 5),      # added by the compiler: no op_name
        )),
    ))
    return [host, device]


def eager(link=True, drop_launch=None):
    """A 200 us window: one eager ``apply_original`` whose five launches run
    on the device after the host has returned."""
    rid = (lambda i: {"run_id": i}) if link else (lambda i: {})
    launches = [ev(f"PjitFunction({n})", s, 3) for n, s in
                (("gather", 1), ("_pad", 5), ("spmv_csrk_tiles_pallas", 10),
                 ("scatter", 15), ("gather", 26))]
    helpers = [ev("ExecuteHelper", s + 1, 1, **rid(i + 1))
               for i, s in enumerate((1, 5, 10, 15, 26))]
    if drop_launch is not None:
        del launches[drop_launch]
    host = Plane("/host:CPU", (Line("python", tuple([
        ev("bench.window", 0, 200),
        ev("repro.apply_original", 0, 30),
        ev("repro.permute_in", 0, 5),
        ev("repro.spmv_csrk_bucketed", 5, 20),
        ev("repro.pad_x", 5, 3),
        ev("repro.combine", 15, 5),
        ev("repro.permute_out", 25, 5),
    ] + launches + helpers)),))
    mods = [(1, 50, 10), (2, 60, 5), (3, 65, 55), (4, 120, 10), (5, 130, 10)]
    device = Plane("/device:TPU:0", (
        Line("XLA Modules", tuple(ev(f"module.{i}", s, d, **rid(i)) for i, s, d in mods)),
        Line("XLA Ops", (
            ev("gather.1", 50, 10, hlo_module="jit_gather"),
            ev("pad.1", 60, 5, hlo_module="jit__pad"),
            ev("spmv_csrk.1", 65, 50, hlo_module="jit_spmv_csrk_tiles_pallas"),
            ev("transpose.2", 115, 5, hlo_module="jit_spmv_csrk_tiles_pallas"),
            ev("scatter.1", 120, 10, hlo_module="jit_scatter"),
            ev("gather.1", 130, 10, hlo_module="jit_gather"),
        )),
    ))
    return [host, device]


def test_jitted_operations_find_their_scope():
    a = at.attribute(jitted())
    assert a.how == "module+scope"     # the copy belongs to the solve's module
    assert a.busy_s == pytest.approx(90e-6)
    # the while loop keeps only its own time, [5, 10) and [90, 95)
    assert a.device_s == pytest.approx({
        "repro.cg": 20e-6, "repro.permute_in": 10e-6, "repro.spmv_csrk_bucketed": 40e-6,
        "repro.combine": 10e-6, "repro.permute_out": 10e-6})
    assert a.kernel_s == pytest.approx({"repro.spmv_csrk_bucketed": 40e-6})
    assert a.unattributed_s == 0
    assert a.device("repro.permute_in", "repro.permute_out") == pytest.approx(20e-6)
    assert a.device("repro.no_such_span") is None


def test_a_scope_named_like_the_kernel_wrapper_is_not_kernel_time():
    s = tr.reduce(jitted())
    # only the Pallas call: the combine scatter under repro.spmv_csrk_bucketed is glue
    assert s.kernel_s == pytest.approx(40e-6)
    assert s.glue_s == pytest.approx(50e-6)
    assert at.attribute(jitted()).kernel_s.keys() == {"repro.spmv_csrk_bucketed"}


def test_eager_launches_find_their_span_by_launch_id():
    a = at.attribute(eager())
    assert a.how == "launch"
    assert a.device_s == pytest.approx({
        "repro.permute_in": 10e-6, "repro.pad_x": 5e-6, "repro.spmv_csrk_bucketed": 55e-6,
        "repro.combine": 10e-6, "repro.permute_out": 10e-6})
    assert a.kernel_s == pytest.approx({"repro.spmv_csrk_bucketed": 50e-6})


def test_eager_launches_are_matched_in_order_without_a_launch_id():
    a = at.attribute(eager(link=False))
    assert a.how == "order"
    assert a.device_s == pytest.approx(at.attribute(eager()).device_s)


def test_launches_and_executions_that_differ_in_number_are_an_error():
    with pytest.raises(at.AttributionFailed, match="4 launches in the window and 5 module"):
        at.attribute(eager(link=False, drop_launch=2))


def test_host_total_self_and_calls():
    a = at.attribute(eager())
    assert a.host_calls["repro.apply_original"] == 1
    assert a.host_s["repro.apply_original"] == pytest.approx(30e-6)
    assert a.host_self_s["repro.apply_original"] == pytest.approx(0)
    assert a.host_s["repro.spmv_csrk_bucketed"] == pytest.approx(20e-6)
    assert a.host_self_s["repro.spmv_csrk_bucketed"] == pytest.approx(12e-6)
    summary = a.summary()["host_ms_per_call"]["repro.spmv_csrk_bucketed"]
    assert summary == pytest.approx({"calls": 1, "total": 20e-3, "self": 12e-3})


def test_idle_time_is_named_by_the_innermost_program_span():
    a = at.attribute(eager())
    # the device idles over [0, 50) and [140, 200)
    assert a.idle_s == pytest.approx({
        "repro.permute_in": 5e-6, "repro.pad_x": 3e-6, "repro.spmv_csrk_bucketed": 12e-6,
        "repro.combine": 5e-6, "repro.permute_out": 5e-6, tr.WINDOW_SPAN: 80e-6})


@pytest.mark.parametrize("lost_us,fails", [(0.5, False), (0.9, False), (2, True), (12, True)])
def test_busy_time_without_a_span_fails_above_one_percent(lost_us, fails):
    host, device = jitted()
    ops = device.lines[1].events + (ev("copy.5", 95, lost_us),)   # no scope, no launch
    device = Plane(device.name, (device.lines[0], Line("XLA Ops", ops)))
    if fails:
        with pytest.raises(at.AttributionFailed, match="found no program span"):
            at.attribute([host, device])
    else:
        a = at.attribute([host, device])
        assert a.unattributed_s == pytest.approx(lost_us * 1e-6)


def test_a_program_without_the_operator_call_span_gives_nothing():
    host = Plane("/host:CPU", (Line("python", (ev("bench.window", 0, 100),)),))
    device = Plane("/device:TPU:0", (Line("XLA Ops", (ev("spmv_csrk", 10, 50),)),))
    assert at.attribute([host, device]) is None
    assert at.attribute([jitted()[0]]) is None                # no device plane
    # a program from before the spans: only its kernel wrapper is named
    host, device = eager()
    old = tuple(e for e in host.lines[0].events if e.name in (
        "bench.window", "repro.spmv_csrk_bucketed") or not e.name.startswith(("repro.", "bench.")))
    assert at.attribute([Plane(host.name, (Line("python", old),)), device]) is None


def tpu_like():
    """The eager call of :func:`eager` as a TPU trace links it: the device's
    module executions end flows that begin on runtime threads, which began
    inside launches on the window's thread."""
    host, device = eager(link=False)
    python = host.lines[0].events + tuple(
        ev("PJRT_LoadedExecutable_Execute linkage", s + 1.5, 0.5, _p=100 + i, _pt=14)
        for i, s in enumerate((1, 5, 10, 15, 26)))
    main, tasks = [], []
    for i, s in enumerate((1, 5, 10, 15, 26)):
        main += [ev("PJRT_LoadedExecutable_Execute", s + 1.6, 1.2, _c=100 + i, _ct=14),
                 ev("tpu::System::Execute", s + 1.7, 0.2, _p=200 + i, _pt=7)]
        # the odd launches are enqueued on a runtime thread after the call returned
        where, at_ = (tasks, s + 30) if i % 2 else (main, s + 2)
        where += [ev("IssueSequencedEvent", at_, 0.6, _c=200 + i, _ct=7),
                  ev("DoEnqueueProgram", at_ + 0.1, 0.3, _p=300 + i, _pt=12, run_id=i + 1)]
    # flow ids of another type that collide with the ones above
    main.append(ev("TransferToDevice", 90, 1, _p=100, _pt=5))
    modules = tuple(Event(m.name, m.start_ns, m.dur_ns, (("_c", 300 + i), ("_ct", 12)))
                    for i, m in enumerate(device.lines[0].events))
    return [Plane(host.name, (Line("python", python), Line("main/289", tuple(main)),
                              Line("pjrt-tpu-tasks/322", tuple(tasks)))),
            Plane(device.name, (Line("XLA Modules", modules), device.lines[1]))]


def test_eager_launches_are_followed_back_along_the_traces_flows():
    a = at.attribute(tpu_like())
    assert a.how == "launch"
    assert a.device_s == pytest.approx(at.attribute(eager()).device_s)


@pytest.mark.parametrize("op_name,path", [
    (f"{SCOPE}/repro.permute_in/gather:", ["repro.cg", "repro.apply_original", "repro.permute_in"]),
    # a fusion: the scopes every fused operation shares
    ("jit(f)/repro.a/repro.b/add;jit(f)/repro.a/repro.c/mul:", ["repro.a"]),
    ("jit(f)/repro.a/add;jit(f)/copy", []),
    ("jit(f)/while/body/add:", []),
    ("jit(f)/not_repro.x/add", []),
    (None, []),
])
def test_scope_path_of_an_op_name(op_name, path):
    assert at.scope_path(op_name) == path


def test_an_op_name_is_found_in_the_events_metadata():
    e = Event("%fusion.3 = f32[8] fusion()", 0, 1)
    assert at.scope_of(e) == []
    assert at.scope_of(e, {e.name: "jit(f)/repro.pad_x/pad:"}) == ["repro.pad_x"]


def _pb(*fields):
    """Protobuf wire bytes of (number, value) fields: int -> varint, bytes/str -> length-delimited."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def test_op_names_reads_the_event_metadata_of_device_planes(tmp_path):
    stat_md = [(5, _pb((1, k), (2, _pb((1, k), (2, name))))) for k, name in
               ((1, "tf_op"), (2, "hlo_category"), (3, "jit(g)/repro.combine/scatter:"))]
    event_md = [(4, _pb((1, k), (2, _pb((1, k), (2, name), *[(5, _pb(*st)) for st in stats]))))
                for k, name, stats in (
                    (10, "%fusion.1 = f32[8] fusion()",
                     [((1, 2), (5, "fusion")), ((1, 1), (5, "jit(f)/repro.permute_in/gather:"))]),
                    (11, "%scatter.2 = f32[8] scatter()", [((1, 1), (7, 3))]),   # interned
                    (12, "%copy.3 = f32[8] copy()", [((1, 2), (5, "copy"))]),
                    (13, "%same = f32[8] add()", [((1, 1), (5, "jit(f)/repro.a/add:"))]),
                    (14, "%same = f32[8] add()", [((1, 1), (5, "jit(f)/repro.b/add:"))]))]
    device = _pb((1, 7), (2, "/device:TPU:0"), (3, _pb((2, "XLA Ops"))), *event_md, *stat_md)
    host = _pb((2, "/host:CPU"), *event_md[:1], *stat_md)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb((1, device), (1, host)))
    assert at.op_names(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = f32[8] fusion()": "jit(f)/repro.permute_in/gather:",
        "%scatter.2 = f32[8] scatter()": "jit(g)/repro.combine/scatter:"}}


def test_a_run_reads_its_own_trace_once(monkeypatch, capsys):
    looked = []
    monkeypatch.setattr(tr, "find_xplane", lambda d: looked.append(d) or "run.xplane.pb")
    monkeypatch.setattr(tr, "load", lambda path: eager())
    monkeypatch.setattr(at, "op_names", lambda path: {})
    run = harness.Run(host={}, obs={}, trace=object(), mat=None, peaks=None)
    a = at.of(run, trace_dir="traces")
    assert at.of(run) is a and looked == ["traces"]
    assert a.device_s == at.attribute(eager()).device_s
    assert capsys.readouterr().out.count('{"attribution": ') == 1
    untraced = harness.Run(host={}, obs={}, trace=None, mat=None, peaks=None)
    assert at.of(untraced, trace_dir="traces") is None and looked == ["traces"]


def test_leaf_segments_charge_the_innermost_operation():
    ops = [ev("while", 0, 100), ev("a", 10, 20), ev("b", 15, 5), ev("c", 90, 20)]
    got = at.leaf_segments(ops, 0, 100 * US)
    # while [0,10)+[30,90); a [10,15)+[20,30); b [15,20); c [90,100) clipped
    assert got == pytest.approx({0: 70e-6, 1: 15e-6, 2: 5e-6, 3: 10e-6})


def _program_region_names():
    names = set()
    pat = re.compile(r"""\b(?:annotated|annotate)\(\s*["']([^"']+)["']""")
    for base, _, files in os.walk(os.path.join(ROOT, "src", "repro")):
        for f in files:
            if f.endswith(".py"):
                names |= set(pat.findall(open(os.path.join(base, f)).read()))
    return sorted(names)


def test_no_program_region_is_named_like_a_kernel():
    names = _program_region_names()
    assert {"repro.apply_original", "repro.permute_in", "repro.combine"} <= set(names)
    for name in names:
        op = Event("fusion.1", 0, 1, (("tf_op", f"jit(f)/{name}/add"),))
        assert tr.kernel_of(op) is None, name


NEW_READERS = ["permute_ms.spmv", "permute_ms.cg", "apply_host_ms.spmv",
               "prepare_coarsen_s", "prepare_order_s", "prepare_symperm_s"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_find_nothing_in_a_program_without_the_spans(name):
    run = harness.Run(host={"calls": 10, "iterations": 10}, obs={"prepare/phase.reorder_ms": 5.0},
                      trace=None, mat=None, peaks=None)
    assert harness.load_reader(name)(run) is None


def test_new_readers_read_the_attribution_and_the_timers(monkeypatch):
    a = at.attribute(eager())
    monkeypatch.setattr(at, "of", lambda run: a)
    run = harness.Run(host={"calls": 2, "iterations": 4},
                      obs={"prepare/phase.reorder.coarsen_ms": 1500.0,
                           "prepare/phase.reorder.order_ms": 2500.0,
                           "prepare/phase.reorder.symperm_ms": 500.0},
                      trace=object(), mat=None, peaks=None)
    read = {n: harness.load_reader(n)(run) for n in NEW_READERS}
    assert read == pytest.approx({
        "permute_ms.spmv": 20e-3 / 2, "permute_ms.cg": 20e-3 / 4,
        "apply_host_ms.spmv": 30e-3, "prepare_coarsen_s": 1.5,
        "prepare_order_s": 2.5, "prepare_symperm_s": 0.5})
