"""BENCHMARK.json holds to its contract, and the harness finds every piece by name."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench import drivers, harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"] and SPEC["command"][1] == "bench/run.py"
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    # a full check of 24 cells at this length fits its 43200 s
    assert (2 + 14 * 24) * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_entry_keys():
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}
    for group, need in keys.items():
        names = [e["name"] for e in SPEC[group]]
        assert len(set(names)) == len(names)
        for e in SPEC[group]:
            assert set(e) - {"workloads"} == need, e["name"]
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
            for key in ("why", "layer", "source"):
                if key in e:
                    assert 1 <= len(e[key]) <= 200 and not set(e[key]) & {"\n", "\t"}
    for e in SPEC["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25 and e["source"] in ("host_clock", "device_trace")
    assert next(e for e in SPEC["end_to_end"] if e["name"] == "setup_s")["bound"] <= 0.25


def test_every_cell_reports_setup_another_metric_and_a_layer():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for cell in CELLS:
        p = harness.plan(cell)
        mine = {m["name"] for m in p["end_to_end"]}
        assert "setup_s" in mine and len(mine) >= 2
        assert p["per_layer"]
        for m in p["per_layer"]:
            assert m["moves"] in mine, (cell, m["name"])
            assert m["moves"] in e2e
    for m in SPEC["per_layer"] + SPEC["end_to_end"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_layers_are_named_alike_in_perf_md():
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for layer in {m["layer"] for m in SPEC["per_layer"]}:
        assert f"**{layer}**" in perf, layer


def test_configs_are_found_by_name():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/")
        data = json.load(open(os.path.join(ROOT, c["file"])))
        assert data["name"] == c["name"] and data["reduced"] == c["reduced"]
        assert data["source"] == c["source"]
        assert os.path.isfile(os.path.join(BENCH, "matrices", data["matrix"]["generator"] + ".py"))


@pytest.mark.parametrize("cell", CELLS)
def test_traffic_is_data_for_a_known_driver(cell):
    p = harness.plan(cell)
    assert callable(drivers.driver_class(p["traffic"]["driver"]))
    assert all(v > 0 for v in p["traffic"]["checks"].values())


@pytest.mark.parametrize("name", [m["name"] for m in SPEC["per_layer"]])
def test_every_per_layer_metric_has_a_reader(name):
    assert callable(harness.load_reader(name))


def test_an_unknown_metric_or_cell_is_an_error():
    with pytest.raises(ModuleNotFoundError):
        drivers.driver_class("no_such_driver")
    with pytest.raises(FileNotFoundError):
        harness.load_reader("no_such_metric")
    with pytest.raises(KeyError):
        harness.plan("no_such.cell")


def test_readers_find_nothing_without_a_trace():
    run = harness.Run(host={"calls": 10, "units": 10}, obs={}, trace=None, mat=None, peaks=None)
    for m in SPEC["per_layer"]:
        if m["source"] == "device_trace":
            assert harness.load_reader(m["name"])(run) is None, m["name"]


def test_roofline_uses_the_benchmarks_own_byte_count():
    from bench.matrices import Csr
    from bench.trace_reduce import TraceSummary
    import numpy as np

    mat = Csr(np.zeros(1001, np.int32), np.zeros(5000, np.int32), np.zeros(5000, np.float32),
              (1000, 1000))
    t = TraceSummary(chips=1, window_s=1.0, busy_s=0.5, kernel_s=0.4, op_s={}, gaps=[])
    run = harness.Run(host={"calls": 100}, obs={}, trace=t, mat=mat,
                      peaks={"hbm_bytes_per_s": 1e9})
    nbytes = 8 * 5000 + 4 * 1001 + 4 * 1000 + 4 * 1000
    assert harness.load_reader("spmv_roofline")(run) == pytest.approx(
        nbytes / 1e9 / (0.5 / 100) * 100)


def test_off_a_tpu_the_run_exits_non_zero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"metrics"' not in out.stdout and "needs 1 TPU" in out.stderr
