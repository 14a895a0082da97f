"""Benchmark driver: one section per paper table/figure.

``python -m benchmarks.run [--quick]`` prints ``name,...`` CSV blocks.
``--json PATH`` additionally writes every section's rows as machine-readable
records ``{"section", "name", "value", "unit"}``, wrapped with an identity
``meta`` block (git sha, jax version, device kind/count, timestamp — see
``repro.obs.collect_metadata``) so the archived ``BENCH_<sha>.json`` files
can be ordered into a trajectory (``benchmarks/report.py --trajectory``) and
gated against regressions (``benchmarks/check_regression.py``).  The file
also carries the telemetry the run itself produced — ``prepare()`` phase
timings, padding/pointer-overhead gauges, kernel launch counters — exported
from the :mod:`repro.obs` registry in the same record schema.
"""
from __future__ import annotations

import argparse
import sys
import time

Number = (int, float)


def _unit(key: str) -> str:
    """Infer the measurement unit from a row field name."""
    if key.endswith("_us"):
        return "us"
    if key.endswith("_ms"):
        return "ms"
    if "gflops" in key:
        return "gflop/s"
    if key.endswith("_pct") or "relperf" in key:
        return "percent"  # before the overhead check: *_overhead_pct is ×100
    if "overhead" in key or key.endswith("_frac"):
        return "fraction"
    if key.endswith("_rps"):
        return "req/s"
    if "speedup" in key:
        return "ratio"
    if key in ("n", "nnz", "B", "iters", "devices", "halo"):
        return "count"
    return "scalar"


def _flatten(section: str, result) -> list:
    """Flatten a section's return value into {section, name, value, unit} rows.

    Sections return either a list of row dicts (string/bool fields label the
    row, numeric fields are measurements) or a plain dict of named scalars /
    small tuples (e.g. the tuning-model fit coefficients).
    """
    records = []
    if isinstance(result, dict):
        result = [result]
    if not isinstance(result, (list, tuple)):
        return records
    for row in result:
        if not isinstance(row, dict):
            continue
        label = ".".join(
            str(v) for v in row.values() if isinstance(v, (str, bool))
        )
        for key, val in row.items():
            name = f"{label}.{key}" if label else key
            if isinstance(val, Number) and not isinstance(val, bool):
                records.append({"section": section, "name": name,
                                "value": val, "unit": _unit(key)})
            elif isinstance(val, (list, tuple)):
                for i, item in enumerate(val):
                    if isinstance(item, Number) and not isinstance(item, bool):
                        records.append({"section": section, "name": f"{name}.{i}",
                                        "value": item, "unit": _unit(key)})
    return records


def main() -> None:
    ap = argparse.ArgumentParser(
        epilog=(
            "The distributed sweep (shards × x-strategy × B over "
            "prepare(A, mesh=...)) lives in benchmarks/distributed.py — it "
            "must run as its own process to force a multi-device host "
            "platform.  Every section runs in this one process.  Docs: "
            "docs/architecture.md, docs/formats.md, docs/tuning.md, "
            "docs/distributed.md."
        ),
    )
    ap.add_argument("--quick", action="store_true", help="smaller matrices")
    ap.add_argument("--only", default=None,
                    help="comma list: formats,spmm,banding,overhead,"
                         "constant_tuning,scaling,tuning_model,roofline,serve")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write per-section rows as JSON records "
                         '({"section", "name", "value", "unit"})')
    args = ap.parse_args()
    from repro.util.platform import configure_compile_cache

    configure_compile_cache()
    scale = 1024 if args.quick else 2048
    only = set(args.only.split(",")) if args.only else None
    records = []

    def section(name):
        return only is None or name in only

    t0 = time.time()
    if section("formats"):
        print("## formats (paper Figs. 5/6/8/9)")
        from benchmarks import formats
        records += _flatten("formats", formats.run(scale=scale))
        print("\n## formats: adversarial families × all four backends")
        from benchmarks import format_select
        adv = format_select.run_adversarial(scale=128 if args.quick else 64)
        format_select.emit(adv, [
            "matrix", "n", "nnz", "row_var", "row_skew", "diag_fraction",
            "picked", "best", "picked_is_best",
        ] + [f"t_{b}_us" for b in format_select.ALL_BACKENDS])
        records += _flatten("formats", format_select.json_rows(adv))
    if section("spmm"):
        print("\n## spmm (multi-vector fast path: batched vs looped)")
        from benchmarks import spmm
        records += _flatten("spmm", spmm.run(scale=256 if args.quick else 1024))
    if section("overhead"):
        print("\n## overhead (paper Fig. 12)")
        from benchmarks import overhead
        records += _flatten("overhead", overhead.run(scale=scale))
    if section("banding"):
        print("\n## banding ablation (paper Fig. 7)")
        from benchmarks import banding
        records += _flatten("banding", banding.run(scale=max(scale, 1024)))
    if section("constant_tuning"):
        print("\n## constant-time tuning penalty (paper Fig. 11)")
        from benchmarks import constant_tuning
        records += _flatten("constant_tuning", constant_tuning.run(scale=max(scale, 1024)))
    if section("tuning_model"):
        print("\n## tuning-model calibration (paper Sec. 4)")
        from benchmarks import tuning_model
        records += _flatten("tuning_model", tuning_model.run(scale=max(scale, 1024)))
    if section("scaling"):
        print("\n## scalability (paper Fig. 10)")
        from benchmarks import scaling
        records += _flatten("scaling", scaling.run())
    if section("roofline"):
        print("\n## roofline (measured stream ceiling vs modeled SpMV bytes)")
        from benchmarks import roofline
        records += _flatten("roofline", roofline.run(scale=scale,
                                                     quick=args.quick))
    if section("serve"):
        print("\n## serve (engine throughput: coalesced vs one-at-a-time)")
        from benchmarks import serve
        records += _flatten("serve", serve.run(scale=576, quick=args.quick))
    if args.json:
        from repro.obs import get_registry, write_records

        records += get_registry().records()
        write_records(args.json, records)
        print(f"\n# wrote {len(records)} records to {args.json}", file=sys.stderr)
    print(f"\n# total {time.time()-t0:.0f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
