# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark runner: every paper table/figure + roofline + kernels.

``PYTHONPATH=src python -m benchmarks.run [--only substring] [--smoke]``
Writes artifacts/bench/results.csv alongside the stdout CSV.
``--smoke`` is forwarded to every module whose ``run`` accepts it
(seconds-scale sweeps for CI; full-profile numbers otherwise).
"""
from __future__ import annotations

import argparse
import importlib
import inspect
import json
import pathlib
import sys
import time

MODULES = [
    "benchmarks.bench_heterogeneity",      # Table 5
    "benchmarks.bench_selection",          # Table 6
    "benchmarks.bench_selection_scale",    # engine scaling (beyond paper)
    "benchmarks.bench_sharded_selection",  # region-sharded control plane
    "benchmarks.bench_beacon_failover",    # Beacon fault domains / handoff
    "benchmarks.bench_partition",          # split-brain + data locality
    "benchmarks.bench_client_scale",       # client-pool scaling (beyond paper)
    "benchmarks.bench_serving_selection",  # queueing-aware vs proximity-only
    "benchmarks.bench_mesh_scale",         # mesh-sharded pool (multi-device)
    "benchmarks.bench_scalability",        # Fig 6
    "benchmarks.bench_user_distribution",  # Fig 7
    "benchmarks.bench_node_scaling",       # Fig 8
    "benchmarks.bench_autoscale",          # Fig 9
    "benchmarks.bench_fault_tolerance",    # Fig 10
    "benchmarks.bench_storage",            # Table 7 + Fig 11-13
    "benchmarks.bench_kernels",            # kernel oracles + pallas equiv
    "benchmarks.bench_autotune",           # geo_topk (block_u, node_tile)
    "benchmarks.bench_roofline",           # §Roofline table
]


def _us(ms):
    """ms -> us; annotation-only rows (``None`` or NaN timing) become
    ``None`` so the JSON artifact stays strict (``null``, never the
    non-standard ``NaN`` literal that breaks spec-compliant parsers)."""
    return ms * 1e3 if ms is not None and ms == ms else None


def _fmt(us):
    return "" if us is None else f"{us:.1f}"


def _artifacts_dir() -> pathlib.Path:
    return pathlib.Path(__file__).resolve().parents[1] / "artifacts" / "bench"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale profiles for modules that offer one")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    all_rows = []
    print("name,us_per_call,derived")
    mods = {m: importlib.import_module(m) for m in MODULES}
    for modname, mod in mods.items():
        if args.only and args.only not in modname:
            continue
        t0 = time.time()
        kw = {}
        if args.smoke and "smoke" in inspect.signature(mod.run).parameters:
            kw["smoke"] = True
        rows = mod.run(**kw)
        for name, ms, derived in rows:
            us = _us(ms)                                  # ms -> us
            print(f"{name},{_fmt(us)},{derived}")
            all_rows.append({"name": name, "us_per_call": us,
                             "derived": derived})
        print(f"# {modname} done in {time.time()-t0:.1f}s", file=sys.stderr)

    out = _artifacts_dir()
    out.mkdir(parents=True, exist_ok=True)
    results = out / "results.json"
    if args.only and results.exists():
        # partial run: refresh the selected rows in place instead of
        # clobbering every other benchmark's recorded results
        prev = json.loads(results.read_text())
        for r in prev:                       # heal pre-fix NaN artifacts
            if r["us_per_call"] != r["us_per_call"]:
                r["us_per_call"] = None
        fresh = {r["name"] for r in all_rows}
        all_rows = [r for r in prev if r["name"] not in fresh
                    and not r.get("derived_row")] + all_rows
    # Cross-benchmark ratios (speedup rows, weak scaling) are recomputed
    # from the *merged* measurements by each module's ``derive`` hook —
    # a partial ``--only`` run can therefore never leave a stale ratio
    # computed against rows it did not re-measure.
    us_by_name = {r["name"]: r["us_per_call"] for r in all_rows}
    for modname, mod in mods.items():
        fn = getattr(mod, "derive", None)
        if fn is None:
            continue
        for name, ms, derived in fn(us_by_name):
            us = _us(ms)
            print(f"{name},{_fmt(us)},{derived}")
            all_rows.append({"name": name, "us_per_call": us,
                             "derived": derived, "derived_row": True})
    results.write_text(json.dumps(all_rows, indent=1, allow_nan=False))
    with open(out / "results.csv", "w") as f:
        f.write("name,us_per_call,derived\n")
        for r in all_rows:
            f.write(f"{r['name']},{_fmt(r['us_per_call'])},"
                    f"{r['derived']}\n")


if __name__ == "__main__":
    main()
