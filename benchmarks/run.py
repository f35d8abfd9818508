"""Benchmark driver. One function per paper table/figure (+ substrate perf).
Prints ``name,us_per_call,derived`` CSV rows.

Usage: PYTHONPATH=src python -m benchmarks.run [--only NAME]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time


def _registry():
    from benchmarks import paper_figures as F
    from benchmarks import perf as P
    from benchmarks.carbon_ablation import carbon_ablation
    return [
        ("fig2_path_carbon", F.fig2_path_carbon),
        ("fig3_time_shift", F.fig3_time_shift),
        ("fig4_space_shift", F.fig4_space_shift),
        ("fig5_overlay", F.fig5_overlay),
        ("eq1_carbonscore", F.eq1_carbonscore),
        ("table2_planner_e2e", F.table2_planner_e2e),
        ("kernel_flash_vs_ref", P.kernel_flash_vs_ref),
        ("kernel_ssd_vs_ref", P.kernel_ssd_vs_ref),
        ("carbon_field", P.carbon_field),
        ("planner_scan", P.planner_scan),
        ("planner_multi_device", P.planner_multi_device),
        ("planner_scale", P.planner_scale),
        ("field_lattice", P.field_lattice),
        ("fleet_loop", P.fleet_loop),
        ("fleet_sharded", P.fleet_sharded),
        ("fleet_streaming", P.fleet_streaming),
        ("fleet_matrix", P.fleet_matrix),
        ("fleet_faults", P.fleet_faults),
        ("fleet_obs", P.fleet_obs),
        ("train_step_microbench", P.train_step_microbench),
        ("carbon_ablation", carbon_ablation),
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args(argv)
    from repro.core.compile_cache import use_compile_cache
    use_compile_cache(pathlib.Path(__file__).resolve().parent.parent)
    rows = []
    print("name,us_per_call,derived")
    failed = 0
    for name, fn in _registry():
        if args.only and args.only != name:
            continue
        t0 = time.perf_counter()
        try:
            derived = fn()
        except Exception as e:  # noqa: BLE001
            print(f"{name},ERROR,{e!r}")
            failed += 1
            continue
        us = (time.perf_counter() - t0) * 1e6
        print(f"{name},{us:.0f},{json.dumps(derived, sort_keys=True)}")
        rows.append((name, us, derived))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
