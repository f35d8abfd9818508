#!/usr/bin/env python
"""Fold every BENCH_*.json section into one trajectory table — the
generated replacement for the hand-maintained "Net bench trajectory"
paragraph in ROADMAP.md.

    PYTHONPATH=src python scripts/bench_summary.py [--dir .] [--markdown]
    PYTHONPATH=src python scripts/bench_summary.py --delta OLD.json

Each bench section (``fleet_loop``, ``fleet_sharded``, ``planner_scan``,
...) becomes one line of headline numbers, so a CI job summary (or a
human mid-review) reads the whole perf state of the repo at a glance.
Sections this script does not know about still appear with their first
few scalar fields — new benches are never silently dropped.

``--delta OLD.json`` compares a prior artifact (say, ``git show
HEAD:BENCH_fleet.json`` dumped to a temp file) against its current
counterpart in ``--dir`` (matched by basename, any ``.old`` infix
stripped) and prints per-section deltas for every numeric field that
moved >= 1% plus every *raising-floor* field. Raising-floor fields
(``_RAISING_FLOORS``) are the higher-is-better numbers the repo
ratchets. Exit codes distinguish the failure modes so CI can fail on a
real perf regression without also failing on a missing baseline:

* ``0`` — deltas printed, no raising-floor field regressed > 10%;
* ``1`` — artifacts unreadable (baseline or current file missing or
  unparseable) — CI treats this as a warning, not a regression;
* ``2`` — at least one raising-floor field regressed > 10% vs the
  prior artifact — CI fails the job on this code.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

# section -> ordered (label, key) headline fields; missing keys skipped.
_HEADLINES = {
    "fleet_loop": (("jobs/s", "jobs_per_s"), ("events/s", "events_per_s"),
                   ("migrations", "migrations"),
                   ("sla_miss", "sla_misses"), ("kg", "actual_kg")),
    "fleet_sharded": (("4sh jobs/s", "jobs_per_s"),
                      ("vs loop", "speedup_vs_fleet_loop_x"),
                      ("par jobs/s", "parallel.jobs_per_s"),
                      ("par x", "parallel.parallel_speedup_x"),
                      ("exact", "parallel.exact_merge_match")),
    "fleet_streaming": (("jobs/s", "jobs_per_s"),
                        ("vs batch", "vs_batch_mode_x"),
                        ("p95 adm s", "admission_p95_s"),
                        ("backfill", "backfill_promotions"),
                        ("pipe x", "pipeline.streamed_speedup_x"),
                        ("pipe-only x", "pipeline.pipeline_only_speedup_x"),
                        ("overlap", "pipeline.overlap_fraction"),
                        ("pipe exact", "pipeline.exact_merge_match")),
    "fleet_matrix": (("cells", "cells"), ("horizon h", "horizon_h")),
    "fleet_faults": (("recoveries", "recoveries"),
                     ("rec s", "recovery_latency_mean_s"),
                     ("ckpt ovh %", "checkpoint_overhead_pct"),
                     ("exact", "exact_match_after_faults")),
    "fleet_obs": (("overhead %", "overhead_pct"),
                  ("spans/job", "spans_per_job"),
                  ("series", "metric_series"),
                  ("saved kg", "counterfactual_saved_kg")),
    "planner_scan": (("plan us", "plan_us"), ("speedup x", "speedup_x"),
                     ("batch jobs/s", "batch_jobs_per_s"),
                     ("oracle", "matches_oracle")),
    "planner_scale": (("device", "device.kind"), ("chunk", "chunk"),
                      ("rungs", "rungs")),
    "field_lattice": (("rungs", "rungs"),),
}

# section -> dotted higher-is-better fields the repo ratchets; --delta
# exits nonzero when any regresses >10% vs the prior artifact. Walls and
# counts are deliberately absent: container CPU drifts, so only the
# co-measured ratios and throughputs are floored.
_RAISING_FLOORS = {
    "fleet_loop": ("jobs_per_s",),
    "fleet_sharded": ("jobs_per_s", "parallel.parallel_speedup_x"),
    "fleet_streaming": ("jobs_per_s", "vs_batch_mode_x",
                        "pipeline.streamed_speedup_x"),
    "planner_scan": ("speedup_x", "batch_jobs_per_s"),
}

# BENCH_planner.json keeps the original scan fields at the top level;
# group them under a synthetic section so the table stays uniform.
_PLANNER_FLAT = ("plan_us", "reference_us", "speedup_x", "alternatives",
                 "alternatives_per_s", "batch_jobs_per_s", "matches_oracle",
                 "emissions_rel_err", "multi_device_count",
                 "multi_device_gate_armed", "multi_device_note",
                 "multi_device_platform",
                 "multi_device_sharded_us", "multi_device_single_us",
                 "multi_device_speedup_x")


def _get(d: dict, dotted: str):
    for part in dotted.split("."):
        if not isinstance(d, dict) or part not in d:
            return None
        d = d[part]
    return d


def _fmt(v) -> str:
    """Scalar values verbatim; containers collapse to their size so one
    section can never flood the one-line table."""
    if isinstance(v, float):
        return f"{v:.4g}"
    if isinstance(v, (list, tuple)):
        return f"[{len(v)}]"
    if isinstance(v, dict):
        return f"{{{len(v)}}}"
    return str(v)


def _headline(section: str, data: dict) -> str:
    prefs = _HEADLINES.get(section)
    parts = []
    if prefs:
        for label, key in prefs:
            v = _get(data, key)
            if v is not None:
                parts.append(f"{label}={_fmt(v)}")
    if not parts:                      # unknown section: first scalars
        for k, v in list(data.items()):
            if isinstance(v, (int, float, str)) and len(parts) < 4:
                parts.append(f"{k}={_fmt(v)}")
    return "  ".join(parts) or "(empty)"


def collect(bench_dir: pathlib.Path):
    rows = []
    for path in sorted(bench_dir.glob("BENCH_*.json")):
        try:
            data = json.loads(path.read_text())
        except (OSError, ValueError):
            continue
        if not isinstance(data, dict):
            continue
        flat = {k: v for k, v in data.items() if not isinstance(v, dict)}
        if flat and path.name == "BENCH_planner.json":
            rows.append((path.name, "planner_scan",
                         _headline("planner_scan", flat)))
        for section, sec in sorted(data.items()):
            if isinstance(sec, dict):
                rows.append((path.name, section, _headline(section, sec)))
    return rows


def _flatten(d: dict, prefix: str = "") -> dict:
    """Numeric leaves of a nested section as dotted keys (lists and
    strings are skipped — deltas only make sense for scalars)."""
    out = {}
    for k, v in d.items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flatten(v, key + "."))
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            out[key] = float(v)
    return out


def delta(old_path: pathlib.Path, bench_dir: pathlib.Path,
          markdown: bool) -> int:
    """Per-section numeric deltas of a prior artifact vs its current
    counterpart in ``bench_dir``. Returns 2 when any raising-floor field
    regressed more than 10%, 1 when the artifacts cannot be read, else
    0 (see the module docstring's exit-code table)."""
    new_name = old_path.name.replace(".old", "")
    new_path = bench_dir / new_name
    try:
        old = json.loads(old_path.read_text())
        new = json.loads(new_path.read_text())
    except (OSError, ValueError) as e:
        print(f"delta: cannot read artifacts: {e}", file=sys.stderr)
        return 1
    # BENCH_planner.json keeps scan fields flat; group them like collect()
    def _sections(data):
        secs = {k: v for k, v in data.items() if isinstance(v, dict)}
        flat = {k: v for k, v in data.items() if not isinstance(v, dict)}
        if flat and new_name == "BENCH_planner.json":
            secs["planner_scan"] = flat
        return secs

    old_secs, new_secs = _sections(old), _sections(new)
    rows = []                           # (section, field, old, new, pct)
    regressions = []
    for section in sorted(old_secs.keys() | new_secs.keys()):
        floors = _RAISING_FLOORS.get(section, ())
        o = _flatten(old_secs.get(section, {}))
        n = _flatten(new_secs.get(section, {}))
        for key in sorted(o.keys() | n.keys()):
            ov, nv = o.get(key), n.get(key)
            pct = (nv - ov) / abs(ov) * 100.0 \
                if ov not in (None, 0.0) and nv is not None else None
            floored = key in floors
            if floored and ov is not None and nv is not None \
                    and nv < ov * 0.9:
                regressions.append((section, key, ov, nv))
            # keep the table readable: floor fields always, the rest only
            # when they actually moved
            if floored or (pct is not None and abs(pct) >= 1.0) \
                    or (ov is None) != (nv is None):
                rows.append((section, key, ov, nv, pct, floored))

    def _num(v):
        return "-" if v is None else f"{v:.4g}"

    def _pct(p):
        return "-" if p is None else f"{p:+.1f}%"

    if markdown:
        print(f"### Bench delta: {new_name} vs prior")
        print("| section | field | old | new | delta |")
        print("|---|---|---|---|---|")
        for s, k, ov, nv, p, fl in rows:
            mark = " (floor)" if fl else ""
            print(f"| {s} | {k}{mark} | {_num(ov)} | {_num(nv)} "
                  f"| {_pct(p)} |")
    else:
        for s, k, ov, nv, p, fl in rows:
            mark = " [floor]" if fl else ""
            print(f"{s}.{k}{mark}: {_num(ov)} -> {_num(nv)} ({_pct(p)})")
    if not rows:
        print(f"delta: no numeric field of {new_name} moved >= 1%")
    for s, k, ov, nv in regressions:
        print(f"REGRESSION: {s}.{k} fell {_num(ov)} -> {_num(nv)} "
              f"(> 10% below the prior artifact)",
              file=sys.stderr)
    return 2 if regressions else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="BENCH_*.json one-line "
                                             "trajectory table")
    ap.add_argument("--dir", default=None,
                    help="directory holding BENCH_*.json (default: repo "
                         "root, one level above this script)")
    ap.add_argument("--markdown", action="store_true",
                    help="emit a GitHub-flavored markdown table (for "
                         "$GITHUB_STEP_SUMMARY)")
    ap.add_argument("--delta", default=None, metavar="OLD.json",
                    help="compare a prior BENCH artifact against its "
                         "current counterpart in --dir; exit 2 on >10%% "
                         "regression in any raising-floor field, 1 when "
                         "the artifacts cannot be read")
    args = ap.parse_args(argv)
    bench_dir = pathlib.Path(args.dir) if args.dir else \
        pathlib.Path(__file__).resolve().parent.parent
    if args.delta:
        return delta(pathlib.Path(args.delta), bench_dir, args.markdown)
    rows = collect(bench_dir)
    if not rows:
        print(f"no BENCH_*.json under {bench_dir}", file=sys.stderr)
        return 1
    if args.markdown:
        print("| file | section | headline |")
        print("|---|---|---|")
        for f, s, h in rows:
            print(f"| {f} | {s} | {h} |")
        return 0
    wf = max(len(r[0]) for r in rows)
    ws = max(len(r[1]) for r in rows)
    for f, s, h in rows:
        print(f"{f.ljust(wf)}  {s.ljust(ws)}  {h}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
