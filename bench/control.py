#!/usr/bin/env python3
"""Readings for the limits of ``correct``: for each seed, a short window
of a cell at its own load, the numbers the check compares for the
program, and the same numbers, on the same sampled jobs, for the control
-- the plain reference put in the program's place with each step's
emission rate stored in bfloat16 -- and for the fault ``runner_up`` --
the float64 reference choosing each job's second-best cell, with that
cell's own emissions. One process reads every seed.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5

Writes one JSON line per seed to ``bench_out/readings/<cell>.jsonl`` and
prints the largest program reading and the smallest control reading of
each number.
"""
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CONTROL_DTYPE = "bfloat16"


def readings(name, seeds, seconds, *, overrides=None,
             require_accelerator=True, log=print):
    from bench import check, harness
    out = []
    for seed in seeds:
        _, _, drv = harness.build(name, seed, overrides=overrides,
                                   require_accelerator=require_accelerator)
        drv.setup()
        drv.window(seconds)
        t0 = time.perf_counter()
        prog = drv.numbers()
        jobs = [j for j, _ in drv.sampled()]
        ctl = check.control_numbers(drv.dep, jobs, CONTROL_DTYPE)
        wrong = check.control_numbers(drv.dep, jobs, None, check.runner_up)
        row = {"seed": seed, "program": prog, "control": ctl,
               "runner_up": wrong, "reference_s": time.perf_counter() - t0}
        log(json.dumps(row))
        out.append(row)
    return out


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    from bench import harness
    rows = readings(args.workload, [int(s) for s in args.seeds.split(",")],
                    args.seconds)
    dst = harness.OUT / "readings" / f"{args.workload}.jsonl"
    dst.parent.mkdir(parents=True, exist_ok=True)
    with dst.open("a") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    for k in rows[0]["program"]:
        lo = max(r["program"][k] for r in rows)
        up = {w: min((r[w].get(k, float("nan")) for r in rows),
                     default=float("nan")) for w in ("control", "runner_up")}
        print(f"{k}: program max {lo!r}, control min {up['control']!r}, "
              f"runner_up min {up['runner_up']!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
