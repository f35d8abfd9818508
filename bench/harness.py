"""Resolves a cell of ``BENCHMARK.json`` by name into its configuration,
traffic mix and metric readers, runs it, and assembles the result line.

Everything that belongs to one configuration, mix or per-layer metric is
a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json`` -- the deployment, its job law, its
  guarantees and the limits of the numbers the check compares;
* ``bench/traffic/<traffic>.json`` -- the mix: which loop offers the
  jobs (``"loop"``) and with what parameters;
* ``bench/loops/<loop>.py`` -- a loop ``Loop(config, mix, seed)`` with
  ``setup``, ``window``, ``pairs`` and ``numbers``;
* ``bench/laws/<law>.py`` -- a job law, named by a configuration's
  ``jobs`` entry (``bench/traffic.py``);
* ``bench/metrics/<metric>.py`` -- a reader ``read(run)`` that returns
  the per-layer metric's value, or None when it finds nothing to read.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
OUT = ROOT / "bench_out"               # listed in .gitignore
CACHE = OUT / "jax_cache"              # JAX's persistent compile cache


class NoAccelerator(RuntimeError):
    pass


def spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def resolve(name: str, bench: Optional[dict] = None) -> dict:
    """The cell ``name`` with its configuration, mix and metric entries."""
    bench = bench or spec()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[name]
    (cfg,) = [c for c in bench["configs"] if c["name"] == cell["config"]]

    def mine(m):
        return name in m.get("workloads", [name])

    return {
        "cell": cell,
        "config": json.loads((ROOT / cfg["file"]).read_text()),
        "mix": json.loads(
            (BENCH / "traffic" / f"{cell['traffic']}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def plugin(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py`` (a loop, a law or a metric
    reader), loaded by path."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {kind} file {path.name!r} in {path.parent}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def reader(metric: str) -> Callable:
    return plugin("metrics", metric).read


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader may read."""
    cell: str
    loop: object
    sweeps: list                       # the window's admission sweeps
    wall_s: float
    jobs_done: int
    device_kind: str
    trace: Optional[dict] = None
    stats: object = None               # GatewayStats of a served cell
    _work: Optional[Dict[str, tuple]] = None

    def work(self) -> Optional[Dict[str, tuple]]:
        """Summed (operations, bytes) of each kernel over the window's
        device sweeps, or None where the loop cannot count them."""
        from bench import roofline
        if self._work is None and hasattr(self.loop, "sweep_work"):
            rp = sw = (0.0, 0.0)
            for sweep, (pairs, cells) in zip(self.sweeps,
                                             self.loop.sweep_work()):
                if sweep.device:
                    rp = tuple(map(sum, zip(rp, roofline.rate_prefix_cost(
                        pairs))))
                    sw = tuple(map(sum, zip(sw, roofline.sweep_cost(cells))))
            self._work = {"rate_prefix": rp, "sweep": sw}
        return self._work


def device_info(chips: int, require_accelerator: bool) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_accelerator and (info["platform"] == "cpu"
                                or len(devs) < chips):
        raise NoAccelerator(
            f"the cell needs {chips} accelerator chip(s); JAX sees "
            f"{len(devs)} {info['platform']} device(s)")
    return info


def use_cache() -> None:
    """JAX's persistent compile cache at a fixed path in the checkout;
    every program is kept, however quickly it compiled."""
    import jax
    CACHE.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def memory_peak() -> int:
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def build(name: str, seed: int, *, require_accelerator: bool = True,
           overrides: Optional[dict] = None):
    """The cell resolved (with ``overrides`` on its parts), the device
    it will run on, and its loop, not yet set up."""
    r = resolve(name)
    for part, kv in (overrides or {}).items():
        r[part].update(kv)
    info = device_info(r["cell"]["chips"], require_accelerator)
    use_cache()
    return r, info, plugin("loops", r["mix"]["loop"]).Loop(
        r["config"], r["mix"], seed)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_accelerator: bool = True,
             overrides: Optional[dict] = None, log=print) -> dict:
    """One run of a cell: set-up, the measured window (traced or not),
    the check, and the result line's object (``checks`` last)."""
    import jax
    from bench import check, roofline, system, trace_reduce
    r, info, drv = build(name, seed, overrides=overrides,
                          require_accelerator=require_accelerator)
    compiled = system.compile_counter()
    drv.setup()
    # what set-up built lives for the whole run, as in a long-running
    # server: keep it out of the collector's scans inside the window
    gc.collect()
    gc.freeze()
    n_compiled = compiled()
    setup_s = time.perf_counter() - t_start
    tdir = OUT / "trace" / name
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            res = drv.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    in_window = compiled()[0] - n_compiled[0]
    log(f"set-up {setup_s:.3f} s: {n_compiled[0]} backend compiles "
        f"({n_compiled[1]:.2f} s), {n_compiled[2]} persistent-cache hits; "
        f"window {res['wall_s']:.3f} s, {res['jobs_done']} jobs, "
        f"{len(drv.planner.sweeps)} admission sweeps, "
        f"{in_window} compiles inside the window")
    info["memory_peak_bytes"] = memory_peak()
    out = {"correct": False, "attempted": 0, "failed": 0, "metrics": {},
           "device": info}
    run = Run(cell=name, loop=drv, sweeps=drv.planner.sweeps,
              wall_s=res["wall_s"], jobs_done=res["jobs_done"],
              device_kind=info["kind"], stats=getattr(drv, "stats", None))
    if trace:
        run.trace = trace_reduce.load(str(tdir))
        lo, hi = trace_reduce.window(run.trace)
        info["busy_s"] = trace_reduce.busy(run.trace, lo, hi) / 1e9
        info["window_s"] = (hi - lo) / 1e9
        for m in r["per_layer"]:
            v = reader(m["name"])(run)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = trace_reduce.breakdown(run.trace,
                                                  roofline.KERNELS)
    else:
        e2e = dict(res["end_to_end"], setup_s=setup_s)
        for m in r["end_to_end"]:
            out["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
    numbers = drv.numbers()
    ok, rows = check.judge(numbers, r["config"]["limits"])
    pairs = drv.pairs()
    out["attempted"] = len(pairs)
    out["failed"] = int(numbers["unplanned"] + numbers.get("incomplete", 0))
    out["correct"] = bool(ok and in_window == 0)
    rows.append(("compiles_in_window", float(in_window), 0.0))
    out["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    return out


def main(argv: Optional[List[str]] = None, t_start: Optional[float] = None
         ) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(
        description="Run one cell of BENCHMARK.json on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start,
                       log=lambda s: print(s, file=sys.stderr, flush=True))
    except NoAccelerator as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0
