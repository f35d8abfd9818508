"""Tiny sizes for running the cells on the CPU, in the tests: the same
loops, traffic files and metric readers, with Pallas in interpret mode.
A served stream keeps its 30-min windows (about 20 jobs, so the sweeps
reach the device tier) over a one-hour horizon."""
import time

from bench import harness

TINY = {
    "ftn_overlay.replan": {"mix": {"jobs_per_sweep": 16, "sample": 16}},
    "metro_fanout_200.served": {"mix": {"horizon_h": 1.0, "max_horizons": 1,
                                        "sample": 12}},
    "metro_fanout_200.served_w300": {"mix": {"horizon_h": 1.0,
                                             "max_horizons": 1,
                                             "sample": 12}},
}
SEED = 2_147_483_711                   # a seed past 32 signed bits


def run(cell: str, *, trace: bool = False, seed: int = SEED,
        seconds: float = 0.5) -> dict:
    return harness.run_cell(cell, seed, seconds, trace,
                            t_start=time.perf_counter(),
                            require_accelerator=False,
                            overrides=TINY[cell], log=lambda s: None)
