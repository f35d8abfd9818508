"""Peaks of the chip and the operations and bytes each admission kernel
needs, counted from the work really present: the live (anchor, path)
pairs with their own hop counts and step counts for the rate/prefix
kernel, the live cells with their own legs, hops and start slots for the
sweep kernel. Bucket padding, padded hops and lanes, and the kernels'
staging copies are left out, so a share can only be under-counted.

The kernels are found in the device trace by their HLO text: both are
``tpu_custom_call``s of the jitted ``_fused``, the rate/prefix kernel the
one whose result is the (pair, phase, plane=3, hop, lane=128) f32 table,
the sweep kernel the one whose result is (cell, 1, 128) f32.
"""
from __future__ import annotations

import math
from typing import Iterable, Sequence, Tuple

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}

KERNELS = {
    "rate_prefix": r"^%\S+ = f32\[\d+,\d+,3,\d+,128\]\{[^}]*\} custom-call\(",
    "sweep": r"^%\S+ = f32\[\d+,1,128\]\{[^}]*\} custom-call\(",
}

F32 = 4
STEPS_PER_HOUR = 60                    # 60 s steps
# rate/prefix, per (pair, hop, step): hour of day 3, diurnal cosine 6,
# solar dip 7, weekend 1, weather noise 3, floor and calibration 4, device
# band and noise 5, compensated prefix add 7 (transcendentals count 1)
RATE_FLOPS = 35
# [E hi, E lo, r] written per (pair, hop, step)
RATE_BYTES = 3 * F32
# sweep: per (leg, hop, slot) the compensated segment 3 and its weighted
# sums 4; per (leg, slot) step lengths, unit and drift scale 5; per slot
# the leg sum, cost, feasibility and the min 9
SWEEP_FLOPS_LHS, SWEEP_FLOPS_LS, SWEEP_FLOPS_S = 7, 5, 9
# per (leg, hop, slot): E hi and E lo at the start and at the end, r at
# the end; per (leg, hop) its weight; per (leg, slot) the drift scale;
# per cell the SLA row (5 f32) and the result (3 f32)
SWEEP_BYTES_LHS, SWEEP_BYTES_LH, SWEEP_BYTES_LS, SWEEP_BYTES_C = \
    5 * F32, F32, F32, 8 * F32


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def rate_prefix_cost(pairs: Iterable[Tuple[int, int]]
                     ) -> Tuple[float, float]:
    """(operations, bytes) for live pairs given as (hops, steps): every
    step's device-CI rate and prefix, the per-hop parameters (8 f32) and
    the two hourly noise values of each hour a pair spans."""
    flops = nbytes = 0.0
    for hops, steps in pairs:
        hours = math.ceil(steps / STEPS_PER_HOUR) + 1
        flops += RATE_FLOPS * hops * steps
        nbytes += hops * (RATE_BYTES * steps + 2 * F32 * hours + 8 * F32)
    return flops, nbytes


def sweep_cost(cells: Iterable[Tuple[Sequence[int], int]]
               ) -> Tuple[float, float]:
    """(operations, bytes) for live cells given as (hops of each leg,
    start slots)."""
    flops = nbytes = 0.0
    for leg_hops, slots in cells:
        lh = sum(leg_hops)
        nl = len(leg_hops)
        flops += (SWEEP_FLOPS_LHS * lh + SWEEP_FLOPS_LS * nl
                  + SWEEP_FLOPS_S) * slots
        nbytes += (SWEEP_BYTES_LHS * lh * slots + SWEEP_BYTES_LH * lh
                   + SWEEP_BYTES_LS * nl * slots + SWEEP_BYTES_C)
    return flops, nbytes


def least_s(flops: float, nbytes: float, device_kind: str
            ) -> Tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    pk = peaks(device_kind)
    tc = flops / pk["flops_per_s"]
    tm = nbytes / pk["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
