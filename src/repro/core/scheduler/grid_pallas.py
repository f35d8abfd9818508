"""Fused Pallas planner kernel: the admission-sweep scoring chain in two
``pl.pallas_call``s (``CarbonPlanner(batch_backend="pallas")``).

Layer contract: **numpy is the pinned oracle** (see ``grid_jax.py``). The
jitted lattice path (:func:`grid_jax.batch_cell_emissions`) materializes a
full ``(C, 2, S)`` emission tensor and leaves the per-cell feasible-argmin
to the host. This module fuses the per-cell chain — CI evaluation, the
prefix sum over the rate grid, the per-(anchor, path) gather, SLA masking
and the argmin over start slots — so only the winning (cost, emissions,
slot) of each cell leaves the device.

No kernel value is 64-bit (TPU Mosaic has no f64/i64). Time and hour
math runs on anchor-relative int32 step indices, and the prefix sum is
carried as a compensated (hi, lo) f32 pair, so a segment
``E[hi] - E[k]`` keeps the ~1e-7 relative error of an f64 accumulator.

The rate grid of every (anchor, path) pair is laid out **slot-major**:
``(pair, phase, plane, hop, row)`` with planes [E hi, E lo, r], where
step ``t = row * stride + phase`` and ``stride`` is the slot spacing in
steps. A start slot ``s`` is then lane ``s`` of the phase-0 slab, and the
step ``s * stride + n - 1`` a transfer of ``n`` steps ends on is lane
``s + (n - 1) // stride`` of the phase ``(n - 1) % stride`` slab — a
gather becomes a slab copy plus one lane rotation.

* :func:`_rate_prefix_kernel` — grid ``(A, R/L)`` over pairs and blocks of
  ``L = 128`` rows. Per block it evaluates device CI phase by phase
  (hour noise from ``K`` per-row candidate hours, picked by integer
  compares), accumulates the within-row exclusive prefix, scans the row
  totals across lanes and carries the block total in VMEM scratch.
* :func:`_sweep_kernel` — grid ``(C,)``, one cell per step. Scalar
  prefetch holds each cell's pair rows and phase; the kernel copies just
  the slabs the cell reads out of HBM, rotates them, weighs hops, applies
  the drift-scale row, masks infeasible slots (deadline count, carbon
  budget) and takes the first min over slots.

Execution: ``interpret=True`` where the default backend is the CPU (CI
runs the kernels through the XLA interpreter: correctness, not speed),
compiled everywhere else. A kernel that fails to lower raises.
Equivalence with the numpy ``plan_batch`` oracle (same cells, emissions
<= 1e-4 relative) is pinned by ``tests/test_grid_pallas.py``; the v5e
compile of both kernels by ``tests/test_chip_compile.py``.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from repro.core.carbon.field import CarbonField
from repro.core.carbon.path import NetworkPath
from repro.core.scheduler.grid_jax import (_B_CELLS, _MAX_ELEMS, CellTask,
                                           HAVE_JAX, _chunk_tables,
                                           _iter_chunks, _round_up)

if HAVE_JAX:
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

_LANES = 128                           # rows per rate block (one vreg wide)
_HOP_COLS = 8                          # [base, amp, dip, namp, peak, band,
                                       #  cal_a, cal_b] per (pair, hop)
_CELL_COLS = 8                         # [rem, dur, w_perf/slack, w_carbon,
                                       #  budget_g, 0, 0, 0] per cell
_WEEKEND = np.float32(0.94)


def _add2(ah, al, bh, bl):
    """(ah + al) + (bh + bl) as a compensated f32 pair: the high parts sum
    with TwoSum, so the rounding error of ``ah + bh`` lands in the low
    part exactly."""
    s = ah + bh
    bp = s - ah
    err = (ah - (s - bp)) + (bh - bp)
    return s, (al + bl) + err


def _rate_prefix_kernel(hp_ref, zk_ref, hk_ref, ti_ref, tf_ref,
                        tab_ref, chi_ref, clo_ref, *,
                        n_phase: int, n_cand: int, sph: int, spd: int,
                        dt_s: float):
    """Device-CI rates and the exclusive compensated prefix of one pair
    over one block of ``L`` rows.

    Blocks: hp (1, H, 8) per-hop params; zk/hk (1, K, H, L) zone/hop
    noise at the K candidate hours of each row; ti (1, 2, L) int32
    [phase of the row's first step within its hour, step-of-day of the
    row's first step]; tf (1, 3, L) f32 [sub-step anchor seconds,
    weekday factor of the row's first day, of the next day]. Writes
    tab (1, P, 3, H, L) planes [E hi, E lo, r]; chi/clo (H, L) carry the
    prefix across row blocks.
    """
    lb = pl.program_id(1)

    @pl.when(lb == 0)
    def _init():
        chi_ref[...] = jnp.zeros_like(chi_ref)
        clo_ref[...] = jnp.zeros_like(clo_ref)

    hp = hp_ref[0]
    base, amp, dip = hp[:, 0:1], hp[:, 1:2], hp[:, 2:3]
    namp, peak, band = hp[:, 3:4], hp[:, 4:5], hp[:, 5:6]
    cal_a, cal_b = hp[:, 6:7], hp[:, 7:8]
    ti, tf = ti_ref[0], tf_ref[0]
    offh, mo = ti[0:1], ti[1:2]
    fsec, wk_a, wk_b = tf[0:1], tf[1:2], tf[2:3]
    shape = chi_ref.shape

    def phase(j, acc):
        hi, lo = acc
        m = mo + j
        wrap = m >= spd                                  # next day
        m = jnp.where(wrap, m - spd, m)
        hod = (m.astype(jnp.float32) * dt_s + fsec) / 3600.0
        z, u = zk_ref[0, 0], hk_ref[0, 0]
        for k in range(1, n_cand):                       # hour of step j
            nxt = offh + j >= k * sph
            z = jnp.where(nxt, zk_ref[0, k], z)
            u = jnp.where(nxt, hk_ref[0, k], u)
        v = base + amp * jnp.cos(2 * np.pi * (hod - peak) / 24.0)
        v = v - dip * jnp.exp(-0.5 * ((hod - 13.0) / 2.5) ** 2)
        v = v * jnp.where(wrap, wk_b, wk_a)
        v = jnp.maximum(v + namp * z, 1.0)
        v = jnp.maximum(cal_a * v + cal_b, 0.5)
        r = v * (1.0 + 0.02 * band + 0.005 * u)
        tab_ref[0, j, 0] = hi
        tab_ref[0, j, 1] = lo
        tab_ref[0, j, 2] = r
        return _add2(hi, lo, r, jnp.zeros_like(r))

    zero = jnp.zeros(shape, jnp.float32)
    th, tl = jax.lax.fori_loop(0, n_phase, phase, (zero, zero))
    # inclusive lane scan of the row totals (Hillis-Steele, compensated)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    ih, il = th, tl
    d = 1
    while d < shape[1]:
        keep = lane >= d
        ih, il = _add2(ih, il,
                       jnp.where(keep, pltpu.roll(ih, d, 1), 0.0),
                       jnp.where(keep, pltpu.roll(il, d, 1), 0.0))
        d *= 2
    first = lane >= 1
    bh, bl = _add2(chi_ref[...], clo_ref[...],
                   jnp.where(first, pltpu.roll(ih, 1, 1), 0.0),
                   jnp.where(first, pltpu.roll(il, 1, 1), 0.0))
    last = shape[1] - 1
    chi, clo = _add2(chi_ref[...], clo_ref[...],
                     jnp.broadcast_to(ih[:, last:], shape),
                     jnp.broadcast_to(il[:, last:], shape))
    chi_ref[...] = chi
    clo_ref[...] = clo

    def rebase(j, carry):
        eh, el = _add2(bh, bl, tab_ref[0, j, 0], tab_ref[0, j, 1])
        tab_ref[0, j, 0] = eh
        tab_ref[0, j, 1] = el
        return carry

    jax.lax.fori_loop(0, n_phase, rebase, 0)


def _sweep_kernel(pidx_ref, ph_ref, sh_ref, nval_ref, tab_hbm, scl_hbm,
                  w_ref, cf_ref, out_ref, tbuf, sbuf, sem, *,
                  dt_s: float, slot_s: float):
    """One cell: both legs' segment emissions over every start slot, the
    SLA mask and the first-min argmin.

    Scalar prefetch: pidx (2C,) pair row per leg, ph (C,) phase of the
    last step, sh (C,) lane rotation that brings row ``s + o`` to lane
    ``s``, nval (C,) count of deadline-feasible slots (0 for pad cells,
    so every slot masks to +inf). ``tab_hbm`` (A, P, 3, H, L) and
    ``scl_hbm`` (A, 1, L) stay in HBM: per leg the kernel copies the
    [E hi, E lo, r] slabs at the last-step phase and at phase 0, and the
    pair's drift-scale row. w (1, 2, H, 1) hop weights; cf (1, 1, 8) the
    cell's f32 row. Writes lanes [cost, emissions, slot] of out.
    """
    c = pl.program_id(0)
    ph = ph_ref[c]
    copies = []
    for leg in range(2):
        a = pidx_ref[2 * c + leg]
        copies += [
            pltpu.make_async_copy(tab_hbm.at[a, ph], tbuf.at[leg, 0],
                                  sem.at[leg, 0]),
            pltpu.make_async_copy(tab_hbm.at[a, 0], tbuf.at[leg, 1],
                                  sem.at[leg, 1]),
            pltpu.make_async_copy(scl_hbm.at[a], sbuf.at[leg],
                                  sem.at[leg, 2])]
    for cp in copies:
        cp.start()
    for cp in copies:
        cp.wait()
    sh = sh_ref[c]
    cf = cf_ref[0]
    rem, dur, wp, wc, budget = (cf[:, 0:1], cf[:, 1:2], cf[:, 2:3],
                                cf[:, 3:4], cf[:, 4:5])
    w = w_ref[0]

    def leg_emis(leg):
        end, start = tbuf[leg, 0], tbuf[leg, 1]          # (3, H, L) each
        seg = ((pltpu.roll(end[0], sh, 1) - start[0])
               + (pltpu.roll(end[1], sh, 1) - start[1]))
        last = pltpu.roll(end[2], sh, 1)
        e = (jnp.sum(w[leg] * seg, axis=0, keepdims=True) * dt_s
             + jnp.sum(w[leg] * last, axis=0, keepdims=True) * rem) / 3.6e6
        return e * sbuf[leg]

    emis = leg_emis(0) + leg_emis(1)                     # (1, L)
    slots = jax.lax.broadcasted_iota(jnp.int32, emis.shape, 1)
    # perf term relative to submission: start slot_s*s + duration
    cost = wc * emis + wp * (slot_s * slots.astype(jnp.float32) + dur)
    feas = (slots < nval_ref[c]) & (emis <= budget)
    cost = jnp.where(feas, cost, jnp.inf)
    cmin = jnp.min(cost, axis=1, keepdims=True)
    jmin = jnp.min(jnp.where(cost == cmin, slots, emis.shape[1]),
                   axis=1, keepdims=True)                # first min
    emin = jnp.sum(jnp.where(slots == jmin, emis, 0.0), axis=1,
                   keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape[1:], 1)
    out_ref[0] = jnp.where(lane == 0, cmin,
                           jnp.where(lane == 1, emin,
                                     jnp.where(lane == 2,
                                               jmin.astype(jnp.float32),
                                               0.0)))


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """None = auto: interpret where the default backend is the CPU,
    compiled lowering everywhere else."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() == "cpu"


class KernelInputs(NamedTuple):
    """Host-built arrays of one chunk, in ``_fused`` argument order."""
    hp: np.ndarray                     # (A, H, 8) f32
    zk: np.ndarray                     # (A, K, H, L) f32
    hk: np.ndarray                     # (A, K, H, L) f32
    ti: np.ndarray                     # (A, 2, L) i32
    tf: np.ndarray                     # (A, 3, L) f32
    pidx: np.ndarray                   # (2C,) i32
    ph: np.ndarray                     # (C,) i32
    sh: np.ndarray                     # (C,) i32
    nval: np.ndarray                   # (C,) i32
    w: np.ndarray                      # (C, 2, H, 1) f32
    cf: np.ndarray                     # (C, 1, 8) f32
    scl: np.ndarray                    # (A, 1, L) f32


def _rate_table(hp, zk, hk, ti, tf, *, stride: int, dt_s: float,
                interpret: bool):
    """The rate/prefix kernel over every (pair, row block): the packed
    (A, P, 3, H, L) f32 table of [E hi, E lo, r] planes."""
    a_pad, n_cand, h_hops, width = zk.shape
    dt = int(dt_s)
    rate = functools.partial(_rate_prefix_kernel, n_phase=stride,
                             n_cand=n_cand, sph=3600 // dt, spd=86400 // dt,
                             dt_s=dt_s)
    blk = pl.BlockSpec((1, stride, 3, h_hops, _LANES),
                       lambda a, b: (a, 0, 0, 0, b))
    return pl.pallas_call(
        rate,
        grid=(a_pad, width // _LANES),
        in_specs=[
            pl.BlockSpec((1, h_hops, _HOP_COLS), lambda a, b: (a, 0, 0)),
            pl.BlockSpec((1, n_cand, h_hops, _LANES),
                         lambda a, b: (a, 0, 0, b)),
            pl.BlockSpec((1, n_cand, h_hops, _LANES),
                         lambda a, b: (a, 0, 0, b)),
            pl.BlockSpec((1, 2, _LANES), lambda a, b: (a, 0, b)),
            pl.BlockSpec((1, 3, _LANES), lambda a, b: (a, 0, b)),
        ],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((a_pad, stride, 3, h_hops, width),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((h_hops, _LANES), jnp.float32),
                        pltpu.VMEM((h_hops, _LANES), jnp.float32)],
        interpret=interpret,
    )(hp, zk, hk, ti, tf)


def _sweep(tab, pidx, ph, sh, nval, w, cf, scl, *, dt_s: float,
           slot_s: float, interpret: bool):
    """The per-cell sweep kernel over a rate table: (C, 1, 128) f32 with
    lanes [cost, emissions, slot]."""
    h_hops, width = tab.shape[3:]
    c_pad = ph.shape[0]
    sweep = functools.partial(_sweep_kernel, dt_s=dt_s, slot_s=slot_s)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(c_pad,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec((1, 2, h_hops, 1),
                               lambda c, *_: (c, 0, 0, 0)),
                  pl.BlockSpec((1, 1, _CELL_COLS), lambda c, *_: (c, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, _LANES), lambda c, *_: (c, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, 2, 3, h_hops, width), jnp.float32),
                        pltpu.VMEM((2, 1, width), jnp.float32),
                        pltpu.SemaphoreType.DMA((2, 3))],
    )
    return pl.pallas_call(
        sweep,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((c_pad, 1, _LANES), jnp.float32),
        interpret=interpret,
    )(pidx, ph, sh, nval, tab, scl, w, cf)


def _fused(hp, zk, hk, ti, tf, pidx, ph, sh, nval, w, cf, scl, *,
           stride: int, dt_s: float, slot_s: float, interpret: bool):
    """The fused sweep for one chunk: the rate table, then the per-cell
    sweep. Returns (C, 1, 128) f32 with lanes [cost, emissions, slot]."""
    tab = _rate_table(hp, zk, hk, ti, tf, stride=stride, dt_s=dt_s,
                      interpret=interpret)
    return _sweep(tab, pidx, ph, sh, nval, w, cf, scl, dt_s=dt_s,
                  slot_s=slot_s, interpret=interpret)


_fused_jit = None                      # one compiled-kernel cache per process


def _fused_call():
    global _fused_jit
    if _fused_jit is None:
        _fused_jit = jax.jit(_fused, static_argnames=(
            "stride", "dt_s", "slot_s", "interpret"))
    return _fused_jit


def _kernel_inputs(field: CarbonField, cells: Sequence[CellTask],
                   sla_rows: np.ndarray, *, dt_s: float, slot_stride: int,
                   slot_s: float,
                   scale_fn: Optional[Callable[[NetworkPath, np.ndarray],
                                               np.ndarray]]
                   ) -> KernelInputs:
    """Lay one chunk's cell tables out slot-major for the two kernels.

    All 64-bit time math happens here, on the host: each pair's anchor
    becomes an int32 step index ``q`` from the hour-aligned window start
    plus a sub-step remainder, and every row's first step gets its phase
    within the hour, its step of the day and its weekday factors.
    """
    t = _chunk_tables(field, cells, dt_s=dt_s, slot_stride=slot_stride,
                      cell_bucket=_B_CELLS)
    dt = int(dt_s)
    if dt != dt_s or 3600 % dt:
        raise ValueError(f"dt_s must be a whole divisor of 3600 s, "
                         f"got {dt_s}")
    sph, spd = 3600 // dt, 86400 // dt
    p = int(slot_stride)
    if not 1 <= p <= spd:
        raise ValueError(f"slot_stride must be 1..{spd} steps, got {p}")
    width = _round_up(-(-t.n_grid_pad // p), _LANES)    # rows, lane-padded
    a_pad, h_hops = t.path_idx.shape[0], t.n_hops
    hours = t.znoise.shape[1]
    # --- per-pair time rows (int math; anchors may be fractional) ---------
    rel0 = t.rel0a[t.anchor_idx]                        # (A,) f64
    q = np.floor(rel0 / dt).astype(np.int64)
    fsec = (rel0 - q * dt).astype(np.float32)
    u = q[:, None] + p * np.arange(width)[None, :]      # first step of row
    hour0 = u // sph
    n_cand = (sph - 1 + p - 1) // sph + 1               # hours a row spans
    hrs = np.clip(hour0[:, None, :] + np.arange(n_cand)[None, :, None],
                  0, hours - 1)                         # (A, K, L)
    zid = t.zone_idx[t.path_idx]                        # (A, H)
    zk = t.znoise[zid[:, None, :, None], hrs[:, :, None, :]]
    hk = t.hnoise[t.path_idx[:, None, None, None],
                  np.arange(h_hops)[None, None, :, None],
                  hrs[:, :, None, :]]
    hod0 = int(round(t.h_of_day0))
    day = (u + int(round(t.day_frac_s)) // dt) // spd
    dow = (t.dow0 + day) % 7
    ti = np.stack([u - hour0 * sph, (hod0 * sph + u) % spd],
                  axis=1).astype(np.int32)
    tf = np.stack([np.broadcast_to(fsec[:, None], u.shape),
                   np.where(dow >= 5, _WEEKEND, np.float32(1.0)),
                   np.where((dow + 1) % 7 >= 5, _WEEKEND, np.float32(1.0))],
                  axis=1).astype(np.float32)
    zbase, zamp, zdip, znamp, zpeak = t.zcols
    hp = np.stack([zbase[zid], zamp[zid], zdip[zid], znamp[zid],
                   zpeak[zid], t.band[t.path_idx],
                   np.full(zid.shape, t.cal_a), np.full(zid.shape, t.cal_b)],
                  axis=-1).astype(np.float32)
    # --- drift-scale rows: a pair's slot times are anchor + slot_s * s ----
    scl = np.ones((a_pad, 1, width), dtype=np.float32)
    if scale_fn is not None:
        for a in range(t.n_pairs):
            ts = t.pair_anchors[a] + slot_s * np.arange(width)
            scl[a, 0] = scale_fn(t.pair_paths[a], ts)
    # --- per-cell rows (pads: n_steps 1, n_valid 0) -----------------------
    c_pad = t.pair_idx.shape[0]
    last = t.n_steps.astype(np.int64) - 1               # step of the end
    nval = np.zeros(c_pad, dtype=np.int32)
    nval[:len(cells)] = sla_rows[:, 0]
    cf = np.zeros((c_pad, 1, _CELL_COLS), dtype=np.float32)
    cf[:, 0, 0] = t.rem
    cf[:len(cells), 0, 1:5] = sla_rows[:, 1:5]
    return KernelInputs(
        hp=hp, zk=zk.astype(np.float32), hk=hk.astype(np.float32), ti=ti,
        tf=tf, pidx=t.pair_idx.reshape(-1).astype(np.int32),
        ph=(last % p).astype(np.int32),
        sh=((width - last // p) % width).astype(np.int32), nval=nval,
        w=t.w_dev.astype(np.float32)[..., None], cf=cf, scl=scl)


def batch_cell_best(field: CarbonField, cells: Sequence[CellTask],
                    sla_rows: Sequence[Sequence[float]], *,
                    dt_s: float = 60.0, slot_stride: int = 60,
                    slot_s: float = 3600.0,
                    scale_fn: Optional[Callable[[NetworkPath, np.ndarray],
                                                np.ndarray]] = None,
                    interpret: Optional[bool] = None
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Fused admission sweep: the winning (cost, emissions, slot) of every
    cell, computed entirely in-kernel — the ``(C, 2, S)`` emission tensor
    the lattice path materializes never exists.

    ``sla_rows`` carries one ``[n_valid, dur_s, w_perf/slack, w_carbon,
    budget_g]`` row per cell (``n_valid`` = the count of deadline-feasible
    leading slots, computed host-side because that mask is monotone in the
    slot index; ``budget_g`` = +inf when the SLA has no carbon budget).
    ``scale_fn`` is the planner's ``emission_scale_fn`` drift hook,
    evaluated host-side into a per-(anchor, path) slot table.

    Returns ``(cost, emis, slot)`` arrays over cells; ``cost = +inf``
    means no feasible slot (the caller falls back per job). The kernels
    compute in f32 and the host widens; cost and emissions match the
    numpy ``plan_batch`` oracle within 1e-4 relative (~1e-7 in practice).
    """
    if not HAVE_JAX:
        raise ImportError(
            "batch_cell_best needs jax; use the numpy plan_batch oracle")
    sla_rows = np.asarray(sla_rows, dtype=np.float64)
    if sla_rows.shape != (len(cells), 5):
        raise ValueError(f"sla_rows must be (n_cells, 5), got "
                         f"{sla_rows.shape}")
    run_interpret = _resolve_interpret(interpret)
    cost = np.full(len(cells), np.inf)
    emis = np.full(len(cells), np.inf)
    slot = np.zeros(len(cells), dtype=np.int64)
    for chunk in _iter_chunks(cells, slot_stride, _MAX_ELEMS):
        x = _kernel_inputs(field, [cells[j] for j in chunk],
                           sla_rows[chunk], dt_s=dt_s,
                           slot_stride=slot_stride, slot_s=slot_s,
                           scale_fn=scale_fn)
        best = np.asarray(_fused_call()(
            *x, stride=int(slot_stride), dt_s=float(dt_s),
            slot_s=float(slot_s), interpret=run_interpret))
        idx = np.asarray(chunk, dtype=np.int64)
        n = len(chunk)
        cost[idx] = best[:n, 0, 0]
        emis[idx] = best[:n, 0, 1]
        slot[idx] = best[:n, 0, 2].astype(np.int64)
    return cost, emis, slot
