"""Jit-compiled planner grid scoring on the jnp backend.

Layer contract: **numpy is the pinned oracle**. Every code path in this
module recomputes a quantity that ``CarbonField`` (and through it
``CarbonPlanner.plan`` / ``plan_batch``) already defines on numpy; the jax
paths exist purely for speed and must agree with the numpy results within
1e-4 relative (asserted by ``tests/test_controlplane.py``). New fast paths
follow the same rule: add the jnp kernel *and* the equivalence test against
the numpy implementation, never a jnp-only behaviour.

Two scorers live here:

* :class:`JaxGridScorer` — the per-leg backend behind
  ``CarbonPlanner(backend="jax")``. The planner's inner loop scores every
  candidate start slot of a (FTN x replica) leg by integrating the per-hop
  emission rate r(t) = sum_dev P_dev * CI_dev(t) / 3.6e6 over the transfer
  window; here that integral runs as a ``jax.jit``-compiled kernel built on
  the ``make_window`` / ``window_ci`` dense view — all blake2b noise is
  hashed once into (zone x hour) and (hop x hour) arrays at window-build
  time, and the jitted function is pure array math.
* :func:`batch_cell_emissions` — the fleet-scale path behind
  ``CarbonPlanner.plan_batch_jax``: the (job x FTN x replica x slot) grids
  of *many* jobs are padded/masked into one stacked cell table and scored
  by a single jitted kernel (``vmap`` over the stacked job-cell axis, and
  optionally ``shard_map`` over the cell axis when more than one device is
  visible). One call replaces thousands of per-leg evaluations.

Design notes for jit stability:

* windows are anchored per *path* at an hour boundary with a generous
  horizon, so ``window_ci``'s host-side time constants (``t0``-derived)
  stay static across a planning session — recompiles happen per path, not
  per job; the batched kernel instead passes every anchor-derived time
  constant as a *traced* argument, so one compilation serves every
  planning sweep;
* grid lengths are padded to coarse buckets so shape-driven recompiles are
  bounded;
* both kernels evaluate f32 CI and accumulate the prefix sums in f64
  (~1e-7 relative emission error, memory-bound CPU passes at half the
  bandwidth); the batched kernel runs under ``jax.enable_x64`` only so
  its *time and index* math (hour boundaries, day-of-week flips) lands
  exactly where the numpy oracle puts it. (The Pallas tier in
  ``grid_pallas`` has no 64-bit values at all.)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.carbon.energy import HostPowerModel
from repro.core.carbon.field import (CarbonField, CarbonWindow, default_field,
                                     make_window, window_ci)
from repro.core.carbon.intensity import REGIONS, get_calibration
from repro.core.carbon.path import NetworkPath

try:                                   # jax is optional: numpy stays the oracle
    import jax
except ImportError:                    # pragma: no cover - env without jax
    jax = None
HAVE_JAX = jax is not None
if HAVE_JAX:
    import jax.numpy as jnp

_WINDOW_HOURS = 24 * 14                # per-anchor horizon (2 weeks)
_GRID_BUCKET = 512                     # rate-grid length rounding


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Declared multi-chip mesh for the batched planner's cell-axis split.

    ``batch_cell_emissions`` (and through it
    ``CarbonPlanner.plan_batch_jax``) historically accepted ``shard=True``
    — "use every visible device" — which is the right default on a
    single-host CI runner but under-specifies a real multi-chip topology.
    A ``MeshConfig`` *declares* the placement instead: which platform's
    devices, how many of them, and the mesh axis name the kernel's
    ``PartitionSpec``\\ s shard the cell axis over. ``build()`` resolves it
    against the live process into a ``jax.sharding.Mesh``; the forced
    host-device subprocess sweep (``benchmarks/perf.py::
    planner_multi_device``) is the CI stand-in for genuinely distinct
    chips.

    Frozen (hashable) on purpose: the built mesh rides the jit cache as a
    static argument, so two sweeps under the same declared mesh reuse one
    compilation.
    """
    axis: str = "cells"
    n_devices: Optional[int] = None    # None = every matching device
    platform: Optional[str] = None     # None = the default backend's

    def __post_init__(self):
        if not self.axis:
            raise ValueError("MeshConfig.axis must be a non-empty name")
        if self.n_devices is not None and self.n_devices < 1:
            raise ValueError(f"MeshConfig.n_devices must be >= 1 or None, "
                             f"got {self.n_devices}")

    def devices(self) -> list:
        """The live devices this config selects, in jax enumeration
        order (truncated to ``n_devices`` when set)."""
        if not HAVE_JAX:
            raise ImportError("MeshConfig needs jax")
        devs = (jax.devices(self.platform) if self.platform is not None
                else jax.devices())
        if self.n_devices is not None:
            devs = devs[:self.n_devices]
        return list(devs)

    def build(self) -> "jax.sharding.Mesh":
        """Resolve into a 1-D ``jax.sharding.Mesh`` over :meth:`devices`."""
        devs = self.devices()
        if not devs:
            raise ValueError(f"MeshConfig{dataclasses.astuple(self)!r} "
                             f"matches no devices")
        return jax.sharding.Mesh(np.array(devs), (self.axis,))


class _PathWindow:
    """Dense, jit-ready view of one path over [t0, t0 + hours h): the zone
    window plus the per-hop sub-metering band and hourly noise that turn
    zone CI into device CI (``CarbonField.hop_ci_matrix`` semantics)."""

    def __init__(self, field: CarbonField, path: NetworkPath, t0: float,
                 hours: int):
        zones = tuple(dict.fromkeys(h.zone for h in path.hops))
        self.window: CarbonWindow = make_window(zones, t0, hours, field)
        self.t0, self.hours = float(t0), int(hours)
        self.zone_idx = np.array([zones.index(h.zone) for h in path.hops],
                                 dtype=np.int32)
        self.hop_band = np.array([field._hop_band(h.ip) for h in path.hops])
        hour0 = int(t0 // 3600.0)
        hour_idx = np.arange(hour0, hour0 + hours)
        self.hop_noise = np.stack(
            [field._hop_noise.lookup(h.ip, hour_idx) - 0.5
             for h in path.hops])

    def covers(self, t_lo: float, t_hi: float) -> bool:
        return (t_lo >= self.t0
                and t_hi <= self.t0 + 3600.0 * self.hours - 1e-6)


def _make_rate_fn(window: CarbonWindow):
    """Jitted emission-rate kernel for one window anchor. ``window``'s time
    constants are closed over (static); all per-call arrays are traced."""

    def rate(base, amp, dip, namp, peak, znoise, zone_idx, hop_band,
             hop_noise, w_dev, rel_ts):
        w = CarbonWindow(zones=window.zones, t0=window.t0,
                         hours=window.hours, base=base, amp=amp, dip=dip,
                         noise_amp=namp, peak=peak, noise=znoise,
                         cal_a=window.cal_a, cal_b=window.cal_b)
        zci = window_ci(w, zone_idx[:, None], rel_ts[None, :], xp=jnp)
        hour_frac = window.t0 - 3600.0 * math.floor(window.t0 / 3600.0)
        hour_rel = jnp.clip(
            jnp.floor((rel_ts + hour_frac) / 3600.0).astype(jnp.int32),
            0, window.hours - 1)
        band = (1.0 + 0.02 * hop_band[:, None]
                + 0.005 * hop_noise[:, hour_rel])
        return (w_dev @ (zci * band)) / 3.6e6

    return jax.jit(rate)


class JaxGridScorer:
    """Per-planner cache of path windows + compiled rate kernels."""

    def __init__(self, field: Optional[CarbonField] = None):
        if not HAVE_JAX:
            raise ImportError(
                "CarbonPlanner(backend='jax') needs jax; install it or use "
                "backend='numpy' (the pinned oracle)")
        self.field = field or default_field()
        self._windows: Dict[Tuple, _PathWindow] = {}
        self._rate_fns: Dict[Tuple, object] = {}

    def _path_window(self, path: NetworkPath, t_lo: float,
                     t_hi: float) -> _PathWindow:
        key = (path.src, path.dst, path.hops)
        pw = self._windows.get(key)
        if pw is None or not pw.covers(t_lo, t_hi):
            t0 = 3600.0 * math.floor(t_lo / 3600.0)
            hours = max(int(math.ceil((t_hi - t0) / 3600.0)) + 1,
                        _WINDOW_HOURS)
            hours = int(math.ceil(hours / _WINDOW_HOURS)) * _WINDOW_HOURS
            pw = _PathWindow(self.field, path, t0, hours)
            self._windows[key] = pw
            # anchor changed: the closed-over time constants did too
            self._rate_fns.pop(key, None)
        return pw

    def leg_emissions_g(self, path: NetworkPath, sender: HostPowerModel,
                        receiver: HostPowerModel, bytes_moved: float,
                        t0s: np.ndarray, throughput_gbps: float, *,
                        parallelism: int = 1, concurrency: int = 1,
                        dt_s: float = 60.0) -> np.ndarray:
        """``CarbonField.transfer_emissions_g`` for slot-aligned starts, with
        the O(hops x grid) rate evaluation under ``jax.jit``."""
        t0s = np.atleast_1d(np.asarray(t0s, dtype=np.float64))
        if throughput_gbps <= 0:
            return np.full(t0s.shape, np.inf)
        duration_s = bytes_moved * 8.0 / (throughput_gbps * 1e9)
        n_steps = max(int(math.ceil(duration_s / dt_s - 1e-12)), 1)
        rem = duration_s - (n_steps - 1) * dt_s
        offsets = (t0s - t0s.min()) / dt_s
        k = np.rint(offsets).astype(np.int64)
        if offsets.size and np.max(np.abs(offsets - k)) >= 1e-9:
            # unaligned starts: stay on the numpy oracle (rare; the planner
            # slot scan is always grid-aligned)
            return self.field.transfer_emissions_g(
                path, sender, receiver, bytes_moved, t0s, throughput_gbps,
                parallelism=parallelism, concurrency=concurrency, dt_s=dt_s)
        n_grid = int(k.max()) + n_steps
        n_pad = int(math.ceil(n_grid / _GRID_BUCKET)) * _GRID_BUCKET
        pw = self._path_window(path, float(t0s.min()),
                               float(t0s.min()) + n_pad * dt_s)
        key = (path.src, path.dst, path.hops)
        fn = self._rate_fns.get(key)
        if fn is None:
            fn = self._rate_fns[key] = _make_rate_fn(pw.window)
        w_dev = self.field._device_weights(path, sender, receiver,
                                           throughput_gbps, parallelism,
                                           concurrency)
        rel = (float(t0s.min()) - pw.t0) + dt_s * np.arange(n_pad)
        w = pw.window
        r = np.asarray(fn(w.base, w.amp, w.dip, w.noise_amp, w.peak, w.noise,
                          pw.zone_idx, pw.hop_band, pw.hop_noise, w_dev,
                          rel), dtype=np.float64)
        prefix = np.concatenate([[0.0], np.cumsum(r[:n_grid])])
        full = (prefix[k + n_steps - 1] - prefix[k]) * dt_s
        return full + r[k + n_steps - 1] * rem


# --- fleet-batched scoring (plan_batch_jax) --------------------------------
#
# One jitted call scores every (job, FTN, replica) cell of a whole fleet:
# ragged per-job grids are padded/masked into rectangular tables, a stacked
# (anchor, path) axis carries the per-hop CI grids, and a vmap over the
# job-cell axis turns prefix-sum gathers into per-cell emission rows.

_B_PAIRS = 64                          # (anchor, path) axis bucket
_B_CELLS = 64                          # job-cell axis bucket
_B_SLOTS = 16                          # start-slot axis bucket
_B_HOURS = 168                         # window-hours bucket (one week)
_B_ZONES = 8                           # zone axis bucket
_MAX_GRID = 1 << 15                    # per-cell rate-grid cap (~22 days)
_MAX_ELEMS = 32 * 1024 * 1024          # pairs*hops*grid budget per jit call


@dataclasses.dataclass(frozen=True)
class LegTask:
    """One leg of one grid cell: a path plus its device-power weights."""
    path: NetworkPath
    anchor: float                      # grid anchor (the job's first slot)
    w_dev: np.ndarray                  # (n_hops,) device power draw, W


@dataclasses.dataclass(frozen=True)
class CellTask:
    """One (job, FTN, replica) cell: 1–2 legs sharing a slot/step layout."""
    legs: Tuple[LegTask, ...]
    n_slots: int                       # candidate starts: anchor + k*slot
    n_steps: int                       # dt_s steps per transfer
    rem_s: float                       # pro-rated final-step seconds


def _round_up(n: int, b: int) -> int:
    return int(math.ceil(max(n, 1) / b)) * b


def _kernel(zbase, zamp, zdip, znamp, zpeak, znoise, cal_a, cal_b,
            h_of_day0, day_frac_s, dow0, rel0a, anchor_idx, zone_idx,
            band, hnoise, path_idx, pair_idx, w_dev, n_steps, rem,
            *, n_grid, n_slots, slot_stride, dt_s, n_dev, mesh=None):
    """The one-jit fleet scorer (shapes: Z zones, W hours, N anchors,
    P paths, H hops, A (anchor, path) pairs, C cells, S slots, T=n_grid
    rate-grid steps).

    Stage 1 evaluates zone CI on the (anchor x zone x grid) lattice — the
    trig/noise chain runs once per anchor-zone, not once per hop — with
    all anchor-derived time constants traced, so one compilation serves
    every sweep. Stage 2 gathers the lattice into per-(anchor, path)
    device-CI grids (sub-metering band x hourly hop noise) and
    prefix-sums them. Stage 3 vmaps a gather/einsum over the stacked
    job-cell axis; with more than one visible device the cell axis is
    additionally ``shard_map``-ed.
    """
    n_z, W = znoise.shape
    n_hops = zone_idx.shape[1]
    # time/index math stays f64 (hour boundaries must land exactly); the
    # CI value chain runs f32 (memory-bound on CPU; ~1e-7 rel), and the
    # prefix sum accumulates the f32 rates in f64 — the same split the
    # per-leg JaxGridScorer uses, honoring the 1e-4 oracle bound.
    t_rel = rel0a[:, None] + dt_s * jnp.arange(n_grid)[None, :]     # (N,T)
    hour_rel = jnp.clip((t_rel // 3600.0).astype(jnp.int32), 0, W - 1)
    hod = (((h_of_day0 + t_rel / 3600.0) % 24.0)
           .astype(znoise.dtype)[:, None, :])                       # (N,1,T)
    dow = ((dow0 + jnp.floor((t_rel + day_frac_s) / 86400.0)
            .astype(jnp.int32)) % 7)[:, None, :]
    v = (zbase[None, :, None] + zamp[None, :, None]
         * jnp.cos(2 * np.pi * (hod - zpeak[None, :, None]) / 24.0))
    v = v - zdip[None, :, None] * jnp.exp(-0.5 * ((hod - 13.0) / 2.5) ** 2)
    v = jnp.where((dow == 5) | (dow == 6), v * 0.94, v)
    v = v + znamp[None, :, None] * jnp.take(
        znoise.ravel(),
        jnp.arange(n_z)[None, :, None] * W + hour_rel[:, None, :])
    v = jnp.maximum(v, 1.0)
    v = jnp.maximum(cal_a * v + cal_b, 0.5)                         # (N,Z,T)
    # stage 2: gather the lattice into (anchor, path) device-CI grids
    zrow = anchor_idx[:, None] * n_z + zone_idx[path_idx]           # (A,H)
    ci = v.reshape(-1, v.shape[2])[zrow]                            # (A,H,T)
    hseq = jnp.arange(n_hops)
    u = jnp.take(hnoise.reshape(-1, W).ravel(),
                 (path_idx[:, None, None] * n_hops
                  + hseq[None, :, None]) * W
                 + hour_rel[anchor_idx][:, None, :])                # (A,H,T)
    ci = ci * (1.0 + 0.02 * band[path_idx][:, :, None] + 0.005 * u)
    prefix = jnp.concatenate(
        [jnp.zeros(ci.shape[:2] + (1,), jnp.float64),
         jnp.cumsum(ci.astype(jnp.float64), axis=2)],
        axis=2)                                                     # (A,H,T+1)
    kk = slot_stride * jnp.arange(n_slots)                          # (S,)
    hh = hseq

    def cell(pids, wd, n, rm, prefix, ci):
        hi = kk + n - 1
        p3, h3 = pids[:, None, None], hh[None, :, None]
        seg = (prefix[p3, h3, jnp.minimum(hi, n_grid)[None, None, :]]
               - prefix[p3, h3, kk[None, None, :]])
        last = ci[p3, h3, jnp.minimum(hi, n_grid - 1)[None, None, :]]
        return (jnp.einsum("lh,lhs->ls", wd, seg) * dt_s
                + jnp.einsum("lh,lhs->ls", wd, last) * rm) / 3.6e6

    vcell = jax.vmap(cell, in_axes=(0, 0, 0, 0, None, None))
    if n_dev > 1:                      # optional scale-out across devices
        if mesh is None:               # undeclared: every visible device
            mesh = jax.sharding.Mesh(np.array(jax.devices()[:n_dev]),
                                     ("cells",))
        axis = mesh.axis_names[0]
        spec = jax.sharding.PartitionSpec
        vcell = jax.shard_map(
            vcell, mesh=mesh,
            in_specs=(spec(axis), spec(axis), spec(axis),
                      spec(axis), spec(), spec()),
            out_specs=spec(axis), check_vma=False)
    return vcell(pair_idx, w_dev, n_steps, rem, prefix, ci)         # (C,2,S)


_kernel_jit = None                     # one compiled-kernel cache per process


def _batch_kernel():
    global _kernel_jit
    if _kernel_jit is None:
        # the mesh is static too: jax.sharding.Mesh hashes by device
        # tuple + axis names, so same declared mesh => same compilation
        _kernel_jit = jax.jit(_kernel, static_argnames=(
            "n_grid", "n_slots", "slot_stride", "dt_s", "n_dev", "mesh"))
    return _kernel_jit


def _iter_chunks(cells: Sequence[CellTask], slot_stride: int,
                 max_elems: int) -> Iterator[List[int]]:
    """Split a fleet of cells into anchor-sorted chunks whose
    pairs*hops*grid element count stays under ``max_elems`` (pathological
    fleets with thousands of distinct anchors would otherwise materialize
    a multi-GB CI grid in one call). Yields lists of original indices —
    shared by the jitted lattice path and the fused Pallas path, so both
    see identical chunk boundaries for a given budget."""
    order = sorted(range(len(cells)),
                   key=lambda i: cells[i].legs[0].anchor)
    i = 0
    while i < len(order):
        chunk: List[int] = []
        pairs: Dict[Tuple, None] = {}
        grid_max = hops_max = 0
        while i < len(order):
            c = cells[order[i]]
            trial = dict(pairs)
            for leg in c.legs:
                # discover_path memoizes paths: identity is a stable key
                trial.setdefault((leg.anchor, id(leg.path)), None)
            g = max(grid_max, (c.n_slots - 1) * slot_stride + c.n_steps)
            h = max(hops_max, max(leg.path.n_hops for leg in c.legs))
            if chunk and len(trial) * h * g > max_elems:
                break
            pairs, grid_max, hops_max = trial, g, h
            chunk.append(order[i])
            i += 1
        yield chunk


def batch_cell_emissions(field: CarbonField, cells: Sequence[CellTask], *,
                         dt_s: float = 60.0, slot_stride: int = 60,
                         shard=None) -> List[np.ndarray]:
    """Score every cell's (leg, start-slot) emission table in one jitted
    call per memory chunk. Returns, per cell, a ``(n_legs, n_slots)`` f64
    array matching ``CarbonField.transfer_emissions_g`` per leg to ~1e-7
    relative (f32 CI chain, f64 time/index math and prefix accumulation).

    ``slot_stride`` is the slot spacing in dt_s steps (the planner's
    ``slot_s / dt_s``; both legs of a cell share the slot/step layout).
    ``shard`` selects the multi-device cell-axis split: ``True`` forces
    it on over every visible device, ``False`` forces it off, a
    :class:`MeshConfig` shards over that declared mesh, and ``None`` uses
    every visible device when there is more than one. A mesh (declared or
    not) that resolves to fewer than two devices falls back to the
    single-device path — the split is a speed knob, never a semantics
    change.
    """
    if not HAVE_JAX:
        raise ImportError("batch_cell_emissions needs jax; use the numpy "
                          "CarbonPlanner.plan_batch oracle instead")
    mesh = None
    if isinstance(shard, MeshConfig):
        devs = shard.devices()
        n_dev = len(devs)
        if n_dev >= 2:
            mesh = shard.build()
        else:
            n_dev = 1
    else:
        n_dev = jax.device_count() if shard is None or shard else 1
        if shard and n_dev < 2:
            n_dev = 1
    out: List[Optional[np.ndarray]] = [None] * len(cells)
    for chunk in _iter_chunks(cells, slot_stride, _MAX_ELEMS):
        for ci_, emis in zip(chunk, _score_chunk(
                field, [cells[j] for j in chunk], dt_s=dt_s,
                slot_stride=slot_stride, n_dev=n_dev, mesh=mesh)):
            out[ci_] = emis
    return out                         # type: ignore[return-value]


@dataclasses.dataclass
class ChunkTables:
    """Host-built padded tables for one anchor-sorted chunk of cells.

    One builder serves both fleet scorers: the jitted lattice kernel
    (:func:`_score_chunk`) and the fused Pallas planner kernel
    (``grid_pallas``) consume the same arrays, so padding/masking
    semantics — zero-weight pad hops, ``n_steps=1`` pad cells, bucketed
    axis lengths — are defined exactly once.
    """
    zcols: Tuple[np.ndarray, ...]      # base/amp/dip/namp/peak (n_z,) f32
    znoise: np.ndarray                 # (n_z, hours) f32, pre-scaled
    cal_a: np.float32
    cal_b: np.float32
    h_of_day0: float                   # t0w-derived traced time constants
    day_frac_s: float
    dow0: int
    zone_idx: np.ndarray               # (n_p, n_hops) i32
    band: np.ndarray                   # (n_p, n_hops) f32
    hnoise: np.ndarray                 # (n_p, n_hops, hours) f32
    rel0a: np.ndarray                  # (n_anch,) f64, anchor - t0w
    anchor_idx: np.ndarray             # (n_a,) i32 pair -> anchor row
    path_idx: np.ndarray               # (n_a,) i32 pair -> path row
    pair_idx: np.ndarray               # (n_c, 2) i32 cell -> pair rows
    w_dev: np.ndarray                  # (n_c, 2, n_hops) f64
    n_steps: np.ndarray                # (n_c,) i32 (pads: 1)
    rem: np.ndarray                    # (n_c,) f64 (pads: 0)
    n_grid_pad: int
    n_slots_pad: int
    n_hops: int
    n_pairs: int                       # live (anchor, path) pairs
    pair_paths: List[NetworkPath]      # per live pair, kernel row order
    pair_anchors: List[float]          # per live pair, kernel row order


def _chunk_tables(field: CarbonField, cells: Sequence[CellTask], *,
                  dt_s: float, slot_stride: int,
                  cell_bucket: int) -> ChunkTables:
    # --- dedupe (anchor, path) pairs and paths ----------------------------
    paths: Dict[Tuple, int] = {}
    path_objs: List[NetworkPath] = []
    anchors: Dict[float, int] = {}
    pair_ids: Dict[Tuple, int] = {}
    pair_path: List[int] = []
    pair_anchor: List[int] = []
    n_grid = 1
    for c in cells:
        n_grid = max(n_grid, (c.n_slots - 1) * slot_stride + c.n_steps)
        for leg in c.legs:
            pk = id(leg.path)          # memoized paths: identity is stable
            if pk not in paths:
                paths[pk] = len(path_objs)
                path_objs.append(leg.path)
            if leg.anchor not in anchors:
                anchors[leg.anchor] = len(anchors)
            ak = (leg.anchor, pk)
            if ak not in pair_ids:
                pair_ids[ak] = len(pair_path)
                pair_path.append(paths[pk])
                pair_anchor.append(anchors[leg.anchor])
    n_hops = max(p.n_hops for p in path_objs)
    n_slots = max(c.n_slots for c in cells)
    zones = sorted({h.zone for p in path_objs for h in p.hops})
    # --- window: one hour-aligned anchor covering every pair's grid -------
    t0w = 3600.0 * math.floor(min(anchors) / 3600.0)
    t_end = max(a + n_grid * dt_s for a in anchors)
    hours = _round_up(int(math.ceil((t_end - t0w) / 3600.0)) + 1, _B_HOURS)
    hour0 = int(t0w // 3600.0)
    hour_idx = np.arange(hour0, hour0 + hours)
    n_z = _round_up(len(zones), _B_ZONES)
    znoise = np.zeros((n_z, hours), dtype=np.float32)
    for zi_, z in enumerate(zones):
        znoise[zi_] = (field._zone_noise.lookup(z, hour_idx) - 0.5) * 2.0
    regs = [REGIONS[z] for z in zones]

    def _zcol(attr):
        col = np.zeros(n_z, dtype=np.float32)
        col[:len(regs)] = [getattr(r, attr) for r in regs]
        return col

    cal_a, cal_b = get_calibration()
    # --- per-path hop tables (padded to n_hops; pads weigh 0) -------------
    n_p = _round_up(len(path_objs), 2)
    zone_idx = np.zeros((n_p, n_hops), dtype=np.int32)
    band = np.zeros((n_p, n_hops), dtype=np.float32)
    hnoise = np.zeros((n_p, n_hops, hours), dtype=np.float32)
    for pi, p in enumerate(path_objs):
        for hi_, h in enumerate(p.hops):
            zone_idx[pi, hi_] = zones.index(h.zone)
            band[pi, hi_] = field._hop_band(h.ip)
            hnoise[pi, hi_] = field._hop_noise.lookup(h.ip, hour_idx) - 0.5
    # --- anchor, pair and cell tables -------------------------------------
    n_anch = _round_up(len(anchors), 32)
    rel0a = np.zeros(n_anch)
    rel0a[:len(anchors)] = np.fromiter(anchors, dtype=np.float64,
                                       count=len(anchors)) - t0w
    n_a = _round_up(len(pair_path), _B_PAIRS)
    path_idx = np.zeros(n_a, dtype=np.int32)
    path_idx[:len(pair_path)] = pair_path
    anchor_idx = np.zeros(n_a, dtype=np.int32)
    anchor_idx[:len(pair_anchor)] = pair_anchor
    n_c = _round_up(len(cells), cell_bucket)
    pair_idx = np.zeros((n_c, 2), dtype=np.int32)
    w_dev = np.zeros((n_c, 2, n_hops))
    n_steps = np.ones(n_c, dtype=np.int32)
    rem = np.zeros(n_c)
    for ci_, c in enumerate(cells):
        for li, leg in enumerate(c.legs):
            pair_idx[ci_, li] = pair_ids[(leg.anchor, id(leg.path))]
            w_dev[ci_, li, :leg.path.n_hops] = leg.w_dev
        n_steps[ci_] = c.n_steps
        rem[ci_] = c.rem_s
    inv_pair: List[Optional[Tuple[float, int]]] = [None] * len(pair_ids)
    for (anchor, _pk), row in pair_ids.items():
        inv_pair[row] = (anchor, pair_path[row])
    return ChunkTables(
        zcols=tuple(_zcol(a) for a in ("base_ci", "diurnal_amp",
                                       "solar_dip", "noise", "peak_hour")),
        znoise=znoise, cal_a=np.float32(cal_a), cal_b=np.float32(cal_b),
        h_of_day0=(t0w / 3600.0) % 24.0,
        day_frac_s=t0w - 86400.0 * math.floor(t0w / 86400.0),
        dow0=int(t0w // 86400.0) % 7,
        zone_idx=zone_idx, band=band, hnoise=hnoise, rel0a=rel0a,
        anchor_idx=anchor_idx, path_idx=path_idx, pair_idx=pair_idx,
        w_dev=w_dev, n_steps=n_steps, rem=rem,
        n_grid_pad=_round_up(n_grid, _GRID_BUCKET),
        n_slots_pad=_round_up(n_slots, _B_SLOTS),
        n_hops=n_hops, n_pairs=len(pair_ids),
        pair_paths=[path_objs[p] for _, p in inv_pair],
        pair_anchors=[a for a, _ in inv_pair])


def chunk_scores(field: CarbonField, cells: Sequence[CellTask], *,
                 dt_s: float, slot_stride: int, n_dev: int,
                 mesh=None) -> "jax.Array":
    """The ``(C_pad, 2, S_pad)`` f64 emission table of one chunk as the
    device array the kernel returns: with ``n_dev > 1`` its cell axis is
    sharded over the mesh, which callers can check through
    ``addressable_shards``."""
    # the cell axis must split evenly across devices for shard_map
    t = _chunk_tables(field, cells, dt_s=dt_s, slot_stride=slot_stride,
                      cell_bucket=math.lcm(_B_CELLS, max(n_dev, 1)))
    with jax.enable_x64(True):
        return _batch_kernel()(
            *t.zcols, t.znoise, t.cal_a, t.cal_b,
            t.h_of_day0, t.day_frac_s, np.int32(t.dow0),
            t.rel0a, t.anchor_idx, t.zone_idx, t.band, t.hnoise,
            t.path_idx, t.pair_idx, t.w_dev, t.n_steps, t.rem,
            n_grid=t.n_grid_pad, n_slots=t.n_slots_pad,
            slot_stride=slot_stride, dt_s=float(dt_s), n_dev=n_dev,
            mesh=mesh)


def _score_chunk(field: CarbonField, cells: Sequence[CellTask], *,
                 dt_s: float, slot_stride: int, n_dev: int,
                 mesh=None) -> List[np.ndarray]:
    emis = np.asarray(chunk_scores(field, cells, dt_s=dt_s,
                                   slot_stride=slot_stride, n_dev=n_dev,
                                   mesh=mesh), dtype=np.float64)
    return [emis[ci_, :len(c.legs), :c.n_slots]
            for ci_, c in enumerate(cells)]
