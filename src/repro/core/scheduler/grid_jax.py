"""The admission sweep's host-side cell table and chunk layout.

``CarbonPlanner.plan_batch_jax`` stacks every (job, FTN, replica) cell of
a sweep into one columnar :class:`CellTable`; :func:`_iter_chunks` cuts
it into anchor-sorted chunks under a memory budget, and
:func:`_chunk_tables` lays each chunk out as the padded tables
(:class:`ChunkTables`) that the fused Pallas kernels of ``grid_pallas``
consume. Nothing here runs on a device.

Layer contract: **numpy is the pinned oracle**. The device tier recomputes
what ``CarbonField`` (and through it ``CarbonPlanner.plan`` /
``plan_batch``) already defines on numpy, and must pick the same cells
with emissions within 1e-4 relative (``tests/test_grid_pallas.py``,
``tests/test_controlplane.py``). Padding and masking are defined here
once: zero-weight pad hops, ``n_steps=1`` pad cells, bucketed axis
lengths.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Iterator, List, Tuple

import numpy as np

from repro.core.carbon.field import CarbonField
from repro.core.carbon.intensity import REGIONS, get_calibration
from repro.core.carbon.path import NetworkPath

try:                                   # jax is optional: numpy stays the oracle
    import jax  # noqa: F401
    HAVE_JAX = True
except ImportError:                    # pragma: no cover - env without jax
    HAVE_JAX = False

_GRID_BUCKET = 512                     # rate-grid length rounding
_B_PAIRS = 64                          # (anchor, path) axis bucket
_B_CELLS = 64                          # job-cell axis bucket
_B_SLOTS = 16                          # start-slot axis bucket
_B_HOURS = 168                         # window-hours bucket (one week)
_B_ZONES = 8                           # zone axis bucket
_MAX_GRID = 1 << 15                    # per-cell rate-grid cap (~22 days)
_MAX_ELEMS = 32 * 1024 * 1024          # pairs*hops*grid budget per chunk


@dataclasses.dataclass(frozen=True)
class LegTask:
    """One leg of one grid cell: a path plus its device-power weights."""
    path: NetworkPath
    anchor: float                      # grid anchor (the job's first slot)
    w_dev: np.ndarray                  # (n_hops,) device power draw, W


@dataclasses.dataclass(frozen=True)
class CellTask:
    """One (job, FTN, replica) cell: 1–2 legs sharing a slot/step layout
    (a row of a :class:`CellTable`, built on demand)."""
    legs: Tuple[LegTask, ...]
    n_slots: int                       # candidate starts: anchor + k*slot
    n_steps: int                       # dt_s steps per transfer
    rem_s: float                       # pro-rated final-step seconds


@dataclasses.dataclass(frozen=True, eq=False)
class CellTable:
    """An admission sweep's (job, FTN, replica) cells as columns, in the
    planner's order (job-major, then FTN, then replica).

    The layout reads the columns directly; ``len``, indexing and
    iteration give :class:`CellTask` rows, built on demand for callers
    that want objects (never on the scoring path)."""
    paths: Tuple[NetworkPath, ...]     # path id -> memoized path
    legs: np.ndarray                   # (C, 2) i32 path id per leg, -1: none
    anchor: np.ndarray                 # (C,) f64 grid anchor (first slot)
    n_slots: np.ndarray                # (C,) i64 candidate starts
    n_steps: np.ndarray                # (C,) i64 dt_s steps per transfer
    rem_s: np.ndarray                  # (C,) f64 pro-rated final step
    w_dev: np.ndarray                  # (C, 2, H) f64 hop power, pads 0

    def __len__(self) -> int:
        return len(self.anchor)

    def __getitem__(self, i: int) -> CellTask:
        legs = tuple(
            LegTask(path=self.paths[p], anchor=float(self.anchor[i]),
                    w_dev=self.w_dev[i, li, :self.paths[p].n_hops])
            for li, p in enumerate(self.legs[i]) if p >= 0)
        return CellTask(legs=legs, n_slots=int(self.n_slots[i]),
                        n_steps=int(self.n_steps[i]),
                        rem_s=float(self.rem_s[i]))

    def __iter__(self) -> Iterator[CellTask]:
        return (self[i] for i in range(len(self)))

    def take(self, idx) -> "CellTable":
        """The cells at ``idx``, in that order (paths shared)."""
        return CellTable(self.paths, self.legs[idx], self.anchor[idx],
                         self.n_slots[idx], self.n_steps[idx],
                         self.rem_s[idx], self.w_dev[idx])

    def n_legs(self) -> np.ndarray:
        return 1 + (self.legs[:, 1] >= 0)

    def hops(self) -> np.ndarray:
        """(C,) hop count of each cell's longest leg."""
        n = np.array([p.n_hops for p in self.paths] + [0])
        return np.maximum(n[self.legs[:, 0]], n[self.legs[:, 1]])


def _round_up(n: int, b: int) -> int:
    return int(math.ceil(max(n, 1) / b)) * b


def _first_rank(first: np.ndarray) -> np.ndarray:
    """Rank of each ``np.unique`` value by its first occurrence."""
    rank = np.empty(len(first), dtype=np.int64)
    rank[np.argsort(first, kind="stable")] = np.arange(len(first))
    return rank


def _leg_pairs(cells: CellTable) -> Tuple[np.ndarray, np.ndarray,
                                          np.ndarray]:
    """The live (cell, leg) entries in cell-then-leg order: their cell
    index, anchor code (index into the sorted distinct anchors) and
    (anchor, path) pair code."""
    live = cells.legs >= 0
    cell_of = np.nonzero(live)[0]
    ua, acode = np.unique(cells.anchor[cell_of], return_inverse=True)
    pcode = acode * len(cells.paths) + cells.legs[live]
    return cell_of, acode, pcode


def _iter_chunks(cells: CellTable, slot_stride: int,
                 max_elems: int) -> Iterator[np.ndarray]:
    """Split a fleet of cells into anchor-sorted chunks whose
    pairs*hops*grid element count stays under ``max_elems`` (pathological
    fleets with thousands of distinct anchors would otherwise materialize
    a multi-GB rate table in one call). Yields arrays of original
    indices.

    A chunk grows greedily in anchor order while its distinct (anchor,
    path) pairs x its longest leg's hops x its longest rate grid fits;
    all three are running counts over the sorted columns."""
    n = len(cells)
    if not n:
        return
    order = np.argsort(cells.anchor, kind="stable")
    s = cells.take(order)
    grid = (s.n_slots - 1) * slot_stride + s.n_steps
    hops = s.hops()
    cell_of, _, pcode = _leg_pairs(s)
    # previous entry of the same pair (-1: none); a pair counts as new in
    # a chunk starting at cell c0 when its previous entry lies before c0
    by = np.argsort(pcode, kind="stable")
    prev = np.full(len(pcode), -1, dtype=np.int64)
    same = pcode[by[1:]] == pcode[by[:-1]]
    prev[by[1:][same]] = cell_of[by[:-1][same]]
    c0 = 0
    while c0 < n:
        tail = cell_of >= c0
        new = np.bincount(cell_of[tail & (prev < c0)] - c0,
                          minlength=n - c0)
        elems = (np.cumsum(new)
                 * np.maximum.accumulate(hops[c0:])
                 * np.maximum.accumulate(grid[c0:]))
        over = np.flatnonzero(elems[1:] > max_elems)
        c1 = c0 + 1 + (int(over[0]) if len(over) else n - c0 - 1)
        yield order[c0:c1]
        c0 = c1


@dataclasses.dataclass
class ChunkTables:
    """Host-built padded tables for one anchor-sorted chunk of cells,
    which ``grid_pallas._layout`` lays out slot-major for its kernels."""
    zcols: Tuple[np.ndarray, ...]      # base/amp/dip/namp/peak (n_z,) f32
    znoise: np.ndarray                 # (n_z, hours) f32, pre-scaled
    cal_a: np.float32
    cal_b: np.float32
    h_of_day0: float                   # t0w-derived time constants
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
    hop_keys: int                      # distinct hop keys (``Hop.ip``)
    pair_paths: List[NetworkPath]      # per live pair, kernel row order
    pair_anchors: List[float]          # per live pair, kernel row order


def _chunk_tables(field: CarbonField, cells: CellTable, *,
                  dt_s: float, slot_stride: int,
                  cell_bucket: int) -> ChunkTables:
    # --- dedupe paths, anchors and (anchor, path) pairs, each row in the
    # order of its first (cell, leg) entry -------------------------------
    cell_of, acode, pcode = _leg_pairs(cells)
    flat_path = cells.legs[cells.legs >= 0]
    upath, pfirst = np.unique(flat_path, return_index=True)
    path_row = np.zeros(len(cells.paths), dtype=np.int64)
    path_row[upath] = _first_rank(pfirst)
    path_objs = [cells.paths[p] for p in upath[np.argsort(pfirst)]]
    _, afirst = np.unique(acode, return_index=True)
    anchor_row = _first_rank(afirst)
    anchors = cells.anchor[cell_of[np.sort(afirst)]]
    _, qfirst, qinv = np.unique(pcode, return_index=True,
                                return_inverse=True)
    pair_row = _first_rank(qfirst)[qinv]               # per (cell, leg)
    qorder = np.sort(qfirst)                           # first entry per row
    pair_path = path_row[flat_path[qorder]]
    pair_anchor = anchor_row[acode[qorder]]
    n_grid = max(1, int(np.max((cells.n_slots - 1) * slot_stride
                               + cells.n_steps)))
    n_hops = max(p.n_hops for p in path_objs)
    n_slots = int(np.max(cells.n_slots))
    hops = [h for p in path_objs for h in p.hops]       # path-major
    hop_zone = [h.zone for h in hops]
    zones = sorted(set(hop_zone))
    # --- window: one hour-aligned anchor covering every pair's grid -------
    t0w = 3600.0 * math.floor(float(np.min(anchors)) / 3600.0)
    t_end = float(np.max(anchors + n_grid * dt_s))
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
    # --- per-path hop tables (padded to n_hops; pads weigh 0): one noise
    # row and band per distinct hop key, gathered per (path, hop) ---------
    n_p = _round_up(len(path_objs), 2)
    live = np.zeros((n_p, n_hops), dtype=bool)          # row-major = hops
    live[:len(path_objs)] = (np.arange(n_hops)[None, :] < np.array(
        [p.n_hops for p in path_objs])[:, None])
    zone_row = {z: zi_ for zi_, z in enumerate(zones)}
    key_row: Dict[str, int] = {}
    zone_idx = np.zeros((n_p, n_hops), dtype=np.int32)
    zone_idx[live] = [zone_row[z] for z in hop_zone]
    hop_key = np.full((n_p, n_hops), -1, dtype=np.int64)   # -1: pad row
    hop_key[live] = [key_row.setdefault(h.ip, len(key_row)) for h in hops]
    key_noise = np.zeros((len(key_row) + 1, hours), dtype=np.float32)
    key_band = np.zeros(len(key_row) + 1, dtype=np.float32)
    for k, ip in enumerate(key_row):
        key_noise[k] = field._hop_noise.lookup(ip, hour_idx) - 0.5
        key_band[k] = field._hop_band(ip)
    hnoise = key_noise[hop_key]
    band = key_band[hop_key]
    # --- anchor, pair and cell tables -------------------------------------
    n_anch = _round_up(len(anchors), 32)
    rel0a = np.zeros(n_anch)
    rel0a[:len(anchors)] = anchors - t0w
    n_a = _round_up(len(pair_path), _B_PAIRS)
    path_idx = np.zeros(n_a, dtype=np.int32)
    path_idx[:len(pair_path)] = pair_path
    anchor_idx = np.zeros(n_a, dtype=np.int32)
    anchor_idx[:len(pair_anchor)] = pair_anchor
    n_live = len(cells)
    n_c = _round_up(n_live, cell_bucket)
    pair_idx = np.zeros((n_c, 2), dtype=np.int32)
    pair_idx[:n_live][cells.legs >= 0] = pair_row
    w_dev = np.zeros((n_c, 2, n_hops))
    w_dev[:n_live] = cells.w_dev[:, :, :n_hops]
    n_steps = np.ones(n_c, dtype=np.int32)
    n_steps[:n_live] = cells.n_steps
    rem = np.zeros(n_c)
    rem[:n_live] = cells.rem_s
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
        n_hops=n_hops, n_pairs=len(pair_path), hop_keys=len(key_row),
        pair_paths=[path_objs[p] for p in pair_path],
        pair_anchors=anchors[pair_anchor].tolist())
