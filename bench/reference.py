"""Plain reference of the admission decision the benchmark checks.

It imports nothing of the program. Given a job, the FTN overlay and the
deployment's data (each route's hops, each link's capacity, each zone's
carbon-intensity parameters), it enumerates every (FTN, source replica,
start slot) cell, integrates the transfer's emissions step by step in
float64, applies the SLA, and returns the whole scored grid. The model it
computes is the published one the program implements:

* zone CI: diurnal cosine, midday solar dip, weekend factor, hourly
  weather noise hashed with blake2b, floored, then the paper-window affine
  calibration (min 255.714 / max 488.6 gCO2/kWh on the UC->TACC path over
  51 h from 2024-04-14T00:00Z);
* device CI: zone CI times a per-IP sub-metering band and hourly noise;
* power: the linear utilization model of Alan et al. for end systems
  (sender a storage frontend, receiver the FTN's host) and per-bit shares
  of line rate for the devices between them;
* rate: link capacity times the stream-efficiency law, capped by the FTN;
* emissions of a start: sum of per-step power x CI over 60 s steps, the
  last step pro-rated; cost = w_carbon * g + w_perf * (finish - submit) /
  deadline; feasible = finishes by the deadline and within the budget.

``rate_dtype="bfloat16"`` is the control: the same computation with each
step's emission rate stored in bfloat16 and summed in float32.
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

DT_S = 60.0
PAPER_T0 = 1713052800.0
PAPER_HOURS = 51
PAPER_MIN_CI = 255.714
PAPER_MAX_CI = 488.6
UC_TACC_ZONES = ("US-MIDW-MISO",) * 3 + ("US-CENT-SWPP",) * 2 \
    + ("US-TEX-ERCO",) * 3


def steps(dur_s: float) -> int:
    """Whole and partial ``DT_S`` steps a transfer of ``dur_s`` spans."""
    return max(int(math.ceil(dur_s / DT_S - 1e-12)), 1)


def _unit(key: str) -> float:
    d = hashlib.blake2b(key.encode(), digest_size=8).digest()
    return int.from_bytes(d, "big") / 2**64


@dataclasses.dataclass(frozen=True)
class Job:
    """A transfer request as the benchmark generated it."""
    uuid: str
    size_bytes: float
    replicas: Tuple[str, ...]
    dst: str
    deadline_s: float
    submitted_t: float
    w_carbon: float = 1.0
    w_perf: float = 0.0
    budget_g: Optional[float] = None
    parallelism: int = 4
    concurrency: int = 2


@dataclasses.dataclass
class Cell:
    """One (FTN, source) cell of a job: its legs and every start slot."""
    ftn: str
    source: str
    legs: Tuple[Tuple[str, str], ...]
    gbps: float
    dur_s: float
    starts: np.ndarray                 # (S,) start times
    emis_g: Optional[np.ndarray] = None
    cost: Optional[np.ndarray] = None
    feasible: Optional[np.ndarray] = None


class Deployment:
    """The deployment's data and physical constants.

    ``routes`` maps "src>dst" to a list of hops [ip, zone, org];
    ``capacity`` maps "src>dst" to Gbps; ``zones`` maps a zone id to
    [base, amp, dip, noise, peak_hour]; ``config`` is the configuration
    file (FTNs, host profiles, hop classes, slot length)."""

    def __init__(self, config: dict, routes: Dict[str, list],
                 capacity: Dict[str, float], zones: Dict[str, list]):
        self.ftns = [(f["name"], f["profile"], float(f["max_gbps"]))
                     for f in config["ftns"]]
        self.hosts = config["host_profiles"]
        self.hop_classes = config["hop_classes"]
        self.org_class = config["org_class"]
        self.sender = config["sender_profile"]
        self.slot_s = float(config["slot_s"])
        self.routes = routes
        self.capacity = capacity
        self.zones = zones
        self._noise: Dict[Tuple[str, int], float] = {}
        self._band: Dict[str, float] = {}
        self.cal = self._calibration()

    # --- carbon intensity ---------------------------------------------
    def _hourly(self, key: str, hours: np.ndarray) -> np.ndarray:
        out = np.empty(hours.shape)
        flat = out.reshape(-1)
        for i, h in enumerate(hours.reshape(-1)):
            k = (key, int(h))
            u = self._noise.get(k)
            if u is None:
                u = self._noise[k] = _unit(f"{key}:{int(h)}")
            flat[i] = u
        return out

    def raw_ci(self, zones: Sequence[str], t: np.ndarray) -> np.ndarray:
        """Uncalibrated CI of each zone at times ``t``: (zones,) + t.shape."""
        p = np.array([self.zones[z] for z in zones]).T[(...,) + (None,)
                                                        * t.ndim]
        base, amp, dip, noise, peak = p
        hod = (t / 3600.0) % 24.0
        v = base + amp * np.cos(2 * np.pi * (hod - peak) / 24.0)
        v = v - dip * np.exp(-0.5 * ((hod - 13.0) / 2.5) ** 2)
        dow = np.floor(t / 86400.0).astype(np.int64) % 7
        v = np.where(dow >= 5, v * 0.94, v)
        u = self._noise_at(zones, t)
        return np.maximum(v + noise * (u - 0.5) * 2.0, 1.0)

    def _noise_at(self, keys: Sequence[str], t: np.ndarray) -> np.ndarray:
        """Hourly noise in [0, 1) of each key at times ``t``."""
        hours = np.floor(t / 3600.0).astype(np.int64)
        uniq, inv = np.unique(hours, return_inverse=True)
        table = np.stack([self._hourly(k, uniq) for k in keys])
        return table[:, inv].reshape((len(keys),) + t.shape)

    def _calibration(self) -> Tuple[float, float]:
        t = PAPER_T0 + 3600.0 * np.arange(PAPER_HOURS)
        avg = self.raw_ci(UC_TACC_ZONES, t).mean(axis=0)
        lo, hi = float(avg.min()), float(avg.max())
        a = (PAPER_MAX_CI - PAPER_MIN_CI) / (hi - lo)
        return a, PAPER_MIN_CI - a * lo

    def device_ci(self, hops: Sequence[Sequence[str]], t: np.ndarray
                  ) -> np.ndarray:
        """CI of each device [ip, zone, org] at times ``t``: the zone's
        calibrated CI times the device's band and hourly noise."""
        a, b = self.cal
        ips = [h[0] for h in hops]
        zci = np.maximum(a * self.raw_ci([h[1] for h in hops], t) + b, 0.5)
        for ip in ips:
            if ip not in self._band:
                self._band[ip] = _unit(ip) - 0.5
        band = np.array([self._band[ip] for ip in ips])[
            (...,) + (None,) * t.ndim]
        u = self._noise_at(ips, t) - 0.5
        return zci * (1.0 + 0.02 * band + 0.005 * u)

    # --- power and rate -----------------------------------------------
    def host_power_w(self, profile: str, gbps: float, streams: int
                     ) -> float:
        idle, cpu_w, mem_w, nic_w, nic = self.hosts[profile]
        cpu = min(0.05 + 0.02 * streams + 0.4 * gbps / nic, 1.0)
        mem = min(0.10 + 0.05 * gbps / nic, 1.0)
        return (idle + cpu_w * min(max(cpu, 0.0), 1.0)
                + mem_w * min(max(mem, 0.0), 1.0)
                + nic_w * min(gbps / nic, 1.0))

    def hop_power_w(self, org: str, gbps: float) -> float:
        port_w, line = self.hop_classes[self.org_class.get(org, "campus")]
        return port_w * min(gbps / line, 1.0)

    def leg_weights(self, leg: Tuple[str, str], receiver: str,
                    gbps: float, streams: int) -> np.ndarray:
        hops = self.routes[f"{leg[0]}>{leg[1]}"]
        w = np.empty(len(hops))
        w[0] = self.host_power_w(self.sender, gbps, streams)
        w[-1] = self.host_power_w(receiver, gbps, streams)
        for i, (_ip, _zone, org) in enumerate(hops[1:-1], start=1):
            w[i] = self.hop_power_w(org, gbps)
        return w

    def gbps(self, job: Job, legs: Sequence[Tuple[str, str]],
             ftn_max: float) -> float:
        streams = max(job.parallelism * job.concurrency, 1)
        eff = 1.0 - 0.55 * math.exp(-(streams - 1) / 3.0)
        g = min(max(self.capacity[f"{a}>{b}"] * eff, 1e-3) for a, b in legs)
        return min(g, ftn_max)

    # --- the grid ---------------------------------------------------------
    def cells(self, job: Job) -> List[Cell]:
        """Every (FTN, source) cell in overlay order, with its slots."""
        out = []
        deadline_t = job.submitted_t + job.deadline_s
        for name, _profile, max_gbps in self.ftns:
            for src in job.replicas:
                legs = [(src, name)]
                if name != job.dst:
                    legs.append((name, job.dst))
                g = self.gbps(job, legs, max_gbps)
                dur = job.size_bytes * 8.0 / (g * 1e9)
                latest = deadline_t - dur
                n = 1
                if latest + 1e-9 >= job.submitted_t:
                    n = int((latest + 1e-9 - job.submitted_t)
                            // self.slot_s) + 1
                out.append(Cell(ftn=name, source=src, legs=tuple(legs),
                                gbps=g, dur_s=dur,
                                starts=job.submitted_t
                                + self.slot_s * np.arange(n)))
        return out

    def leg_emissions(self, job: Job, cell: Cell, leg: Tuple[str, str],
                      receiver: str, rate_dtype: Optional[str]
                      ) -> np.ndarray:
        """Grams of CO2 for every start of one leg: the per-step rate
        summed over the transfer's steps, the last step pro-rated."""
        n = steps(cell.dur_s)
        rem = cell.dur_s - (n - 1) * DT_S
        streams = job.parallelism * job.concurrency
        w = self.leg_weights(leg, receiver, cell.gbps, streams)
        t = cell.starts[:, None] + DT_S * np.arange(n)[None, :]   # (S, n)
        ci = self.device_ci(self.routes[f"{leg[0]}>{leg[1]}"], t)
        rate = (w[:, None, None] * ci).sum(axis=0) / 3.6e6   # g/s
        step = np.full(n, DT_S)
        step[-1] = rem
        if rate_dtype is None:
            return (rate * step).sum(axis=1)
        import ml_dtypes
        low = rate.astype(getattr(ml_dtypes, rate_dtype)).astype(np.float32)
        return (low * step.astype(np.float32)).sum(axis=1, dtype=np.float32
                                                   ).astype(np.float64)

    def score(self, job: Job, rate_dtype: Optional[str] = None
              ) -> List[Cell]:
        """The job's whole grid, scored: emissions, cost and feasibility
        of every start slot of every cell."""
        profile = {name: prof for name, prof, _ in self.ftns}
        deadline_t = job.submitted_t + job.deadline_s
        cells = self.cells(job)
        for c in cells:
            c.emis_g = sum(self.leg_emissions(job, c, leg, profile[c.ftn],
                                              rate_dtype)
                           for leg in c.legs)
            c.feasible = c.starts + c.dur_s <= deadline_t + 1e-9
            if job.budget_g is not None:
                c.feasible &= c.emis_g <= job.budget_g
            c.cost = (job.w_carbon * c.emis_g
                      + job.w_perf * (c.starts + c.dur_s - job.submitted_t)
                      / max(job.deadline_s, 1.0))
        return cells


def best(cells: Sequence[Cell]) -> Optional[Tuple[Cell, int]]:
    """The feasible (cell, slot) of least cost; first in overlay order
    on a tie. None when no slot is feasible."""
    out, best_cost = None, math.inf
    for c in cells:
        cost = np.where(c.feasible, c.cost, np.inf)
        i = int(np.argmin(cost))
        if cost[i] < best_cost:
            out, best_cost = (c, i), float(cost[i])
    return out


def lookup(cells: Sequence[Cell], ftn: str, source: str, start_t: float
           ) -> Optional[Tuple[Cell, int]]:
    """The (cell, slot) a plan chose, or None when the grid has no such
    start."""
    for c in cells:
        if c.ftn == ftn and c.source == source:
            i = int(round((start_t - c.starts[0]) / (
                c.starts[1] - c.starts[0]))) if len(c.starts) > 1 else 0
            if 0 <= i < len(c.starts) and abs(c.starts[i] - start_t) < 1e-6:
                return c, i
            return None
    return None
