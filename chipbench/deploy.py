"""Builds one cell's deployment from its configuration and traffic files.

A configuration (``configs/<name>.json``) is a fleet and a population:
metro centres, edge nodes per metro and their parameter ranges, one
running replica of the service per node, and users spread around the
metros.  A traffic mix (``traffic/<name>.json``) is the probe period, the
frame interval, the node churn and the candidate-refresh policy.
Everything random is drawn from the run's seed.

Churn follows the traffic file: each node alternates exponential up
and down times (``ChurnModel``'s distribution), starting in the steady
state.  The schedule is drawn once from the file's own
``schedule_seed``, per volunteer and per dedicated slot, and slot ``i``
of a class is that class's ``i``-th node of the seed's fleet: every seed
gets the same failures at the same instants, bursts included, on other
nodes.

The fleet and population follow ``bench_client_scale._system`` and
``bench_mesh_scale._build_system`` (copied here, so that the yardstick
does not move when those benchmarks change), with one difference: the
points are placed a fixed number per region shard (``cell_strata``).
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


def load_json(kind: str, name: str) -> dict:
    """``<kind>/<name>.json`` under this directory."""
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Fleet:
    """The nodes as the reference sees them, in task order."""
    names: list
    lat: np.ndarray
    lon: np.ndarray
    proc_ms: np.ndarray
    slots: np.ndarray
    dedicated: np.ndarray
    net: list


@dataclasses.dataclass
class Deployment:
    cfg: dict
    traffic: dict
    system: object          # repro.core.beacon.ArmadaSystem
    pool: object            # repro.core.client_pool.ClientPool
    churn: "ScheduledChurn"
    fleet: Fleet
    user_locs: np.ndarray   # (U, 2) float64
    user_net: str

    @property
    def sim(self):
        return self.system.sim

    @property
    def period_ms(self) -> float:
        return float(self.traffic["probe_period_ms"])

    @property
    def captains(self) -> list:
        caps = self.system.captains
        return [caps[n] for n in self.fleet.names]


def cell_strata(cfg: dict, spread: float) -> list:
    """The parts of each metro's square (centre +- ``spread`` degrees)
    cut by the geohash cells of ``shard_precision`` (one part per metro
    when the fleet is unsharded), as ``(metro, lat0, lat1, lon0, lon1)``.
    Points are placed a fixed number per part, in proportion to its
    area, so that every seed gives each region shard the same number of
    nodes and users (the shards' program shapes) in other places."""
    p = cfg["shard_precision"]
    lon_cells = 2 ** ((5 * p + 1) // 2) if p else 1
    lat_cells = 2 ** (5 * p // 2) if p else 1

    def cuts(lo, hi, origin, size):
        edges = origin + size * np.arange(np.floor((lo - origin) / size) + 1,
                                          np.ceil((hi - origin) / size))
        pts = [lo, *edges[(edges > lo) & (edges < hi)], hi]
        return list(zip(pts[:-1], pts[1:]))

    out = []
    for m, (clat, clon) in enumerate(cfg["metros"]):
        for a, b in cuts(clat - spread, clat + spread, -90.0,
                         180.0 / lat_cells):
            for c, d in cuts(clon - spread, clon + spread, -180.0,
                             360.0 / lon_cells):
                out.append((m, a, b, c, d))
    return out


def place(cfg: dict, spread: float, per_metro: int, rng) -> np.ndarray:
    """(metros x per_metro, 2) points uniform over each metro's square,
    a fixed count in each of its ``cell_strata``, metro by metro."""
    strata = cell_strata(cfg, spread)
    out = []
    for m in range(len(cfg["metros"])):
        parts = [s for s in strata if s[0] == m]
        area = np.asarray([(b - a) * (d - c) for _, a, b, c, d in parts])
        share = area / area.sum() * per_metro
        count = np.floor(share).astype(int)
        rest = np.argsort(-(share - count), kind="stable")
        count[rest[:per_metro - count.sum()]] += 1
        for (_, a, b, c, d), n in zip(parts, count):
            out.append(np.stack([rng.uniform(a, b, n), rng.uniform(c, d, n)],
                                axis=1))
    return np.concatenate(out)


def n_dedicated(cfg: dict) -> int:
    """Dedicated nodes in the fleet: a fixed count, so that every seed
    churns the same number of volunteers."""
    n = cfg["nodes_per_metro"] * len(cfg["metros"])
    return int(round(cfg["dedicated_share"] * n))


def make_fleet(cfg: dict, seed: int) -> Fleet:
    rng = np.random.default_rng(seed)
    n = cfg["nodes_per_metro"] * len(cfg["metros"])
    loc = place(cfg, cfg["node_spread_deg"], cfg["nodes_per_metro"], rng)
    p_lo, p_hi = cfg["proc_ms"]
    s_lo, s_hi = cfg["slots"]
    nets = cfg["node_nets"]
    return Fleet(
        names=[f"N{i}" for i in range(n)], lat=loc[:, 0], lon=loc[:, 1],
        proc_ms=rng.uniform(p_lo, p_hi, n),
        slots=rng.integers(s_lo, s_hi + 1, n),
        dedicated=np.isin(np.arange(n), rng.permutation(n)[:n_dedicated(cfg)]),
        net=[nets[i] for i in rng.integers(len(nets), size=n)])


def make_users(cfg: dict, seed: int) -> np.ndarray:
    """(U, 2) user locations: ``users`` split evenly over the metros,
    uniform within ``user_spread_deg`` of each centre."""
    per_metro, rest = divmod(cfg["users"], len(cfg["metros"]))
    if rest:
        raise ValueError("users must split evenly over the metros")
    rng = np.random.default_rng([seed, 1])
    return place(cfg, cfg["user_spread_deg"], per_metro, rng)


def build_system(cfg: dict, fleet: Fleet, seed: int):
    """The Armada system over ``fleet`` with one running replica of the
    service per node, registered directly (no image pulls)."""
    from repro.core.app_manager import ServiceSpec, Task
    from repro.core.beacon import ArmadaSystem, detection_image
    from repro.core.cluster import NodeSpec, Topology

    nodes = {}
    for i, name in enumerate(fleet.names):
        nodes[name] = NodeSpec(
            name, (float(fleet.lat[i]), float(fleet.lon[i])),
            proc_ms=float(fleet.proc_ms[i]), slots=int(fleet.slots[i]),
            dedicated=bool(fleet.dedicated[i]), net_type=fleet.net[i])
    system = ArmadaSystem(Topology(nodes, {}), seed=seed,
                          trace_enabled=False, include_cloud_compute=False,
                          shard_precision=cfg["shard_precision"])
    service = cfg["service"]
    am = system.am
    am.services[service] = ServiceSpec(service, detection_image())
    am.tasks[service] = []
    am.users[service] = []
    for i, name in enumerate(fleet.names):
        cap = system.captains[name]
        task = Task(f"{service}/t{i}", service, captain=cap,
                    status="running", ready_at=0.0)
        cap.tasks[task.task_id] = task
        am.tasks[service].append(task)
    am.autoscale_enabled = False
    return system


def pool_options(traffic: dict, n_users: int) -> dict:
    """``make_client_pool`` options the traffic file states."""
    period = float(traffic["probe_period_ms"])
    kw = dict(transport="fluid", selection_backend="geo_topk",
              tick="device", record_samples=False,
              probe_period_ms=period,
              frame_interval_ms=float(traffic["frame_interval_ms"]))
    refresh = traffic["refresh"]
    if refresh["policy"] == "incremental":
        kw["refresh_period_ms"] = refresh["period_probes"] * period
        kw["refresh_cap"] = max(128, n_users // refresh["cap_divisor"])
    elif refresh["policy"] != "every_tick":
        raise ValueError(f"unknown refresh policy {refresh['policy']!r}")
    return kw


class ScheduledChurn:
    """Node failures and recoveries on the traffic file's schedule.

    Every node alternates an up time drawn from an exponential of its
    class's mean time to failure and a down time drawn from an
    exponential of the mean time to repair.  A node starts down with the
    steady-state probability ``mttr / (mttf + mttr)``; such nodes fail
    before the pool starts.  Events closer than ``EDGE_MS`` to a probe
    tick are moved past it, so that every tick sees a settled fleet."""

    EDGE_MS = 2.0

    def __init__(self, sim, captains: list, dedicated, traffic: dict):
        c = traffic["churn"]
        if c["distribution"] != "exponential":
            raise ValueError(f"unknown churn {c['distribution']!r}")
        self.sim = sim
        self.captains = captains
        self.period = float(traffic["probe_period_ms"])
        self.horizon = c["horizon_probes"] * self.period
        rng = np.random.default_rng(c["schedule_seed"])
        dedicated = np.asarray(dedicated, bool)
        times, nodes, kinds = [], [], []
        self.down0 = []
        for is_ded in (False, True):
            mttf = c["dedicated_mttf_ms" if is_ded else "volunteer_mttf_ms"]
            for node in np.nonzero(dedicated == is_ded)[0]:
                t, up = 0.0, rng.random() >= c["mttr_ms"] / (
                    mttf + c["mttr_ms"])
                if not up:
                    self.down0.append(int(node))
                while True:
                    t = self._clear(t + rng.exponential(
                        mttf if up else c["mttr_ms"]))
                    if t >= self.horizon:
                        break
                    up = not up
                    times.append(t)
                    nodes.append(int(node))
                    kinds.append("join" if up else "leave")
        order = np.argsort(times, kind="stable")
        self.plan = [(times[i], nodes[i], kinds[i]) for i in order]
        self.next = 0
        self.events: list = []

    def _clear(self, t: float) -> float:
        phase = t % self.period
        if phase < self.EDGE_MS:
            return t - phase + self.EDGE_MS
        if phase > self.period - self.EDGE_MS:
            return t - phase + self.period + self.EDGE_MS
        return t

    def start(self) -> None:
        """Fails the nodes that start down (before the pool starts) and
        schedules the rest period by period."""
        for i in self.down0:
            self.captains[i].fail()
            self.events.append({"t": 0.0, "node": i, "kind": "leave"})
        self.sim.at(0.0, self._plan)

    def _plan(self) -> None:
        now = self.sim.now
        if now + self.period > self.horizon:
            raise RuntimeError("the run outlasted the churn schedule")
        end = now + self.period
        while self.next < len(self.plan) and self.plan[self.next][0] < end:
            t, node, kind = self.plan[self.next]
            self.sim.at(t, self._event, node, kind)
            self.next += 1
        self.sim.at(end, self._plan)

    def _event(self, i: int, kind: str) -> None:
        cap = self.captains[i]
        if kind == "leave":
            cap.fail()
        else:
            cap.recover()
        self.events.append({"t": self.sim.now, "node": i, "kind": kind})


def build(cfg: dict, traffic: dict, seed: int, timings: dict) -> Deployment:
    """The whole deployment with its clock at 0; the pool starts at t=0.
    ``timings['fleet_s']`` gets the host time of the fleet build."""
    import time

    t0 = time.perf_counter()
    fleet = make_fleet(cfg, seed)
    system = build_system(cfg, fleet, seed)
    timings["fleet_s"] = time.perf_counter() - t0

    locs = make_users(cfg, seed)
    pool = system.make_client_pool(
        cfg["service"], locs=locs, nets=cfg["user_net"],
        switch_margin=cfg["switch_margin"], ema_alpha=cfg["ema_alpha"],
        workload_scale=cfg["workload_scale"], ema_slots=cfg["ema_slots"],
        **pool_options(traffic, len(locs)))
    if pool.cand_task.shape[1] != cfg["top_n"]:
        raise RuntimeError(f"pool keeps {pool.cand_task.shape[1]} "
                           f"candidates, the configuration {cfg['top_n']}")
    churn = ScheduledChurn(system.sim,
                           [system.captains[n] for n in fleet.names],
                           fleet.dedicated, traffic)
    churn.start()
    system.sim.at(0.0, pool.start)
    return Deployment(cfg, traffic, system, pool, churn, fleet, locs,
                      cfg["user_net"])
