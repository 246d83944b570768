"""Mesh-sharded ClientPool: million-user fused tick across devices.

The tentpole scale-out bench: the full client data plane (probing,
EMA folds, two-round switches, failover under volunteer churn) runs
through ``ClientPool(tick="device", mesh=4)`` — the SoA state lives on a
1-D ``jax.sharding`` mesh, users sharded by home region so each device
executes the fused tick over only its own region shards
(``repro.core.fused_tick.MeshTickDriver``).

Two cases per profile:

* ``single_d1`` — the PR-6 fused single-device tick at the per-device
  population (the weak-scaling baseline);
* ``mesh_d4``   — 4× the population on a 4-device mesh, same per-device
  share, with churn live.

On the CPU every case runs in a *subprocess* with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``: the flag must be
set before jax initialises, and the parent runner's jax is already up
with one device.  Forced host devices share this machine's physical
cores (``physical_cores`` is recorded in every row), so the honest
weak-scaling number is the *normalized* ratio ``D x t_single / t_mesh``
emitted by the ``derive`` hook.  On an accelerator the cases run in the
calling process on its real devices: a chip belongs to one process, and
the caller already holds it.

``run(smoke=True)`` (or ``--smoke``) is the seconds-scale tier-1
multi-device profile; the full sweep is the acceptance shape
(1M users x 10k nodes on 4 devices, 250k x 10k single-device baseline),
with per-phase wall-time breakdowns in every row.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np

# the four metros of bench_beacon_failover, distinct precision-3 cells
REGIONS = ((44.97, -93.22), (41.88, -87.63), (39.74, -104.99),
           (32.78, -96.80))
SHARD_PRECISION = 3
SERVICE = "detect"
PROBE_MS = 2000.0
FRAME_MS = 500.0
N_DEVICES = 4
_ROOT = pathlib.Path(__file__).resolve().parents[1]
_ROW = "##ROW##"


# --------------------------------------------------------------- child side


def _build_system(n_per_region: int, n_regions: int, seed: int):
    from repro.core.app_manager import ServiceSpec, Task
    from repro.core.beacon import ArmadaSystem, detection_image
    from repro.core.cluster import NodeSpec, Topology

    rng = np.random.default_rng(seed)
    nets = ("wifi", "ethernet", "lte")
    nodes = {}
    for r in range(n_regions):
        base = REGIONS[r % len(REGIONS)]
        for i in range(n_per_region):
            nid = f"R{r}N{i}"
            nodes[nid] = NodeSpec(
                nid, (base[0] + float(rng.uniform(-0.3, 0.3)),
                      base[1] + float(rng.uniform(-0.3, 0.3))),
                proc_ms=float(rng.uniform(10, 30)),
                slots=int(rng.integers(2, 9)),
                dedicated=bool(rng.random() < 0.2),
                net_type=nets[int(rng.integers(len(nets)))])
    topo = Topology(nodes, {})
    sys_ = ArmadaSystem(topo, seed=seed, trace_enabled=False,
                        include_cloud_compute=False,
                        shard_precision=SHARD_PRECISION)
    sys_.am.services[SERVICE] = ServiceSpec(SERVICE, detection_image())
    sys_.am.tasks[SERVICE] = []
    sys_.am.users[SERVICE] = []
    for i, cap in enumerate(sys_.captains.values()):
        t = Task(f"{SERVICE}/t{i}", SERVICE, captain=cap, status="running",
                 ready_at=0.0)
        cap.tasks[t.task_id] = t
        sys_.am.tasks[SERVICE].append(t)
    sys_.am.autoscale_enabled = False
    return sys_


def _child_case(case: dict):
    from repro.core.churn import ChurnModel

    n_users = case["users"]
    n_per = case["nodes_per_region"]
    n_regions = case["regions"]
    mesh = case["mesh"]
    n_warm = case.get("warm", 2)
    n_meas = case.get("measure", 4)
    seed = case.get("seed", 0)
    churn_on = case.get("churn", True)
    refresh = case.get("refresh_ms", 0.0)

    sys_ = _build_system(n_per, n_regions, seed)
    rng = np.random.default_rng(seed + 1)
    region = rng.integers(0, n_regions, n_users)
    base = np.asarray(REGIONS)[region % len(REGIONS)]
    locs = base + rng.uniform(-0.3, 0.3, (n_users, 2))
    kw = {"refresh_period_ms": refresh} if refresh else {}
    pool = sys_.make_client_pool(
        SERVICE, locs=locs, transport="fluid",
        probe_period_ms=PROBE_MS, frame_interval_ms=FRAME_MS,
        selection_backend="geo_topk", tick="device", mesh=mesh,
        record_samples=False, **kw)
    sys_.sim.at(0.0, pool.start)
    churn = None
    if churn_on:
        # death batches must fit the fused tick's fixed break queue
        # (DEATH_QUEUE_MAX=128/window): ~n_volunteers*probe/mttf per tick
        churn = ChurnModel(sys_.sim, sys_.captains,
                           volunteer_mttf_ms=400 * PROBE_MS,
                           mttr_ms=5 * PROBE_MS)
        churn.start()

    sys_.sim.run(until=n_warm * PROBE_MS + 200.0)
    ticks0, phase0 = pool.ticks_run, dict(pool.phase_ms)
    t0 = time.perf_counter()
    sys_.sim.run(until=(n_warm + n_meas) * PROBE_MS + 200.0)
    wall_ms = (time.perf_counter() - t0) * 1e3
    assert not sys_.sim.truncated
    ticks = pool.ticks_run - ticks0
    assert ticks >= n_meas - 1, ticks

    per_tick = wall_ms / max(ticks, 1)
    phases = ";".join(
        f"phase_{k}_ms={(v - phase0.get(k, 0.0)) / max(ticks, 1):.1f}"
        for k, v in sorted(pool.phase_ms.items()))
    leaves = sum(1 for e in churn.events if e["kind"] == "leave") \
        if churn else 0
    dirty = ""
    if pool.dirty_counts is not None:
        fracs = [c / n_users for c in pool.dirty_counts]
        mean = sum(fracs) / max(len(fracs), 1)
        dirty = (f";dirty_frac_mean={mean:.4f};dirty_frac_ticks=" +
                 "|".join(f"{f:.4f}" for f in fracs))
    kind = f"mesh_d{mesh}" if mesh else "single_d1"
    if refresh:
        kind += "_inc"
    tag = f"mesh_scale/u{n_users}_n{n_per * n_regions}/{kind}"
    derived = (f"ticks={ticks};reqs={pool.requests_sent};"
               f"failovers={pool.failovers};node_failures={leaves};"
               f"mean_frame_ms={pool.mean_latency():.1f};"
               f"host_devices={N_DEVICES};physical_cores={os.cpu_count()};"
               f"{phases}{dirty}")
    return [tag, per_tick, derived]


# -------------------------------------------------------------- parent side


def _run_case(case: dict, timeout: float = 3600.0):
    import jax
    if jax.default_backend() != "cpu":
        return [tuple(_child_case(case))]
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") +
        f" --xla_force_host_platform_device_count={N_DEVICES}").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [str(_ROOT / "src"), str(_ROOT)] +
        ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    cmd = [sys.executable, "-m", "benchmarks.bench_mesh_scale",
           "--case", json.dumps(case)]
    proc = subprocess.run(cmd, cwd=str(_ROOT), env=env,
                          capture_output=True, text=True, timeout=timeout)
    rows = [ln for ln in proc.stdout.splitlines() if ln.startswith(_ROW)]
    if proc.returncode != 0 or not rows:
        raise RuntimeError(
            f"bench_mesh_scale child failed ({case}):\n"
            f"{proc.stdout}\n{proc.stderr}")
    name, ms, derived = json.loads(rows[-1][len(_ROW):])
    return [(name, ms, derived)]


def run(smoke: bool = False):
    if smoke:
        # seconds-scale tier-1 multi-device smoke: same code path
        # (subprocess, 4 forced host devices, mesh driver, churn) at a
        # population where compiles dominate
        cases = [
            dict(users=2_000, nodes_per_region=16, regions=4, mesh=None,
                 warm=1, measure=2),
            dict(users=2_000, nodes_per_region=16, regions=4, mesh=4,
                 warm=1, measure=2),
        ]
    else:
        # acceptance shape: 1M users x 10k nodes on 4 devices with churn;
        # the single-device 250k x 10k run is the weak-scaling baseline
        cases = [
            dict(users=250_000, nodes_per_region=2_500, regions=4,
                 mesh=None),
            dict(users=1_000_000, nodes_per_region=2_500, regions=4,
                 mesh=4),
            # incremental candidate refresh at the acceptance shape:
            # same churn, staleness deadline at 20 probe periods
            dict(users=1_000_000, nodes_per_region=2_500, regions=4,
                 mesh=4, refresh_ms=20 * PROBE_MS),
        ]
    rows = []
    for case in cases:
        rows.extend(_run_case(case))
    return rows


def derive(us_by_name):
    """Weak-scaling ratio, recomputed over the merged result set.

    ``normalized_speedup = D x t_single(U) / t_mesh(D x U)`` — what the
    mesh buys per tick once devices stop sharing host cores; the raw
    per-tick ratio on THIS host is reported alongside, never silently
    substituted."""
    t1 = us_by_name.get("mesh_scale/u250000_n10000/single_d1")
    tm = us_by_name.get("mesh_scale/u1000000_n10000/mesh_d4")
    rows = []
    if t1 and tm and t1 == t1 and tm == tm:
        raw = t1 / tm
        rows.append((
            "mesh_scale/u1000000_n10000/weak_scaling_4dev",
            None,
            f"normalized_speedup={N_DEVICES * raw:.2f}x;"
            f"raw_per_tick_ratio={raw:.2f}x;"
            f"host_devices={N_DEVICES};physical_cores={os.cpu_count()};"
            f"note=forced host devices share physical cores - normalized "
            f"is 4x per-tick ratio at 4x population"))
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale profile (small U/N)")
    ap.add_argument("--case", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.case:
        print(_ROW + json.dumps(_child_case(json.loads(args.case))))
    else:
        print("name,ms_per_tick,derived")
        rows = run(smoke=args.smoke)
        for name, ms, derived in rows:
            print(f"{name},{ms:.1f},{derived}")
        for name, ms, derived in derive({n: m * 1e3 for n, m, _ in rows}):
            print(f"{name},{'' if ms is None else f'{ms:.1f}'},{derived}")
