"""Subprocess body for tests/test_mesh_scale.py.

The parent sets ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
in the environment BEFORE this interpreter starts (the flag is read at
jax initialisation, which is why the comparison cannot run in-process
with the tier-1 suite).  Two identical scenarios are driven end-to-end
— node churn (fail + recover, replayed through the fused break queue)
followed by a Beacon fault-domain failover and recovery — once on the
single-device fused tick and once on the 4-device mesh-sharded tick,
and the decision streams must match exactly: candidate matrices,
actives, pending, switch records, failover counts, EMA tables (fp32
rounding).  The in-situ storage data plane is active throughout
(``data_profile`` + two regional Cargos), so the parity pin also covers
the host-computed per-user data term and its read charge-back.  A band of users placed midway between two metros sits
outside every home shard: on the mesh they straddle a device boundary
and are served through the fixed-capacity border pass.

Usage: ``python tests/_mesh_child.py [n_users] [nodes_per_region]
[refresh_period_ms]`` — a non-zero third argument runs BOTH sides with
incremental candidate refresh (``refresh_period_ms``) and additionally
pins the host-side dirty-count stream single == mesh.  Prints one
``##OUT##{json}`` line on success; any parity violation raises and
fails the parent test with this traceback.
"""
import json
import sys

import numpy as np

REGIONS = ((44.97, -93.22), (41.88, -87.63), (39.74, -104.99),
           (32.78, -96.80))
SHARD_PRECISION = 3
SERVICE = "detect"
PROBE_MS = 2000.0
N_BORDER = 8


def _system(n_per_region: int, seed: int):
    from repro.core.app_manager import ServiceSpec, Task
    from repro.core.beacon import ArmadaSystem, detection_image
    from repro.core.cluster import NodeSpec, Topology

    rng = np.random.default_rng(seed)
    nodes = {}
    for r, base in enumerate(REGIONS):
        for i in range(n_per_region):
            nid = f"R{r}N{i}"
            nodes[nid] = NodeSpec(
                nid, (base[0] + float(rng.uniform(-0.5, 0.5)),
                      base[1] + float(rng.uniform(-0.5, 0.5))),
                proc_ms=float(rng.uniform(10, 30)),
                slots=int(rng.integers(2, 9)),
                dedicated=bool(rng.random() < 0.2))
    topo = Topology(nodes, {})
    sys_ = ArmadaSystem(topo, seed=seed, trace_enabled=False,
                        include_cloud_compute=False,
                        shard_precision=SHARD_PRECISION,
                        beacon_heartbeat_ms=1.5 * PROBE_MS)
    sys_.am.services[SERVICE] = ServiceSpec(SERVICE, detection_image())
    sys_.am.tasks[SERVICE] = []
    sys_.am.users[SERVICE] = []
    for i, cap in enumerate(sys_.captains.values()):
        t = Task(f"{SERVICE}/t{i}", SERVICE, captain=cap, status="running",
                 ready_at=0.0)
        cap.tasks[t.task_id] = t
        sys_.am.tasks[SERVICE].append(t)
    sys_.am.autoscale_enabled = False
    # in-situ storage: Cargos in two of the four regions, so the
    # per-user data term varies across shards (users in R2/R3 pay a
    # longer replica hop than R0/R1) and the mesh parity pin covers the
    # host-computed data_ms injection end-to-end
    from repro.core.storage.cargo import Cargo
    for nid in ("R0N0", "R1N0"):
        cg = Cargo(sys_.sim, sys_.topo, sys_.topo.nodes[nid])
        sys_.cargos[nid] = cg
        sys_.beacon.register_cargo(cg)
    spec = ServiceSpec(SERVICE, detection_image(), need_storage=True,
                       locations=[sys_.topo.nodes["R0N0"].loc])
    sys_.cargo_manager.store_register(spec, initial={"k": bytes(1024)})
    return sys_


def _locs(n_users: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed + 1)
    region = rng.integers(0, len(REGIONS), n_users - N_BORDER)
    base = np.asarray(REGIONS)[region]
    clustered = base + rng.uniform(-0.5, 0.5, (n_users - N_BORDER, 2))
    # border band: midway between two metros — outside every home shard
    # at shard precision, so these escalate to the full border pass (and
    # straddle a device boundary on the mesh)
    a, b = np.asarray(REGIONS[0]), np.asarray(REGIONS[1])
    mid = a + (b - a) * np.linspace(0.45, 0.55, N_BORDER)[:, None]
    return np.concatenate([clustered, mid], axis=0)


def _run(mesh, n_users: int, n_per: int, refresh_ms: float = 0.0):
    import repro.core.fused_tick as fused_tick
    from repro.core.storage.cargo_manager import DataProfile

    sys_ = _system(n_per, seed=0)
    # serving-aware scoring active on BOTH sides: mesh parity covers the
    # queueing-delay fold in dynamic_state (single == mesh by construction)
    sys_.am.engine.set_queueing_awareness(SERVICE)
    kw = {"refresh_period_ms": refresh_ms} if refresh_ms else {}
    # the Beacon failover floods the border band with the dead domain's
    # users — size the cap for the whole affected region
    pool = sys_.make_client_pool(
        SERVICE, locs=_locs(n_users, seed=0), transport="fluid",
        frame_interval_ms=500.0, selection_backend="geo_topk",
        tick="device", mesh=mesh,
        data_profile=DataProfile(1.0, 0.2, "eventual"),
        shard_border_cap=max(256, n_users // 2), **kw)
    sys_.sim.at(0.0, pool.start)
    sys_.fail_node("R0N1", 4_200.0)
    sys_.fail_node("R1N2", 4_300.0)

    sys_.sim.run(until=2_100.0)          # start + first full tick traced
    counts0 = dict(fused_tick.COMPILE_COUNTS)
    sys_.sim.run(until=6_000.0)          # both failures replayed
    sys_.captains["R0N1"].recover()
    sys_.sim.run(until=7_000.0)
    churn_delta = {k: fused_tick.COMPILE_COUNTS[k] - counts0.get(k, 0)
                   for k in fused_tick.COMPILE_COUNTS
                   if fused_tick.COMPILE_COUNTS[k] != counts0.get(k, 0)}

    # Beacon fault-domain failover + recovery: ownership merges, users
    # hand off (mesh: re-home across device boundaries), then re-home
    # back when the domain returns
    region = sys_.beacons.busiest_region()
    sys_.fail_beacon(region, 7_900.0)
    sys_.recover_beacon(region, 13_900.0)
    sys_.sim.run(until=14_000.0)
    # a node coming back near its old users beats their failover target
    # by the switch margin -> two-round switches on the final ticks
    sys_.captains["R1N2"].recover()
    sys_.sim.run(until=20_100.0)
    assert not sys_.sim.truncated
    return pool, churn_delta, sys_


def _assert_parity(host, dev, n_users: int) -> None:
    assert host.ticks_run == dev.ticks_run
    assert host.requests_sent == dev.requests_sent
    assert host.failovers == dev.failovers
    np.testing.assert_array_equal(host.cand_task, dev.cand_task)
    np.testing.assert_array_equal(host.active, dev.active)
    np.testing.assert_array_equal(host.pending, dev.pending)
    want = list(zip(host.switch_t, host.switch_user, host.switch_from,
                    host.switch_to))
    got = list(zip(dev.switch_t, dev.switch_user, dev.switch_from,
                   dev.switch_to))
    assert want == got, "switch records diverge"
    np.testing.assert_allclose(host.mean_latency(), dev.mean_latency(),
                               rtol=1e-4)
    sample = sorted(set(range(0, n_users, max(1, n_users // 96))) |
                    set(range(n_users - N_BORDER, n_users)))
    for u in sample:
        a, b = host.ema_of(u), dev.ema_of(u)
        assert set(a) == set(b), f"user {u}: EMA key set diverges"
        for node in a:
            np.testing.assert_allclose(a[node], b[node], rtol=1e-4)


def main() -> None:
    n_users = int(sys.argv[1]) if len(sys.argv) > 1 else 2_000
    n_per = int(sys.argv[2]) if len(sys.argv) > 2 else 16
    refresh_ms = float(sys.argv[3]) if len(sys.argv) > 3 else 0.0
    import jax
    assert len(jax.devices()) >= 4, jax.devices()

    single, _, sys_s = _run(None, n_users, n_per, refresh_ms)
    mesh, churn_delta, sys_m = _run(4, n_users, n_per, refresh_ms)
    assert mesh._dev._sharded, "mesh driver should be region-sharded"
    _assert_parity(single, mesh, n_users)

    # the in-situ data plane charged identically on both paths: same
    # read totals and measured rates on every Cargo replica
    reads = 0
    for nid in sys_s.cargos:
        assert (sys_s.cargos[nid].reads_total
                == sys_m.cargos[nid].reads_total), f"{nid} reads diverge"
        np.testing.assert_allclose(sys_s.cargos[nid].read_rate,
                                   sys_m.cargos[nid].read_rate)
        reads += sys_s.cargos[nid].reads_total
    assert reads > 0, "data term never charged a read"

    # the border band is outside every home shard yet fully served —
    # identically on both paths (covered by the parity assert above)
    border = np.arange(n_users - N_BORDER, n_users)
    assert (mesh.active[border] >= 0).all(), "border users unserved"

    # one SPMD trace per mesh program: node churn is content, not shape
    mesh_delta = {k: v for k, v in churn_delta.items()
                  if k.startswith("mesh_")}
    assert not mesh_delta, f"mesh programs re-traced under churn: " \
                           f"{mesh_delta}"

    out = {
        "ok": True,
        "ticks": single.ticks_run,
        "switches": len(single.switch_t),
        "failovers": single.failovers,
        "border_users": int(border.size),
        "cargo_reads": int(reads),
        # host spans and counters of both drivers (repro.core.spans)
        "spans": {"single": sorted(single.phase_ms),
                  "mesh": sorted(mesh.phase_ms)},
        "counts": {"single": single.counts, "mesh": mesh.counts},
    }
    if refresh_ms:
        # the host-side dirty tracker is shared logic: the mesh driver
        # must refresh exactly the users the single-device driver does
        assert single._rt.dirty_counts == mesh._rt.dirty_counts, \
            "dirty-count streams diverge single vs mesh"
        out["dirty_total"] = int(sum(mesh._rt.dirty_counts))
        out["dirty_frac"] = float(sum(mesh._rt.dirty_counts) /
                                  (n_users * max(1, mesh.ticks_run)))
        out["fallbacks"] = int(mesh._rt.fallbacks)
    print("##OUT##" + json.dumps(out))


if __name__ == "__main__":
    main()
