"""Device-resident fused tick vs the host numpy tick: decision-stream
parity on the paper's Fig. 8/10 scenarios, jit-shape stability under
churn, and the device-mode guard rails.

The fused program must reproduce the host fluid tick EXACTLY in every
decision — candidate matrices, active/pending assignments, switch
records (time, user, from, to), failover counts, request counts — and
match EMAs/latency aggregates to fp32 rounding (the host folds in
float64).  Scoring parity is by construction (both paths consume
bit-identical fp32 inputs through the geo_topk math); this file pins the
whole tick, including the sequential break replay and two-round switch.
"""
import numpy as np
import pytest

from repro.core.app_manager import ServiceSpec, Task
from repro.core.beacon import ArmadaSystem, detection_image
from repro.core.cluster import NodeSpec, Topology

SERVICE = "detect"


def _fluid_system(n_nodes=24, seed=0, spread=0.5):
    """Metro fleet with one running replica per node (Fig 8-style node
    sets; failures injected per test recreate the Fig 10 trajectories)."""
    rng = np.random.default_rng(seed)
    nodes = {f"N{i}": NodeSpec(
        f"N{i}", (44.97 + float(rng.uniform(-spread, spread)),
                  -93.22 + float(rng.uniform(-spread, spread))),
        proc_ms=float(rng.uniform(10, 30)),
        slots=int(rng.integers(2, 9)),
        dedicated=bool(rng.random() < 0.2))
        for i in range(n_nodes)}
    topo = Topology(nodes, {})
    sys_ = ArmadaSystem(topo, seed=seed, trace_enabled=False,
                        include_cloud_compute=False)
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


def _run_pool(tick, *, n_users=50, n_nodes=24, seed=0, until=12_000.0,
              fail=(), frame_interval=500.0, profiled=False,
              queueing=False, slots=None, workload_scale=1.0):
    sys_ = _fluid_system(n_nodes, seed)
    if slots is not None:                 # force capacity (saturation tests)
        for cap in sys_.captains.values():
            cap.spec.slots = slots
    if profiled:
        # heterogeneous serving profiles (detector / facerec / llm-decode
        # round-robin, speed scaled off each node's proc_ms); calibration={}
        # pins the deterministic fallback unit times
        from repro.serving.profile import attach_profiles
        attach_profiles(sys_.captains.values(), calibration={})
    if queueing:
        sys_.am.engine.set_queueing_awareness(SERVICE)
    rng = np.random.default_rng(seed + 1)
    locs = np.stack([44.97 + rng.uniform(-.5, .5, n_users),
                     -93.22 + rng.uniform(-.5, .5, n_users)], axis=1)
    pool = sys_.make_client_pool(
        SERVICE, locs=locs, transport="fluid",
        frame_interval_ms=frame_interval, selection_backend="geo_topk",
        tick=tick, workload_scale=workload_scale)
    sys_.sim.at(0.0, pool.start)
    for node, t in fail:
        sys_.fail_node(node, t)
    sys_.sim.run(until=until)
    return pool, sys_


def _assert_tick_parity(host, dev, n_users):
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
    # fold the open window on BOTH sides before comparing EMA tables
    # (mean_latency flushes the host fluid buffer / the device stash)
    np.testing.assert_allclose(host.mean_latency(), dev.mean_latency(),
                               rtol=1e-4)
    for u in range(n_users):
        a, b = host.ema_of(u), dev.ema_of(u)
        assert set(a) == set(b), f"user {u}: EMA key set diverges"
        for node in a:
            np.testing.assert_allclose(a[node], b[node], rtol=1e-4)


def test_device_tick_matches_host_fig8_steady_state():
    """Fig 8 regime: steady metro fleet, probes + frames + two-round
    switches — decision stream identical, EMAs to fp32 rounding."""
    host, _ = _run_pool("host", until=14_000.0)
    dev, _ = _run_pool("device", until=14_000.0)
    _assert_tick_parity(host, dev, 50)
    assert len(dev.switch_t) > 0          # the scenario actually switches
    assert dev.ticks_run >= 6


def test_device_tick_matches_host_fig10_failover():
    """Fig 10 regime: nodes die mid-run (some within one window) — the
    queued break replay must reproduce the host's instant failovers."""
    fail = [("N1", 4_200.0), ("N5", 4_300.0), ("N9", 6_500.0),
            ("N2", 6_600.0)]
    host, _ = _run_pool("host", until=14_000.0, fail=fail)
    dev, _ = _run_pool("device", until=14_000.0, fail=fail)
    _assert_tick_parity(host, dev, 50)
    assert dev.failovers > 0


def test_device_tick_matches_host_under_volunteer_churn():
    """Fail/recover cycles: recovered nodes re-enter selection, EMAs are
    popped per break — both ticks stay locked step for the whole run."""
    host, hs = _run_pool("host", until=10_000.0,
                         fail=[("N3", 3_100.0), ("N7", 5_100.0)])
    dev, ds = _run_pool("device", until=10_000.0,
                        fail=[("N3", 3_100.0), ("N7", 5_100.0)])
    for s in (hs, ds):
        s.captains["N3"].recover()
        s.sim.run(until=18_000.0)
    _assert_tick_parity(host, dev, 50)


def test_device_tick_matches_host_with_profiles_and_queueing():
    """Serving-aware regime: heterogeneous ServingProfiles set per-node
    unit times, the fleet is driven into saturation (6 single-slot nodes,
    4x workload) so the queueing-aware load fold is numerically active —
    and the
    fused device tick must still reproduce the host decision stream
    exactly (the fold happens in ``dynamic_state``, upstream of both)."""
    # slots=1 + 4x workload on 6 nodes saturates; workload_scale is a
    # runtime scalar and U/nf/node_pad stay at the suite defaults, so the
    # device run reuses the already-compiled fused programs
    hot = dict(until=14_000.0, n_nodes=6, slots=1, workload_scale=4.0,
               profiled=True, queueing=True)
    host, hs = _run_pool("host", **hot)
    dev, _ = _run_pool("device", **hot)
    _assert_tick_parity(host, dev, 50)
    assert dev.ticks_run >= 6
    # the term was genuinely active: backlog built up...
    assert max(c.queueing_delay_ms() for c in hs.captains.values()) > 0.0
    # ...and queueing awareness changed at least one decision vs baseline
    base, _ = _run_pool("host", **{**hot, "queueing": False})
    assert not np.array_equal(base.active, host.active) or \
        list(base.switch_t) != list(host.switch_t) or \
        (base.cand_task != host.cand_task).any()


def test_numpy_kernel_parity_with_queueing_backlog():
    """numpy vs geo_topk index path with the occupancy term active and a
    real injected backlog: a third of the fleet is saturated, so the
    queueing fold moves scores — both paths must still rank identically."""
    sys_ = _fluid_system(24, seed=6)
    from repro.serving.profile import attach_profiles
    attach_profiles(sys_.captains.values(), calibration={})
    sys_.am.engine.set_queueing_awareness(SERVICE)
    for i, cap in enumerate(sys_.captains.values()):
        if i % 3 == 0:                    # drown every third node
            cap.arrive_batch(400.0, 1.0, 1_000.0, 0.0)
    rng = np.random.default_rng(7)
    locs = [(44.97 + float(rng.uniform(-.5, .5)),
             -93.22 + float(rng.uniform(-.5, .5))) for _ in range(20)]
    eng = sys_.am.engine
    tasks = sys_.am.tasks[SERVICE]
    want = eng.candidate_indices(SERVICE, tasks, locs, "wifi")
    got = eng.candidate_indices_kernel(SERVICE, tasks, locs, "wifi",
                                       node_pad=32)
    np.testing.assert_array_equal(got, want)
    # the saturated nodes actually carry a queueing signal
    qs = [cap.queueing_delay_ms() for cap in sys_.captains.values()]
    assert max(qs) > 0.0


def test_device_tick_compiles_once_under_churn():
    """Shape stability: node failures, recoveries AND a replica join
    (within the node_pad) must not retrigger tracing of any fused
    program — a recompiling tick would silently serialize the loop."""
    from repro.core import fused_tick
    sys_ = _fluid_system(16, seed=2)
    rng = np.random.default_rng(3)
    locs = np.stack([44.97 + rng.uniform(-.5, .5, 50),
                     -93.22 + rng.uniform(-.5, .5, 50)], axis=1)
    pool = sys_.make_client_pool(
        SERVICE, locs=locs, transport="fluid", frame_interval_ms=500.0,
        selection_backend="geo_topk", tick="device")
    sys_.sim.at(0.0, pool.start)
    sys_.sim.run(until=2_100.0)           # start + first full tick traced
    counts0 = dict(fused_tick.COMPILE_COUNTS)

    sys_.fail_node("N2", 2_200.0)
    sys_.fail_node("N6", 4_300.0)
    sys_.sim.run(until=6_000.0)
    sys_.captains["N2"].recover()
    # volunteer join: a fresh replica appears on a live node (new task,
    # new node-epoch — static arrays rebuild, shapes must not change)
    cap = sys_.captains["N4"]
    t = Task(f"{SERVICE}/t_join", SERVICE, captain=cap, status="running",
             ready_at=sys_.sim.now)
    cap.tasks[t.task_id] = t
    sys_.am.tasks[SERVICE].append(t)
    sys_.am.engine.invalidate(SERVICE)
    sys_.sim.run(until=14_000.0)
    assert pool.ticks_run >= 6
    delta = {k: fused_tick.COMPILE_COUNTS[k] - counts0.get(k, 0)
             for k in fused_tick.COMPILE_COUNTS}
    assert all(v == 0 for v in delta.values()), \
        f"fused programs re-traced under churn: {delta}"


def test_device_tick_phase_breakdown_recorded():
    # default shapes on purpose: reuses the parity tests' compiled
    # programs (every fused-tick test shares U=50 / node_pad=256 / nf=4)
    dev, _ = _run_pool("device", until=4_100.0)
    assert "fused_tick" in dev.phase_ms and "transport" in dev.phase_ms
    host, _ = _run_pool("host", until=4_100.0)
    assert {"selection", "policy", "transport"} <= set(host.phase_ms)


# the device tick's host spans: parent -> children (dotted names)
SPAN_TREE = {
    "transport": ("transport.static", "transport.dynamic",
                  "transport.mirrors", "transport.admit",
                  "transport.jitter", "transport.push"),
    "fused_tick": ("fused_tick.dispatch", "fused_tick.wait",
                   "fused_tick.pull"),
}


def test_device_tick_spans_and_counters_under_churn(monkeypatch):
    """Every host span of the device tick is recorded and each parent's
    time covers its children's; the counters count what the ticks moved:
    one static rebuild per node epoch, every queued break, bytes both
    ways.  Same shapes as the compile-once test (programs reused)."""
    from repro.core import fused_tick
    queued = []
    on_break = fused_tick.FusedTickDriver.on_break

    def counting_on_break(self, node_ix):
        queued.append(node_ix)
        on_break(self, node_ix)

    monkeypatch.setattr(fused_tick.FusedTickDriver, "on_break",
                        counting_on_break)
    sys_ = _fluid_system(16, seed=2)
    rng = np.random.default_rng(3)
    locs = np.stack([44.97 + rng.uniform(-.5, .5, 50),
                     -93.22 + rng.uniform(-.5, .5, 50)], axis=1)
    pool = sys_.make_client_pool(
        SERVICE, locs=locs, transport="fluid", frame_interval_ms=500.0,
        selection_backend="geo_topk", tick="device")
    sys_.sim.at(0.0, pool.start)
    sys_.fail_node("N2", 2_200.0)
    sys_.fail_node("N6", 4_300.0)
    sys_.sim.run(until=6_000.0)
    sys_.captains["N2"].recover()
    epochs = 1                      # the pool's start
    # two replica joins, two windows apart: two more node epochs
    for node, until in (("N4", 8_100.0), ("N9", 12_100.0)):
        cap = sys_.captains[node]
        t = Task(f"{SERVICE}/t_join_{node}", SERVICE, captain=cap,
                 status="running", ready_at=sys_.sim.now)
        cap.tasks[t.task_id] = t
        sys_.am.tasks[SERVICE].append(t)
        sys_.am.engine.invalidate(SERVICE)
        epochs += 1
        sys_.sim.run(until=until)
    assert pool.ticks_run >= 6

    ms = pool.phase_ms
    for parent, children in SPAN_TREE.items():
        assert set(children) <= set(ms), sorted(ms)
        assert ms[parent] >= sum(ms[c] for c in children) > 0
    counts = pool.counts
    assert counts["static_rebuilds"] == epochs
    assert len(queued) > 0 and counts["breaks"] == len(queued)
    assert counts["h2d_bytes"] > 0 and counts["d2h_bytes"] > 0


def test_device_tick_guard_rails():
    sys_ = _fluid_system(8, seed=1)
    locs = np.zeros((4, 2)) + (44.97, -93.22)
    for kw, msg in [
            (dict(transport="events", selection_backend="numpy"),
             "tick='device'"),
            (dict(transport="fluid", selection_backend="numpy"),
             "geo_topk"),
            (dict(transport="fluid", selection_backend="geo_topk",
                  mode="cloud"), "armada")]:
        with pytest.raises(ValueError, match=msg):
            sys_.make_client_pool(SERVICE, locs=locs, tick="device",
                                  frame_interval_ms=500.0, **kw)


@pytest.mark.slow
def test_device_tick_survives_total_candidate_loss_and_recovery():
    """Kill the whole fleet, then bring one node back: users re-enter
    initial selection at the next tick and traffic resumes."""
    sys_ = _fluid_system(6, seed=4, spread=0.05)
    rng = np.random.default_rng(5)
    locs = np.stack([44.97 + rng.uniform(-.05, .05, 50),
                     -93.22 + rng.uniform(-.05, .05, 50)], axis=1)
    pool = sys_.make_client_pool(
        SERVICE, locs=locs, transport="fluid", frame_interval_ms=500.0,
        selection_backend="geo_topk", tick="device")
    sys_.sim.at(0.0, pool.start)
    for i in range(6):
        sys_.fail_node(f"N{i}", 3_000.0 + 10 * i)
    sys_.sim.run(until=5_000.0)
    assert (pool.active == -1).all()
    sys_.captains["N0"].recover()
    sys_.sim.run(until=12_000.0)
    assert (pool.active >= 0).all()
    assert np.isfinite(pool.mean_latency())


# ---------------------------------------------------------------------------
# switch-confirmation starvation (ROADMAP regression, filed from PR 9)
# ---------------------------------------------------------------------------

def _starved_system(n_thin=23, seed=2):
    """One desirable-looking node whose single slot drowns under load,
    ringed by near-tied thin alternatives: every user wants out of HOT,
    but the thin nodes' EMAs stay within jitter of each other so the
    instantaneous per-tick argmin rotates — the exact regime where the
    old confirm-against-fresh-argmin rule starved every switch."""
    nodes = {"HOT": NodeSpec("HOT", (44.97, -93.22), proc_ms=12.0,
                             slots=1)}
    for i in range(n_thin):
        ang = 2 * np.pi * i / n_thin
        nodes[f"T{i}"] = NodeSpec(
            f"T{i}", (44.97 + 0.3 * float(np.cos(ang)),
                      -93.22 + 0.3 * float(np.sin(ang))),
            proc_ms=20.0, slots=2)
    topo = Topology(nodes, {})
    sys_ = ArmadaSystem(topo, seed=seed, trace_enabled=False,
                        include_cloud_compute=False)
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


# 50 users x 24 nodes x 500 ms frames matches _run_pool's shapes AND
# static config, so the device program compiled by earlier tests in
# this session is reused here (a fresh shape would recompile ~5 s)
def _run_starved(tick, backend, *, n_users=50, until=20_000.0):
    sys_ = _starved_system()
    rng = np.random.default_rng(3)
    locs = np.stack([44.97 + rng.uniform(-.02, .02, n_users),
                     -93.22 + rng.uniform(-.02, .02, n_users)], axis=1)
    pool = sys_.make_client_pool(
        SERVICE, locs=locs, transport="fluid", frame_interval_ms=500.0,
        selection_backend=backend, tick=tick, workload_scale=12.0)
    sys_.sim.at(0.0, pool.start)
    sys_.sim.run(until=until)
    pool.mean_latency()         # flush: sync device actives to the host
    hot_task = next(i for i, t in enumerate(sys_.am.tasks[SERVICE])
                    if t.captain.node_id == "HOT")
    return pool, hot_task


def test_switch_starvation_near_tie_evacuates_all_paths():
    """The drowned node empties on every tick path, and the decision
    streams stay locked: host numpy == geo_topk kernel == fused device.
    (The mesh driver consumes the same device decision code;
    tests/_mesh_child.py pins its stream against the device's.)"""
    runs = {
        "host-numpy": _run_starved("host", "numpy"),
        "host-kernel": _run_starved("host", "geo_topk"),
        "device": _run_starved("device", "geo_topk"),
    }
    base_pool, hot_task = runs["host-numpy"]
    # the crowd initially lands on the fast nearby node...
    first_active = np.asarray(
        [base_pool.switch_from[base_pool.switch_user.index(u)]
         for u in set(base_pool.switch_user)])
    assert (first_active == "HOT").mean() > 0.5
    for name, (pool, hot) in runs.items():
        stranded = int((pool.active == hot).sum())
        assert stranded <= 6, \
            f"{name}: {stranded} users starved on the drowned node"
        assert len(pool.switch_t) >= 32, f"{name}: too few switches"
    _assert_tick_parity(runs["host-kernel"][0], runs["device"][0], 50)
    a, b = runs["host-numpy"][0], runs["host-kernel"][0]
    np.testing.assert_array_equal(a.active, b.active)
    np.testing.assert_array_equal(a.pending, b.pending)
    assert list(a.switch_t) == list(b.switch_t)


# ---------------------------------------------------------------------------
# in-situ data plane (data_profile): identity + effect
# ---------------------------------------------------------------------------

def _data_system(n_nodes=24, seed=0):
    from repro.core.storage.cargo import Cargo
    sys_ = _fluid_system(n_nodes, seed)
    for nid in ("N0", "N3", "N7"):
        cg = Cargo(sys_.sim, sys_.topo, sys_.topo.nodes[nid])
        sys_.cargos[nid] = cg
        sys_.beacon.register_cargo(cg)
    spec = ServiceSpec(SERVICE, detection_image(), need_storage=True,
                       locations=[sys_.topo.nodes["N0"].loc])
    sys_.cargo_manager.store_register(spec, initial={"k": bytes(1024)})
    return sys_


def _run_data_pool(tick, *, profile, n_users=50, until=14_000.0,
                   backend="geo_topk"):
    from repro.core.storage.cargo_manager import DataProfile
    sys_ = _data_system()
    rng = np.random.default_rng(1)
    locs = np.stack([44.97 + rng.uniform(-.5, .5, n_users),
                     -93.22 + rng.uniform(-.5, .5, n_users)], axis=1)
    kw = {}
    if profile:
        kw["data_profile"] = DataProfile(2.0, 0.5, "strong")
    pool = sys_.make_client_pool(
        SERVICE, locs=locs, transport="fluid", frame_interval_ms=500.0,
        selection_backend=backend, tick=tick, **kw)
    sys_.sim.at(0.0, pool.start)
    sys_.sim.run(until=until)
    return pool, sys_


def test_data_term_decision_identity_host_kernel_device():
    """With the in-situ data term active the decision streams stay
    locked across host numpy, the geo_topk kernel, and the fused device
    tick: the (U,) data_ms is computed host-side once per window and
    injected into every backend identically."""
    host, hs = _run_data_pool("host", profile=True)
    kern, _ = _run_data_pool("host", profile=True, backend="geo_topk")
    dev, ds = _run_data_pool("device", profile=True)
    _assert_tick_parity(kern, dev, 50)
    np.testing.assert_array_equal(host.active, kern.active)
    np.testing.assert_array_equal(host.cand_task, kern.cand_task)
    assert list(host.switch_t) == list(kern.switch_t)
    # the charge-back side is identical too: same read totals, same
    # measured rates on every replica
    for nid in hs.cargos:
        assert hs.cargos[nid].reads_total == ds.cargos[nid].reads_total
        np.testing.assert_allclose(hs.cargos[nid].read_rate,
                                   ds.cargos[nid].read_rate)
    assert sum(c.reads_total for c in hs.cargos.values()) > 0, \
        "scenario never charged a read"


def test_data_term_changes_latency_and_decisions():
    """The fold is genuinely active: with a data profile the frame
    latencies include the Cargo hop (mean strictly above the data-less
    run) and at least one selection decision moves toward data."""
    on, _ = _run_data_pool("host", profile=True)
    off, _ = _run_data_pool("host", profile=False)
    assert on.requests_sent == off.requests_sent
    assert on.mean_latency() > off.mean_latency() + 1.0
    assert (not np.array_equal(on.active, off.active)
            or list(on.switch_t) != list(off.switch_t)
            or (on.cand_task != off.cand_task).any())
