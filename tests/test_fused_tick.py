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
    one static rebuild per node epoch, every queued break, the programs
    run with a non-empty break queue, bytes both ways.  Same shapes as
    the compile-once test (programs reused)."""
    from repro.core import fused_tick
    queued, programs = [], []
    drv = fused_tick.FusedTickDriver
    on_break, run_tick, run_flush = drv.on_break, drv._run_tick, \
        drv._run_flush

    def counting_on_break(self, node_ix):
        queued.append(node_ix)
        on_break(self, node_ix)

    def counting_tick(self, free, sched, alive, need, deaths, n_deaths):
        programs.append(int(n_deaths))
        return run_tick(self, free, sched, alive, need, deaths, n_deaths)

    def counting_flush(self, deaths, n_deaths):
        programs.append(int(n_deaths))
        return run_flush(self, deaths, n_deaths)

    monkeypatch.setattr(drv, "on_break", counting_on_break)
    monkeypatch.setattr(drv, "_run_tick", counting_tick)
    monkeypatch.setattr(drv, "_run_flush", counting_flush)
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
    replays = sum(n > 0 for n in programs)
    assert 0 < replays < len(programs)       # some ticks replay, some not
    assert counts["replay_ticks"] == replays
    assert sum(programs) == len(queued)
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


# ---------------------------------------------------------------------------
# break replay: the hoisted replay against the per-break loop it replaced
# ---------------------------------------------------------------------------

def _replay_oracle(state, tn, deaths, n_deaths):
    """The break replay as it was before its per-break work was hoisted:
    one full step per break, task→node maps, EMA lookups and compaction
    by per-row gathers, pops OR-ed into a (U, S) mask every step."""
    import jax
    import jax.numpy as jnp
    from repro.core.client_pool import failover_pick
    from repro.core.fused_tick import _ema_get_matrix

    rows = jnp.arange(state.cand.shape[0])
    running = state.running
    nodes_tab, vals_tab = state.ema_nodes, state.ema_vals

    def step(i, carry):
        cand, active, reinit, failovers, popmask = carry
        d = deaths[i]
        cand_node = jnp.where(cand >= 0, tn[jnp.clip(cand, 0)], -1)
        act_node = jnp.where(active >= 0, tn[jnp.clip(active, 0)], -1)
        hit = running & ((cand_node == d).any(axis=1) | (act_node == d))
        popmask = popmask | (hit[:, None] & (nodes_tab == d))
        keep = (cand >= 0) & (cand_node != d)
        # left-compact kept entries by rank (compact_rows semantics) —
        # closed-form per output column, no per-row sort
        rank = jnp.cumsum(keep, axis=1) - 1
        cols = []
        for j in range(cand.shape[1]):
            hitj = keep & (rank == j)
            src = jnp.argmax(hitj, axis=1)
            cols.append(jnp.where(hitj.any(axis=1), cand[rows, src], -1))
        compacted = jnp.stack(cols, axis=1)
        cand = jnp.where(hit[:, None], compacted, cand)
        act_dead = hit & ((active < 0) | (act_node == d))
        cand_node = jnp.where(cand >= 0, tn[jnp.clip(cand, 0)], -1)
        slot = failover_pick(
            cand, _ema_get_matrix(nodes_tab, vals_tab, cand_node), xp=jnp)
        has = slot >= 0
        picked = cand[rows, jnp.clip(slot, 0)]
        active = jnp.where(act_dead & has, picked, active)
        active = jnp.where(act_dead & ~has, -1, active)
        failovers = failovers + jnp.sum((act_dead & has).astype(jnp.int32))
        reinit = reinit | (act_dead & ~has)
        return cand, active, reinit, failovers, popmask

    cand, active, reinit, failovers, popmask = jax.lax.fori_loop(
        0, n_deaths, step,
        (state.cand, state.active, state.reinit, state.failovers,
         jnp.zeros(nodes_tab.shape, bool)))
    vals_tab = jnp.where(popmask, jnp.nan, vals_tab)
    return nodes_tab, vals_tab, cand, active, reinit, failovers


REPLAY_OUTS = ("ema_nodes", "ema_vals", "cand", "active", "reinit",
               "failovers")


def _replay_state(case, k, *, seed=0, u=1_500, s=64, n_nodes=160,
                  n_tasks=240):
    """A random pool state and break queue at a CPU size: several tasks
    per node and a few on none, distinct left-compacted candidates
    (some rows short or empty), actives on and off their lists, EMA
    tables of unique nodes with popped (NaN) and free slots."""
    import jax.numpy as jnp
    from repro.core.fused_tick import DEATH_QUEUE_MAX, FusedTickState

    rng = np.random.default_rng(seed)
    tn = rng.integers(0, n_nodes, n_tasks).astype(np.int32)
    tn[rng.random(n_tasks) < 0.05] = -1                 # on no node
    n_cand = np.where(rng.random(u) < 0.8, k, rng.integers(0, k + 1, u))
    cand = np.full((u, k), -1, np.int32)
    for r in range(u):
        cand[r, :n_cand[r]] = rng.choice(n_tasks, n_cand[r], replace=False)
    first = cand[:, 0].copy()
    active = first.copy()
    off = rng.random(u) < 0.1
    active[off] = rng.integers(0, n_tasks, off.sum())
    active[rng.random(u) < 0.05] = -1
    running = rng.random(u) < 0.95
    if case == "active_off_list":
        active[:] = rng.integers(0, n_tasks, u)
    elif case == "no_active":
        active[:] = -1
    elif case == "not_running":
        running = rng.random(u) < 0.5

    nodes_tab = np.full((u, s), -1, np.int32)
    vals_tab = np.full((u, s), np.nan, np.float32)
    for r in range(u):
        known = [int(tn[t]) for t in list(cand[r]) + [active[r]]
                 if t >= 0 and tn[t] >= 0 and rng.random() < 0.8]
        extra = rng.choice(n_nodes, rng.integers(0, s // 2), replace=False)
        mine = list(dict.fromkeys(known + extra.tolist()))[:s]
        rng.shuffle(mine)
        m = len(mine)
        nodes_tab[r, :m] = mine
        # a few equal EMAs, so ties in the failover pick are exercised
        vals_tab[r, :m] = rng.integers(5, 60, m).astype(np.float32)
        vals_tab[r, :m][rng.random(m) < 0.15] = np.nan          # popped

    def nodes_of(tasks):
        tasks = tasks[tasks >= 0]
        return [int(x) for x in tn[tasks] if x >= 0]

    if case == "none":
        q = []
    elif case == "single":
        q = nodes_of(first)[:1]
    elif case == "burst":
        q = rng.integers(0, n_nodes, DEATH_QUEUE_MAX).tolist()
    elif case == "duplicates":
        a, b, c = rng.choice(n_nodes, 3, replace=False).tolist()
        q = [a, b, a, a, c, b, c, a]
    elif case == "chains":
        # actives' nodes die, then the nodes failover can land on, in
        # candidate order: replacements die later in the same queue
        q = []
        for j in range(k):
            q += list(dict.fromkeys(nodes_of(cand[:8, j])))
        q = q[:DEATH_QUEUE_MAX]
    elif case == "lose_all":
        q = list(dict.fromkeys(nodes_of(cand[:6].ravel())))
        q = q[:DEATH_QUEUE_MAX]
    else:                                        # state-shape cases
        q = rng.integers(0, n_nodes, 24).tolist()
    deaths = np.full(DEATH_QUEUE_MAX, -1, np.int32)
    deaths[:len(q)] = q
    nf = 2
    state = FusedTickState(
        ema_nodes=jnp.asarray(nodes_tab), ema_vals=jnp.asarray(vals_tab),
        ema_overflow=jnp.zeros((), bool), cand=jnp.asarray(cand),
        active=jnp.asarray(active),
        pending=jnp.full((u,), -1, jnp.int32),
        running=jnp.asarray(running), ticking=jnp.ones((u,), bool),
        reinit=jnp.asarray(rng.random(u) < 0.03),
        lat_probe=jnp.full((u, k), jnp.nan, jnp.float32),
        lat_frame=jnp.full((u, nf), jnp.nan, jnp.float32),
        cand_traffic=jnp.asarray(cand), active_traffic=jnp.asarray(active),
        frame_count=jnp.zeros((u,), jnp.int32),
        frame_sum=jnp.zeros((u,), jnp.float32),
        failovers=jnp.asarray(7, jnp.int32))
    return (state, jnp.asarray(tn), jnp.asarray(deaths),
            jnp.asarray(len(q), jnp.int32))


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


@pytest.mark.parametrize("k", [3, 16])
@pytest.mark.parametrize("case", [
    "none", "single", "burst", "duplicates", "active_off_list",
    "no_active", "not_running", "chains", "lose_all"])
def test_break_replay_matches_per_break_loop(case, k):
    """The hoisted replay gives all six outputs of the per-break loop bit
    for bit (NaN against NaN, compared as uint32): the EMA table with its
    pops, candidates, actives, re-init marks and the failover total."""
    import jax
    from repro.core.fused_tick import DEATH_QUEUE_MAX, _process_deaths
    args = _replay_state(case, k, seed=sum(map(ord, case)) + k)
    want = jax.jit(_replay_oracle)(*args)
    got = jax.jit(_process_deaths)(*args)
    for name, w, g in zip(REPLAY_OUTS, want, got):
        w, g = np.asarray(w), np.asarray(g)
        assert w.dtype == g.dtype and w.shape == g.shape, name
        np.testing.assert_array_equal(_bits(g), _bits(w), err_msg=name)
    # the case exercises what it names
    state, n = args[0], int(args[3])
    fails = int(want[5]) - int(state.failovers)
    active0, active1 = np.asarray(state.active), np.asarray(want[3])
    if case == "none":
        assert n == 0 and fails == 0
    elif case == "burst":
        assert n == DEATH_QUEUE_MAX
    elif case == "chains":
        # some user failed over more than once within the queue
        assert fails > int((active1 != active0).sum())
    elif case == "lose_all":
        assert (np.asarray(want[4]) & ~np.asarray(state.reinit)).any()
    elif case == "no_active":
        assert (active1 >= 0).any()
    if case != "none":
        assert fails > 0
        assert np.isnan(np.asarray(want[1])).sum() \
            > np.isnan(np.asarray(state.ema_vals)).sum()


def _subjaxprs(eqn):
    """The jaxprs inside one equation's parameters (loop bodies, cond
    branches, nested calls)."""
    import jax
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, jax.extend.core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jax.extend.core.Jaxpr):
                yield x


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn):
            yield from _eqns(sub)


def _primitives(jaxpr):
    """Names of every primitive in ``jaxpr`` and its sub-jaxprs."""
    return {eqn.primitive.name for eqn in _eqns(jaxpr)}


def _replay_branches(k):
    """The replay's jaxpr is one ``cond`` on the queue length, with no
    loop outside it; returns its branches (empty queue, non-empty)."""
    import jax
    from repro.core.fused_tick import _process_deaths
    args = _replay_state("burst", k, u=64, s=8, n_nodes=20, n_tasks=40)
    jaxpr = jax.make_jaxpr(_process_deaths)(*args).jaxpr
    top = [e.primitive.name for e in jaxpr.eqns]
    assert top.count("cond") == 1 and "while" not in top, top
    cond, = (e for e in jaxpr.eqns if e.primitive.name == "cond")
    empty, busy = (b.jaxpr for b in cond.params["branches"])
    return empty, busy


@pytest.mark.parametrize("k", [3, 16])
def test_break_replay_loop_has_no_gather_or_scatter(k):
    """A step of the replay touches per-user columns only: the body of
    its ``while`` loop holds no gather or scatter."""
    _, busy = _replay_branches(k)
    loops = [e for e in _eqns(busy) if e.primitive.name == "while"]
    assert len(loops) == 1
    body = _primitives(loops[0].params["body_jaxpr"].jaxpr)
    bad = {p for p in body if "gather" in p or "scatter" in p}
    assert not bad, f"per-break loop gathers or scatters: {bad}"
    assert "dynamic_slice" in body          # one queued death per step


def test_break_replay_empty_queue_runs_nothing():
    """With no breaks queued the replay runs no loop, no lookup and no
    (U, S) pass — the empty-queue branch of its ``cond`` holds no
    equation — and hands back the state's arrays bit for bit, even with
    stale node indices left in the queue past ``n_deaths``."""
    import jax
    import jax.numpy as jnp
    from repro.core.fused_tick import _process_deaths
    empty, busy = _replay_branches(3)
    assert not empty.eqns
    assert "while" in _primitives(busy)
    state, tn, deaths, _ = _replay_state("burst", 3, seed=5)
    got = jax.jit(_process_deaths)(state, tn, deaths,
                                   jnp.asarray(0, jnp.int32))
    for name, g in zip(REPLAY_OUTS, got):
        np.testing.assert_array_equal(_bits(g), _bits(getattr(state, name)),
                                      err_msg=name)
