"""Incremental candidate refresh: dirty-set sparse rescoring.

``ClientPool(refresh_period_ms=...)`` turns the every-tick O(U·N)
candidate refresh into a dirty-set refresh: a user is rescored only
when its home region's node set changed (engine epochs), it had a pool
event (connection break, Beacon handoff), or its per-user staleness
deadline fired.  These tests pin the mode across the tick paths:

* **identity matrix** — host-numpy == host-kernel == fused device tick
  make identical decisions under ``refresh_period_ms``, through node
  churn + recovery and a Beacon fault-domain kill/recover cycle, with
  identical per-tick refreshed-user streams (the mesh leg lives in
  ``tests/test_mesh_scale.py::test_mesh_identity_incremental_refresh``);
* **overflow fallback** — a ``refresh_cap`` smaller than the dirty set
  latches the in-program overflow flag and falls back to the dense
  full-scan branch for that tick, bit-for-bit identical to the host,
  with no retrace (the fallback is a ``lax.cond``, not a new shape);
* **sparse == restricted dense** (property) — for random dirty subsets
  the sparse gather → score → top-k → scatter-back equals a full
  recompute restricted to those rows (rank order and index tie-breaking
  included), and untouched rows keep their previous candidates;
* **discovery × refresh** — a staleness deadline that fires inside a
  Beacon re-discovery window defers exactly once (the gates compose by
  AND: the user stays due and refreshes on the first open tick, which
  re-arms the deadline), identically on host and device;
* the constructor guard rails.
"""
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

import repro.core.fused_tick as fused_tick
from repro.core.app_manager import ServiceSpec, Task
from repro.core.beacon import ArmadaSystem, detection_image
from repro.core.cluster import NodeSpec, Topology
from tests.test_sharded_selection import (SERVICE, _assert_decisions_equal,
                                          _fluid_system)

PROBE = 2000.0
N_USERS = 50


def _locs(n_users=N_USERS, seed=0):
    rng = np.random.default_rng(seed + 1)
    return np.stack([44.97 + rng.uniform(-.5, .5, n_users),
                     -93.22 + rng.uniform(-.5, .5, n_users)], axis=1)


def _run(tick, *, backend="geo_topk", period=None, cap=None, shard=3,
         beacon=False, churn=True, until=16_000.0, seed=0, system=None,
         after_start=None):
    """One Fig 8/10-style fluid run (N1/N5 die, N1 recovers; optional
    Beacon kill/recover on the busiest fault domain)."""
    sys_ = system() if system is not None else _fluid_system(
        seed=seed, shard=shard)
    kw = {}
    if period is not None:
        kw["refresh_period_ms"] = period
    if cap is not None:
        kw["refresh_cap"] = cap
    pool = sys_.make_client_pool(
        SERVICE, locs=_locs(seed=seed), transport="fluid",
        frame_interval_ms=500.0, selection_backend=backend, tick=tick,
        shard_border_cap=N_USERS, **kw)
    sys_.sim.at(0.0, pool.start)
    if churn:
        sys_.fail_node("N1", 4_200.0)
        sys_.fail_node("N5", 4_300.0)
        sys_.sim.at(8_000.0, sys_.captains["N1"].recover)
    if beacon:
        region = sys_.beacons.busiest_region()
        sys_.fail_beacon(region, 5_900.0)
        sys_.recover_beacon(region, 10_100.0)
    if after_start is not None:
        after_start(sys_, pool)
    sys_.sim.run(until=until)
    return pool


def _dirty_streams_equal(host, dev):
    """The device tick runs one extra leading tick at t=0 (which
    refreshes nobody under incremental mode); past that, the per-tick
    refreshed-user streams must match exactly."""
    assert dev.dirty_counts[0] == 0
    assert dev.dirty_counts[1:] == host.dirty_counts


# ---------------------------------------------------------- identity matrix


def test_refresh_identity_host_kernel_device():
    """Under ``refresh_period_ms`` the three in-process tick paths make
    identical decisions through churn + Beacon kill/recover — and the
    refresh really is sparse (well under one rescore per user-tick)."""
    period = 3 * PROBE
    host_np = _run("host", backend="numpy", period=period, beacon=True)
    host_k = _run("host", period=period, beacon=True)
    dev = _run("device", period=period, beacon=True)
    _assert_decisions_equal(host_k, host_np)
    _assert_decisions_equal(dev, host_k)
    _dirty_streams_equal(host_k, dev)
    assert host_np.dirty_counts == host_k.dirty_counts
    total = sum(host_k.dirty_counts)
    assert 0 < total < 0.7 * N_USERS * len(host_k.dirty_counts)
    assert dev._rt.fallbacks == 0


def test_default_mode_reports_no_dirty_stream():
    """Without ``refresh_period_ms`` nothing changes: no tracker, no
    dirty accounting — the historical every-tick semantics (whose
    bit-for-bit stability the rest of the suite pins)."""
    pool = _run("host", until=2_100.0, churn=False)
    assert pool.dirty_counts is None and pool._rt is None


@pytest.mark.slow
@pytest.mark.parametrize("period", [PROBE, 2 * PROBE, 5 * PROBE])
def test_refresh_identity_period_sweep(period):
    host_np = _run("host", backend="numpy", period=period, beacon=True)
    host_k = _run("host", period=period, beacon=True)
    dev = _run("device", period=period, beacon=True)
    _assert_decisions_equal(host_k, host_np)
    _assert_decisions_equal(dev, host_k)
    _dirty_streams_equal(host_k, dev)


# ------------------------------------------------- overflow -> dense branch


@pytest.mark.slow       # ~7 s edge pin; the main identity pin stays fast
def test_overflow_cap_falls_back_to_full_scan_identically():
    """A refresh_cap smaller than the dirty set must not drop users: the
    program latches overflow and takes the dense branch for that tick,
    still refreshing exactly the dirty rows — decisions stay identical
    to the host, and the cond flip retraces nothing."""
    deltas = {}

    def pin(sys_, pool):
        def snap():
            deltas["base"] = dict(fused_tick.COMPILE_COUNTS)
        sys_.sim.at(2_100.0, snap)

    host = _run("host", period=3 * PROBE)
    dev = _run("device", period=3 * PROBE, cap=4, after_start=pin)
    _assert_decisions_equal(dev, host)
    _dirty_streams_equal(host, dev)
    assert dev._rt.fallbacks > 0, "cap=4 never overflowed"
    assert {k: v for k, v in fused_tick.COMPILE_COUNTS.items()
            if v != deltas["base"].get(k, 0)} == {}, \
        "dirty-size changes / overflow fallback retraced the program"


def test_guard_rails():
    sys_ = _fluid_system(seed=0, shard=3)
    with pytest.raises(ValueError, match="refresh_period_ms"):
        sys_.make_client_pool(SERVICE, locs=_locs(), transport="events",
                              refresh_period_ms=1000.0)
    with pytest.raises(ValueError, match="must be > 0"):
        sys_.make_client_pool(SERVICE, locs=_locs(), transport="fluid",
                              frame_interval_ms=500.0,
                              refresh_period_ms=0.0)
    with pytest.raises(ValueError, match="refresh_cap"):
        sys_.make_client_pool(SERVICE, locs=_locs(), transport="fluid",
                              frame_interval_ms=500.0, refresh_cap=16)


# ------------------------------------- property: sparse == restricted dense


_IDLE_PERIOD = 1e9          # staleness never fires inside the horizon
_CAND_CACHE = {}


def _cand_after_marks(marks, shard, tie=False):
    """Device run with an idle tracker; ``marks`` users are dirtied just
    before the tick at t=6000 and the candidate matrix is snapped right
    after it."""
    key = (tuple(sorted(marks)), shard, tie)
    if key in _CAND_CACHE:
        return _CAND_CACHE[key]
    snaps = {}

    def hook(sys_, pool):
        ix = np.asarray(sorted(marks), dtype=int)
        if ix.size:
            sys_.sim.at(4_900.0, lambda: pool._rt.mark(ix))
        sys_.sim.at(6_100.0,
                    lambda: snaps.__setitem__("cand",
                                              pool.cand_task.copy()))

    pool = _run("device", period=_IDLE_PERIOD, cap=N_USERS, shard=shard,
                churn=False, until=6_200.0, after_start=hook,
                system=_tie_system if tie else None)
    assert pool._rt.fallbacks == 0
    _CAND_CACHE[key] = snaps["cand"]
    return snaps["cand"]


@settings(max_examples=8, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=N_USERS - 1),
                min_size=0, max_size=N_USERS, unique=True),
       st.sampled_from([None, 3]))
def test_sparse_scatter_equals_restricted_recompute(subset, shard):
    """For a random dirty subset D, the sparse path's scatter-back
    equals the full recompute restricted to D's rows — same per-row rank
    order, same tie-breaking — and rows outside D are untouched."""
    full = _cand_after_marks(range(N_USERS), shard)
    base = _cand_after_marks((), shard)
    got = _cand_after_marks(subset, shard)
    sub = np.asarray(sorted(subset), dtype=int)
    rest = np.setdiff1d(np.arange(N_USERS), sub)
    np.testing.assert_array_equal(got[sub], full[sub],
                                  err_msg="dirty rows != restricted dense")
    np.testing.assert_array_equal(got[rest], base[rest],
                                  err_msg="clean rows were clobbered")


def _tie_system():
    """Every node identical (location, speed, capacity): all scores tie
    and the candidate order is pure index tie-breaking."""
    nodes = {f"N{i}": NodeSpec(f"N{i}", (44.97, -93.22), proc_ms=20.0,
                               slots=4) for i in range(24)}
    sys_ = ArmadaSystem(Topology(nodes, {}), seed=0, trace_enabled=False,
                        include_cloud_compute=False, shard_precision=3)
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


def test_sparse_preserves_global_tie_breaking():
    """All-tie topology: the sparse gather/top-k/scatter must reproduce
    the dense path's index tie-breaking exactly."""
    subset = (0, 3, 7, 21, 48)
    full = _cand_after_marks(range(N_USERS), 3, tie=True)
    got = _cand_after_marks(subset, 3, tie=True)
    sub = np.asarray(subset)
    np.testing.assert_array_equal(got[sub], full[sub])


# ----------------------------------------- discovery window x refresh period


def _defer_run(tick):
    """Refresh deadlines (period 2·PROBE) with a discovery window pinned
    over users 0..9 covering the tick at t=6000.  Records every
    (user, refresh time) the tracker re-arms."""
    times = {}

    def hook(sys_, pool):
        def arm():
            pool.am.engine.discovery_ms = 1_500.0
            rec_orig = pool._rt.note_refreshed

            def rec(refreshed, now):
                ix = np.asarray(refreshed)
                if ix.dtype == bool:
                    ix = np.nonzero(ix)[0]
                for u in ix:
                    times.setdefault(int(u), []).append(now)
                return rec_orig(refreshed, now)
            pool._rt.note_refreshed = rec

        def window():
            pool._disc_until = np.zeros(pool.n_users)
            pool._disc_until[:10] = 7_500.0
        sys_.sim.at(100.0, arm)
        sys_.sim.at(4_950.0, window)

    pool = _run(tick, period=2 * PROBE, shard=None, churn=False,
                until=13_000.0, after_start=hook)
    return pool, times


def test_deadline_inside_discovery_window_defers_exactly_once():
    """Masks compose by AND: a user whose staleness deadline fires while
    its re-discovery window is closed stays due, refreshes on the FIRST
    open tick (t=8000, not t=6000), and that refresh re-arms the
    deadline (next at t=12000 — no catch-up double fire at t=10000).
    Host and device agree on every (user, time) pair."""
    host, h_times = _defer_run("host")
    dev, d_times = _defer_run("device")
    _assert_decisions_equal(dev, host)
    _dirty_streams_equal(host, dev)
    assert h_times == d_times
    # stagger: users 0..31 first refresh at t=2000, 32..49 at t=4000
    for u in range(10):                       # gated: deferred once
        assert h_times[u] == [2_000.0, 8_000.0, 12_000.0], (u, h_times[u])
    for u in range(10, 32):                   # ungated control group
        assert h_times[u] == [2_000.0, 6_000.0, 10_000.0], (u, h_times[u])
    for u in range(32, 50):
        assert h_times[u] == [4_000.0, 8_000.0, 12_000.0], (u, h_times[u])
