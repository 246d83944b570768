"""Armada control-plane tests: geohash properties (hypothesis), simulator
determinism, scheduler policies, 2-step selection, probing/load-balancing,
auto-scaling, and multi-connection failover."""
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import geohash
from repro.core.app_manager import ServiceSpec, Task
from repro.core.beacon import ArmadaSystem, detection_image
from repro.core.cluster import campus_users, emulation, real_world
from repro.core.sim import Simulator
from repro.core.spinner import Image

# ---------------------------------------------------------------------------
# geohash (property-based)
# ---------------------------------------------------------------------------

lat_st = st.floats(min_value=-89.9, max_value=89.9, allow_nan=False)
lon_st = st.floats(min_value=-179.9, max_value=179.9, allow_nan=False)


@given(lat=lat_st, lon=lon_st)
@settings(max_examples=200, deadline=None)
def test_geohash_roundtrip_within_cell(lat, lon):
    gh = geohash.encode(lat, lon, precision=8)
    dlat, dlon, elat, elon = geohash.decode(gh)
    assert abs(dlat - lat) <= elat * 1.0001
    assert abs(dlon - lon) <= elon * 1.0001


@given(lat=lat_st, lon=lon_st, p=st.integers(min_value=1, max_value=8))
@settings(max_examples=100, deadline=None)
def test_geohash_prefix_nesting(lat, lon, p):
    """A point's precision-p hash is a prefix of its precision-(p+1) hash."""
    assert geohash.encode(lat, lon, p + 1).startswith(
        geohash.encode(lat, lon, p))


@given(lat=st.floats(min_value=-60, max_value=60),
       lon=st.floats(min_value=-170, max_value=170),
       dlat=st.floats(min_value=-0.001, max_value=0.001),
       dlon=st.floats(min_value=-0.001, max_value=0.001))
@settings(max_examples=100, deadline=None)
def test_geohash_nearby_points_share_short_prefix(lat, lon, dlat, dlon):
    a = geohash.encode(lat, lon, 9)
    b = geohash.encode(lat + dlat, lon + dlon, 9)
    # ~100 m apart: must share at least the 2-char (~600 km) prefix except
    # at cell boundaries, where the haversine distance still bounds it
    if geohash.common_prefix(a, b) < 2:
        assert geohash.distance_km(lat, lon, lat + dlat, lon + dlon) < 1.0


def test_proximity_search_widens_until_min_hits():
    items = [("near", (45.0, -93.0)), ("far", (45.5, -93.5)),
             ("vfar", (48.0, -97.0))]
    got = geohash.proximity_search((45.0, -93.0), items, min_hits=3)
    assert set(got) == {"near", "far", "vfar"}


# ---------------------------------------------------------------------------
# simulator
# ---------------------------------------------------------------------------

def test_sim_event_ordering_and_determinism():
    order = []
    sim = Simulator(seed=0)
    sim.at(10.0, order.append, "b")
    sim.at(5.0, order.append, "a")
    sim.after(20.0, order.append, "c")
    sim.run()
    assert order == ["a", "b", "c"]
    # same-seed runs give identical jitter streams
    s1, s2 = Simulator(seed=7), Simulator(seed=7)
    assert [s1.jitter(10) for _ in range(5)] == \
        [s2.jitter(10) for _ in range(5)]


def test_sim_cancel():
    sim = Simulator()
    hit = []
    ev = sim.at(5.0, hit.append, 1)
    sim.cancel(ev)
    sim.run()
    assert not hit


# ---------------------------------------------------------------------------
# spinner scheduling
# ---------------------------------------------------------------------------

def _system(**kw):
    topo = real_world()
    sys_ = ArmadaSystem(topo, seed=3, **kw)
    return sys_


def test_initial_deployment_spreads_replicas():
    sys_ = _system()
    spec = ServiceSpec("svc", detection_image(),
                       locations=[sys_.topo.nodes["D6"].loc],
                       min_replicas=5)
    sys_.beacon.deploy_application(spec)
    sys_.sim.run(until=20_000)
    nodes = [t.captain.node_id for t in sys_.am.tasks["svc"]]
    # D6 has 4 slots but resource scoring must spread beyond one node
    assert len(set(nodes)) >= 3


def test_docker_aware_policy_prefers_warm_nodes():
    sys_ = _system()
    img = detection_image()
    sys_.captains["V4"].spec.layers.update(l for l, _ in img.layers)
    t = Task("warm/t0", "warm")
    dt_warm = sys_.spinner.deploy_task(t, img, sys_.topo.nodes["V4"].loc)
    assert t.captain.node_id == "V4"          # layers present -> wins
    assert dt_warm < 1000.0                   # no pull, just start


def test_prefetch_accelerates_second_deploy():
    sys_ = _system()
    img = detection_image()
    t1 = Task("s/t1", "s")
    dt1 = sys_.spinner.deploy_task(t1, img, sys_.topo.nodes["D6"].loc)
    sys_.sim.run(until=60_000)                # prefetch completes
    t2 = Task("s/t2", "s")
    dt2 = sys_.spinner.deploy_task(t2, img, sys_.topo.nodes["D6"].loc,
                                   selection="armada")
    assert dt2 < dt1 * 0.2                    # Fig 9a effect


def test_scheduler_respects_exclusion_and_failure():
    sys_ = _system()
    for name in ("V1", "V2", "V3", "V4", "V5"):
        sys_.captains[name].fail()
    cap = sys_.spinner.select_captain(detection_image(),
                                      sys_.topo.nodes["D6"].loc)
    assert cap.node_id == "D6"


# ---------------------------------------------------------------------------
# 2-step selection + load balancing + failover
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def steady_system():
    sys_ = _system()
    spec = ServiceSpec("detect", detection_image(),
                       locations=[sys_.topo.nodes["D6"].loc],
                       min_replicas=6)
    sys_.beacon.deploy_application(spec)
    sys_.ensure_cloud_replica("detect")
    sys_.sim.run(until=15_000)
    return sys_


def test_candidate_list_is_topn_and_running(steady_system):
    cands = steady_system.am.candidate_list(
        "detect", steady_system.topo.nodes["C1"].loc, "wifi")
    assert 1 <= len(cands) <= steady_system.am.top_n
    assert all(t.status == "running" for t in cands)


def test_probing_selects_min_latency_node():
    sys_ = _system()
    spec = ServiceSpec("detect", detection_image(),
                       locations=[sys_.topo.nodes["D6"].loc],
                       min_replicas=6)
    sys_.beacon.deploy_application(spec)
    sys_.sim.run(until=15_000)
    c = sys_.make_client("C1", "detect")
    sys_.sim.at(15_000, c.start)
    sys_.sim.run(until=45_000)
    # paper Table 6a: C1's best is V1 at ~38 ms
    assert c.active.captain.node_id == "V1"
    assert 30 < c.mean_latency(since=30_000) < 50


def test_load_balancing_emerges_from_probing():
    """When many clients share one area, probing must spread them."""
    sys_ = _system()
    spec = ServiceSpec("detect", detection_image(),
                       locations=[sys_.topo.nodes["D6"].loc],
                       min_replicas=6)
    sys_.beacon.deploy_application(spec)
    sys_.am.autoscale_enabled = False
    sys_.sim.run(until=15_000)
    users = campus_users(sys_.topo, 8, seed=11)
    clients = [sys_.make_client(u, "detect", frame_interval_ms=5.0)
               for u in users]
    for i, c in enumerate(clients):
        sys_.sim.at(15_000 + 200 * i, c.start)
    sys_.sim.run(until=60_000)
    nodes = {c.active.captain.node_id for c in clients}
    assert len(nodes) >= 3                     # not herded on one node


def test_multi_connection_failover_zero_downtime():
    sys_ = _system()
    spec = ServiceSpec("detect", detection_image(),
                       locations=[sys_.topo.nodes["D6"].loc],
                       min_replicas=6)
    sys_.beacon.deploy_application(spec)
    sys_.sim.run(until=15_000)
    c = sys_.make_client("C1", "detect", frame_interval_ms=33.0)
    sys_.sim.at(15_000, c.start)
    sys_.sim.run(until=30_000)
    active = c.active.captain.node_id
    before = len([s for s in c.samples if not s.is_probe])
    sys_.fail_node(active, 30_000)
    sys_.sim.run(until=40_000)
    after = [s for s in c.samples if not s.is_probe and s.t > 30_000]
    assert after, "no frames after failure"
    gap = after[0].t - 30_000
    assert gap < 500.0                          # zero downtime (paper)
    assert c.active.captain.node_id != active
    assert c.active.captain.alive


def test_autoscaler_adds_replicas_under_demand():
    sys_ = _system()
    spec = ServiceSpec("detect", detection_image(),
                       locations=[sys_.topo.nodes["D6"].loc],
                       min_replicas=3)
    sys_.beacon.deploy_application(spec)
    sys_.sim.run(until=15_000)
    n0 = len([t for t in sys_.am.tasks["detect"]
              if t.status in ("running", "deploying")])
    users = campus_users(sys_.topo, 12, seed=13)
    for i, u in enumerate(users):
        c = sys_.make_client(u, "detect", frame_interval_ms=10.0)
        sys_.sim.at(15_000 + i * 100, c.start)
    sys_.sim.run(until=60_000)
    n1 = len([t for t in sys_.am.tasks["detect"]
              if t.status in ("running", "deploying")])
    assert n1 > n0
    assert sys_.am.scale_events
