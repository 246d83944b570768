"""Mesh-sharded ClientPool vs the single-device fused tick.

The mesh path (``ClientPool(tick="device", mesh=4)`` →
``fused_tick.MeshTickDriver``) must be decision-identical to the
single-device fused tick — which PR 6 pinned against the host tick — so
the chain host == device == mesh holds through churn and Beacon
failover.  The comparison needs 4 XLA devices, and
``--xla_force_host_platform_device_count`` is only read at jax
initialisation: each scenario therefore runs in a subprocess
(``tests/_mesh_child.py``) with the flag injected, while this module
stays importable under the tier-1 suite's single-device jax.

``tests/_mesh_child.py`` asserts, in-process:

* candidate matrices / actives / pending / switch records / failover
  counts identical, EMA tables to fp32 rounding — through node churn
  (fail + recover), a Beacon fault-domain failover + recovery, and
  two-round switches;
* border-band users (homed to no region shard — straddling a device
  boundary on the mesh) are served via the fixed-capacity border pass;
* compile-count pin: node churn re-traces no mesh SPMD program.
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

_ROOT = pathlib.Path(__file__).resolve().parents[1]
_OUT = "##OUT##"


def _run_child(n_users: int, n_per_region: int, timeout: float = 600.0,
               refresh_ms: float = 0.0):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [str(_ROOT / "src")] +
        ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.run(
        [sys.executable, str(_ROOT / "tests" / "_mesh_child.py"),
         str(n_users), str(n_per_region)] +
        ([str(refresh_ms)] if refresh_ms else []),
        env=env, capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, \
        f"mesh identity child failed:\n{proc.stdout}\n{proc.stderr}"
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith(_OUT)]
    assert lines, proc.stdout
    return json.loads(lines[-1][len(_OUT):])


@pytest.fixture(scope="module")
def churn_out():
    return _run_child(2_000, 16)


def test_mesh_identity_churn_beacon_failover(churn_out):
    """4-device mesh == single device, decision for decision, through a
    full churn + Beacon-failover cycle (includes the compile-count pin
    and the border-band straddlers — see tests/_mesh_child.py)."""
    out = churn_out
    assert out["ok"]
    assert out["ticks"] >= 8
    assert out["switches"] > 0, "scenario never exercised two-round switch"
    assert out["failovers"] > 0, "scenario never exercised failover"
    assert out["border_users"] > 0


def test_mesh_driver_spans_and_counters(churn_out):
    """The mesh driver records the single-device driver's host spans,
    rebuilds its static arrays and replays breaks as often, and counts
    bytes both ways (its padded blocks move other byte totals)."""
    spans, counts = churn_out["spans"], churn_out["counts"]
    assert spans["mesh"] == spans["single"]
    assert {"transport.static", "transport.admit", "transport.push",
            "fused_tick.dispatch", "fused_tick.wait",
            "fused_tick.pull"} <= set(spans["mesh"])
    single, mesh = counts["single"], counts["mesh"]
    for key in ("static_rebuilds", "breaks"):
        assert mesh[key] == single[key] > 0, key
    for key in ("h2d_bytes", "d2h_bytes"):
        assert mesh[key] > 0 and single[key] > 0, key


@pytest.mark.slow
def test_mesh_identity_incremental_refresh():
    """Incremental candidate refresh on the mesh: with
    ``refresh_period_ms`` set, the 4-device mesh still reproduces the
    single-device decision stream through churn + Beacon failover, the
    host-side dirty-count streams match exactly, and the steady-state
    dirty fraction is genuinely sparse (the whole point of the mode)."""
    out = _run_child(2_000, 16, refresh_ms=6 * 2_000.0)
    assert out["ok"]
    assert out["switches"] > 0 and out["failovers"] > 0
    assert out["dirty_total"] > 0
    assert out["dirty_frac"] < 0.6, \
        f"incremental refresh not sparse: {out['dirty_frac']:.2f}"


@pytest.mark.slow
def test_mesh_identity_10k_users():
    """ISSUE acceptance shape at reduced scale: 10k users, 4 regions."""
    out = _run_child(10_000, 32, timeout=1200.0)
    assert out["ok"]
    assert out["switches"] > 0 and out["failovers"] > 0


@pytest.mark.slow       # ~20 s: registration smoke, not an identity pin
def test_bench_mesh_scale_smoke_profile():
    """The registered benchmark's --smoke profile runs in tier-1: the
    multi-device subprocess harness, mesh driver, churn and per-phase
    breakdown are exercised on every test run."""
    from benchmarks.bench_mesh_scale import derive, run
    rows = run(smoke=True)
    assert len(rows) == 2
    by_kind = {name.rsplit("/", 1)[1]: (ms, d) for name, ms, d in rows}
    assert {"single_d1", "mesh_d4"} <= set(by_kind)
    for kind, (ms, d) in by_kind.items():
        assert ms == ms and ms > 0
        assert "host_devices=4" in d and "phase_fused_tick_ms=" in d
    # identical populations -> identical aggregate data-plane behavior
    def strip(d):
        return [kv for kv in d.split(";")
                if kv.split("=")[0] in ("ticks", "reqs", "mean_frame_ms")]
    assert strip(by_kind["single_d1"][1]) == strip(by_kind["mesh_d4"][1])
    # the weak-scaling hook needs the full-profile rows; on smoke-only
    # input it must produce nothing (never a stale or partial ratio)
    assert derive({name: ms * 1e3 for name, ms, _ in rows}) == []
