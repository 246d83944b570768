"""The comparison that decides ``correct``, driven on the CPU at a tiny
size through the harness's own build, warm-up, window and audit (the
look for a chip is skipped): a sound run passes, the bfloat16 controls
fail ``cand_gap`` and ``fold_gap``, and each fault a one-chip cell can
have makes ``correct`` false.  Most faults are planted where the tick's
answers reach the host, the pool's decision mirrors, right after every
tick; the wrong EMA weight is planted in the pool the tick reads it
from."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import deploy, reference, run

CELL = "metro_1k.churn"
SEED = 3_000_000_123


def _tiny():
    cfg = deploy.load_json("configs", "metro_1k")
    cfg.update(users=512, nodes_per_metro=64)
    spec = deploy.load_json("cells", CELL)
    spec["sample_users"] = 512
    traffic = deploy.load_json("traffic", "volunteer_churn")
    # churn hard enough that the small fleet fails over in every window
    traffic["churn"].update(volunteer_mttf_ms=6_000, mttr_ms=4_000)
    return cfg, traffic, spec


def _run(monkeypatch, fault=None):
    cfg, traffic, spec = _tiny()
    build = deploy.build
    kept = {}

    def planted(*a, **kw):
        dep = build(*a, **kw)
        kept["dep"] = dep
        if fault is not None:
            _plant(dep, fault)
        return dep

    monkeypatch.setattr(deploy, "build", planted)
    res = run.run_cell(CELL, cfg, traffic, spec, SEED, 0.2, False,
                       jax.devices()[0])
    assert res["error"] is None
    return res, spec, kept["dep"]


def _plant(dep, fault):
    pool, period = dep.pool, dep.period_ms
    saved = {}
    n_tasks = len(dep.fleet.names)
    rng = np.random.default_rng(SEED)
    if fault == "wrong_alpha":
        pool.alpha = 0.5
        return

    def before():
        saved.update(c=pool.cand_task.copy(), a=pool.active.copy(),
                     p=pool.pending.copy(), t=dep.sim.now)

    def after():
        cand, act, pend = (pool.cand_task.copy(), pool.active.copy(),
                           pool.pending.copy())
        if fault == "failover_random":
            # users whose active node failed since the last tick land on
            # a random one of their candidates
            failed = {e["node"] for e in dep.churn.events
                      if e["kind"] == "leave"
                      and saved["t"] - period < e["t"] < saved["t"]}
            was = saved["a"]
            rows = np.nonzero((was >= 0) & np.isin(
                pool.task_node[np.clip(was, 0, None)], list(failed)))[0]
            for u in rows:
                live = cand[u][cand[u] >= 0]
                if live.size:
                    act[u] = rng.choice(live)
        elif fault == "altered":
            rows = np.arange(0, pool.n_users, 7)
            cand[rows, 0] = (cand[rows, 0] + 1) % n_tasks
        else:
            rows = slice(None) if fault == "unchanged" \
                else np.arange(0, pool.n_users, 2)
            cand[rows], act[rows], pend[rows] = (
                saved["c"][rows], saved["a"][rows], saved["p"][rows])
        pool.cand_task, pool.active, pool.pending = cand, act, pend

    for k in range(1, 400):
        dep.sim.at(k * period - 0.25, before)
        dep.sim.at(k * period + 0.25, after)


def test_sound_run_is_correct(monkeypatch):
    res, spec, _ = _run(monkeypatch)
    ok, checks = run.judge(res["readings"], spec["limits"])
    assert ok, checks
    assert res["readings"]["probe_ticks"] == spec["probe_ticks"] + 2
    assert res["readings"]["failed_over"] > 0
    assert res["window"]["ticks"] >= spec["probe_span"]
    used, slots = res["window"]["ema_slots"]
    assert slots == deploy.load_json("configs", "metro_1k")["ema_slots"]
    assert 0 < used <= slots


def test_bfloat16_control_fails_cand_gap(monkeypatch):
    res, spec, dep = _run(monkeypatch)
    probe_ticks = res["readings"]["probe_ticks"]
    assert probe_ticks == spec["probe_ticks"] + 2
    # the control: the reference with its score, latency and fold
    # arithmetic in bfloat16, put in the program's place
    control = res["probe"].readings(lowp_dtype=jnp.bfloat16)
    assert control["cand_gap"] > spec["limits"]["cand_gap"], control
    assert control["fold_gap"] > spec["limits"]["fold_gap"], control


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered",
                                   "wrong_alpha", "failover_random"])
def test_fault_makes_run_incorrect(monkeypatch, fault):
    res, spec, _ = _run(monkeypatch, fault)
    ok, checks = run.judge(res["readings"], spec["limits"])
    assert not ok, (fault, checks)
    if fault == "wrong_alpha":
        assert checks["fold_gap"]["value"] > checks["fold_gap"]["limit"]
    if fault == "failover_random":
        assert checks["failover_err"]["value"] > 0


@pytest.mark.parametrize("config", ["metro_1k"])
def test_reference_selects_as_engine(config):
    """The reference's Algorithm 1 picks what the program's float64
    engine picks, on a fleet with failed and loaded nodes."""
    cfg = deploy.load_json("configs", config)
    cfg.update(users=400, nodes_per_metro=40)
    fleet = deploy.make_fleet(cfg, SEED)
    system = deploy.build_system(cfg, fleet, SEED)
    caps = [system.captains[n] for n in fleet.names]
    rng = np.random.default_rng(SEED)
    for i in rng.choice(len(caps), len(caps) // 10, replace=False):
        caps[i].fail()
    for c in caps:
        c.fluid_work = float(rng.uniform(0, 3 * c.spec.slots * c.spec.proc_ms))
    users = deploy.make_users(cfg, SEED)
    want = system.am.candidate_indices(cfg["service"], users,
                                       cfg["user_net"], top_n=cfg["top_n"])
    alive = np.asarray([c.alive for c in caps])
    free = reference.free_fraction(
        np.asarray([c.fluid_work for c in caps]),
        np.asarray([c.fluid_updated for c in caps]),
        np.zeros(len(caps)), np.zeros(len(caps)), fleet.slots,
        fleet.proc_ms, system.sim.now)
    s = reference.scores(users, cfg["user_net"], fleet, free, alive)
    np.testing.assert_array_equal(reference.top_k(s, cfg["top_n"]), want)


def test_churn_schedule_is_the_same_for_every_seed():
    """Every seed's fleet fails at the same instants, in bursts, a node of
    the same class each time, and never near a probe tick."""
    cfg = deploy.load_json("configs", "metro_1k")
    traffic = deploy.load_json("traffic", "volunteer_churn")
    plans = []
    for seed in (SEED, 2**31 + 11):
        fleet = deploy.make_fleet(cfg, seed)
        assert fleet.dedicated.sum() == deploy.n_dedicated(cfg)
        churn = deploy.ScheduledChurn(None, None, fleet.dedicated, traffic)
        plans.append((churn, fleet))
    (a, fa), (b, fb) = plans
    assert [t for t, _, _ in a.plan] == [t for t, _, _ in b.plan]
    assert [k for _, _, k in a.plan] == [k for _, _, k in b.plan]
    assert len(a.down0) == len(b.down0)
    np.testing.assert_array_equal(
        fa.dedicated[[n for _, n, _ in a.plan]],
        fb.dedicated[[n for _, n, _ in b.plan]])
    period = traffic["probe_period_ms"]
    times = np.asarray([t for t, _, _ in a.plan])
    phase = times % period
    assert ((phase >= a.EDGE_MS) & (phase <= period - a.EDGE_MS)).all()
    leaves = times[[k == "leave" for _, _, k in a.plan]]
    per_period = np.bincount((leaves // period).astype(int))
    # ~20 volunteers fail a period on average, with Poisson bursts
    assert 17 < per_period.mean() < 24
    assert per_period.max() >= per_period.mean() + 8
