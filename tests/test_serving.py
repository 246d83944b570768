"""Serving engine: slot-scheduler invariants (hypothesis), continuous
batching correctness, greedy-decode equivalence, session failover, and
the ServingProfile surrogate <-> real parity pins."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.config import reduced
from repro.configs import get_config
from repro.models.api import build_model
from repro.serving.batching import GenRequest, SlotScheduler
from repro.serving.engine import ServeEngine
from repro.serving.profile import FAMILIES, ProfileMode, ServingProfile
from repro.serving.session import export_slot, import_session

# ---------------------------------------------------------------------------
# scheduler invariants (property-based)
# ---------------------------------------------------------------------------


@given(st.lists(st.integers(min_value=1, max_value=20), min_size=1,
                max_size=30),
       st.integers(min_value=1, max_value=8))
@settings(max_examples=100, deadline=None)
def test_slot_scheduler_invariants(lengths, max_batch):
    sched = SlotScheduler(max_batch)
    for i, n in enumerate(lengths):
        sched.submit(GenRequest(f"r{i}", [1], max_new_tokens=n))
    served = set()
    for _ in range(10_000):
        sched.admit()
        active = sched.active()
        # invariant: no slot double-booked, occupancy <= max_batch
        slots = [r.slot for r in active]
        assert len(slots) == len(set(slots))
        assert len(active) <= max_batch
        if not active:
            break
        r = active[0]
        r.generated.append(0)
        if len(r.generated) >= r.max_new_tokens:
            sched.complete(r)
            served.add(r.request_id)
        if sched.drain():
            break
    assert served == {f"r{i}" for i in range(len(lengths))}


# ---------------------------------------------------------------------------
# engine correctness
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    cfg = reduced(get_config("qwen3-1.7b"), num_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _greedy_reference(model, params, cfg, prompt, n):
    """Generate greedily via repeated full forward (the slow oracle)."""
    toks = list(prompt)
    for _ in range(n):
        h, _ = model.hidden_states(
            params, {"tokens": jnp.asarray([toks], jnp.int32)})
        w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        logits = h[:, -1] @ w
        toks.append(int(jnp.argmax(logits[0])))
    return toks[len(prompt):]


@pytest.mark.slow
def test_engine_matches_full_forward_generation(tiny):
    cfg, model, params = tiny
    engine = ServeEngine(cfg, params, max_batch=2, max_seq=64, eos_id=-1)
    prompts = [[5, 9, 13], [7, 3, 200, 41]]
    for i, p in enumerate(prompts):
        engine.submit(f"r{i}", p, max_new_tokens=6)
    out = engine.run_until_drained()
    for i, p in enumerate(prompts):
        ref = _greedy_reference(model, params, cfg, p, 6)
        assert out[f"r{i}"] == ref, (out[f"r{i}"], ref)


def test_continuous_batching_interleaves(tiny):
    cfg, model, params = tiny
    engine = ServeEngine(cfg, params, max_batch=2, max_seq=64, eos_id=-1)
    for i in range(5):                      # 5 requests > 2 slots
        engine.submit(f"r{i}", [3 + i], max_new_tokens=4)
    out = engine.run_until_drained()
    assert len(out) == 5
    # equivalence with serial execution
    solo = ServeEngine(cfg, params, max_batch=1, max_seq=64, eos_id=-1)
    for i in range(5):
        solo.submit(f"r{i}", [3 + i], max_new_tokens=4)
    ref = solo.run_until_drained()
    assert out == ref


@pytest.mark.slow
def test_session_failover_preserves_generation(tiny):
    cfg, model, params = tiny
    e1 = ServeEngine(cfg, params, max_batch=2, max_seq=64, eos_id=-1)
    e2 = ServeEngine(cfg, params, max_batch=2, max_seq=64, eos_id=-1)
    prompt = [5, 9, 13]
    n = 8
    e1.submit("mig", prompt, max_new_tokens=n)
    for _ in range(4):
        e1.step()
    blob = e1.export_session("mig")
    import_session(e2, blob)
    out = e2.run_until_drained()
    ref = _greedy_reference(model, params, cfg, prompt, n)
    assert out["mig"] == ref


# ---------------------------------------------------------------------------
# session import under load: queue + re-splice, never drop or corrupt
# ---------------------------------------------------------------------------


class _StubEngine:
    """Session-bookkeeping facade: a real ``SlotScheduler`` plus a small
    device cache, mirroring ``ServeEngine``'s ``_splice``/``_admit``
    resume path without building a model — cheap enough for the property
    test to draw many examples inside tier-1."""

    def __init__(self, max_batch, width=4, name="stub-arch"):
        self.cfg = SimpleNamespace(name=name)
        self.max_batch = max_batch
        self.scheduler = SlotScheduler(max_batch)
        self.cache = {"k": jnp.zeros((max_batch, width), jnp.float32),
                      "len": jnp.zeros((max_batch,), jnp.int32)}
        self.cache_batch_axis = {"k": 0, "len": 0}

    def _splice(self, cache, sub, slot):
        out = {}
        for key, c in cache.items():
            idx = [0] * c.ndim
            idx[self.cache_batch_axis[key]] = slot
            out[key] = jax.lax.dynamic_update_slice(
                c, jnp.asarray(sub[key]).astype(c.dtype), tuple(idx))
        return out

    def _admit(self):
        # ServeEngine._admit's resume branch (the only one imports hit)
        for slot, req in self.scheduler.admit():
            assert req.resume_cache is not None
            self.cache = self._splice(
                self.cache, jax.tree.map(jnp.asarray, req.resume_cache),
                slot)
            req.resume_cache = None


def _donor_blob(j):
    """Export a session whose cache row is distinguishable (100+j)."""
    donor = _StubEngine(1)
    donor.cache = {"k": jnp.full((1, 4), 100.0 + j, jnp.float32),
                   "len": jnp.asarray([40 + j], jnp.int32)}
    req = GenRequest(f"mig{j}", [7, j], 32, generated=[9, j], slot=0)
    donor.scheduler.slots[0] = req
    return export_slot(donor, req)


@given(st.integers(min_value=1, max_value=4),
       st.integers(min_value=1, max_value=4),
       st.integers(min_value=0, max_value=15))
@settings(max_examples=25, deadline=None)
def test_import_session_queues_when_full(max_batch, n_imports, free_mask):
    eng = _StubEngine(max_batch)
    occupants = []
    for s in range(max_batch):
        r = GenRequest(f"occ{s}", [s], 16, generated=[s], slot=s)
        eng.scheduler.slots[s] = r
        occupants.append(r)
    eng.cache = {"k": jnp.arange(max_batch * 4, dtype=jnp.float32)
                 .reshape(max_batch, 4),
                 "len": jnp.arange(max_batch, dtype=jnp.int32)}
    before = jax.tree.map(np.asarray, eng.cache)

    imported = [import_session(eng, _donor_blob(j))
                for j in range(n_imports)]

    # full house: every import queues in FIFO order — nothing dropped,
    # no occupied slot reassigned, no cache row overwritten
    assert [r.request_id for r in eng.scheduler.queue] == \
        [f"mig{j}" for j in range(n_imports)]
    for j, r in enumerate(imported):
        assert r.slot is None and r.resume_cache is not None
        assert r.generated == [9, j]
    for s in range(max_batch):
        assert eng.scheduler.slots[s] is occupants[s]
    after = jax.tree.map(np.asarray, eng.cache)
    np.testing.assert_array_equal(before["k"], after["k"])
    np.testing.assert_array_equal(before["len"], after["len"])

    # free a drawn subset of slots; admission re-splices queued sessions
    # in FIFO order without touching the survivors
    freed = [s for s in range(max_batch) if free_mask >> s & 1]
    for s in freed:
        eng.scheduler.complete(occupants[s])
    eng._admit()
    k = np.asarray(eng.cache["k"])
    ln = np.asarray(eng.cache["len"])
    placed = imported[:min(len(freed), n_imports)]
    taken = [r.slot for r in placed]
    assert len(taken) == len(set(taken))
    for j, r in enumerate(placed):
        assert r.slot in freed and r.resume_cache is None
        np.testing.assert_array_equal(k[r.slot], np.full(4, 100.0 + j))
        assert ln[r.slot] == 40 + j
    for r in imported[len(placed):]:        # overflow stays queued intact
        assert r.slot is None and r.resume_cache is not None
    for s in range(max_batch):              # survivors' rows untouched
        if eng.scheduler.slots[s] in occupants:
            np.testing.assert_array_equal(k[s], before["k"][s])
            assert ln[s] == before["len"][s]


@pytest.mark.slow
def test_import_session_queued_resplices_real(tiny):
    cfg, model, params = tiny
    e1 = ServeEngine(cfg, params, max_batch=1, max_seq=64, eos_id=-1)
    prompt = [5, 9, 13]
    n = 8
    e1.submit("mig", prompt, max_new_tokens=n)
    for _ in range(4):
        e1.step()
    blob = e1.export_session("mig")
    # target replica's only slot is busy -> the import must queue, then
    # re-splice once the occupant finishes; generation stays lossless
    e2 = ServeEngine(cfg, params, max_batch=1, max_seq=64, eos_id=-1)
    e2.submit("busy", [7, 3], max_new_tokens=5)
    e2.step()
    req = import_session(e2, blob)
    assert req.slot is None and req.resume_cache is not None
    out = e2.run_until_drained()
    ref = _greedy_reference(model, params, cfg, prompt, n)
    assert out["mig"] == ref
    assert out["busy"] is not None


def test_session_rejects_cross_arch(tiny):
    cfg, model, params = tiny
    e1 = ServeEngine(cfg, params, max_batch=2, max_seq=64)
    e1.submit("x", [5], max_new_tokens=4)
    e1.step()
    blob = e1.export_session("x")
    cfg2 = reduced(get_config("minicpm-2b"), num_layers=2)
    m2 = build_model(cfg2)
    e2 = ServeEngine(cfg2, m2.init(jax.random.key(1)), max_batch=2,
                     max_seq=64)
    with pytest.raises(AssertionError):
        import_session(e2, blob)


# ---------------------------------------------------------------------------
# ServingProfile: surrogate <-> real parity pins (one per model family)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fam", FAMILIES)
def test_profile_surrogate_real_parity(fam):
    """Fast per-family pin: the surrogate exposes the analytic contract
    (linear request_ms, affine monotone step estimate, nothing measured)
    and a reduced real backend produces finite measured timings through
    the same API.  Full-config real profiles live behind the slow marker
    (test_profile_real_full_config / bench_heterogeneity)."""
    sur = ServingProfile(fam, calibration={})
    assert sur.mode is ProfileMode.SURROGATE
    assert sur.measured_ms() is None
    s1, s2 = sur.estimate_step_ms(1), sur.estimate_step_ms(2)
    assert 0.0 < s1 <= s2 <= 2.0 * s1 + 1e-9      # affine, sub-linear
    assert sur.request_ms(2.0) == pytest.approx(2.0 * sur.unit_ms)
    assert sur.step_ms(2) == pytest.approx(s2)    # surrogate dispatch

    real = ServingProfile(fam, calibration={})
    real.attach_real(reduce_layers=1, max_batch=2, max_seq=32)
    assert real.mode is ProfileMode.REAL
    for b in (1, 2):
        m = real.step_ms(b)
        assert np.isfinite(m) and m > 0.0
    assert real.measured_ms() is not None and real.measured_ms() > 0.0
    # surrogate request_ms is unchanged by attaching a real backend: tick
    # paths consume the analytic unit time either way (device linearity)
    assert real.request_ms(1.5) == pytest.approx(sur.request_ms(1.5))


def test_heartbeat_surfaces_profile():
    from repro.core.captain import Captain
    from repro.core.cluster import NodeSpec, Topology
    from repro.core.sim import Simulator

    prof = ServingProfile("armada-detector", calibration={})
    spec = NodeSpec("N", (0.0, 0.0), 30.0, slots=2, profile=prof)
    sim = Simulator(seed=0)
    cap = Captain(sim, Topology({"N": spec}, {}), spec)
    assert cap.request_ms() == pytest.approx(prof.unit_ms)
    hb = cap.heartbeat()
    assert hb["model"] == "armada-detector"
    assert hb["decode_ms"] is None            # surrogate: nothing measured
    assert hb["occupancy"] == 0.0 and hb["queue_ms"] == 0.0
    # 200 frames x 30 ms >> 2 slots x 1000 ms window: node saturates
    cap.arrive_batch(200.0, 1.0, 1000.0, now=0.0)
    hb2 = cap.heartbeat()
    assert hb2["queue_ms"] > 0.0 and hb2["occupancy"] > 0.0

    # synthetic captains keep the legacy contract
    bare = NodeSpec("M", (0.0, 0.0), 24.0, slots=1)
    cap2 = Captain(sim, Topology({"M": bare}, {}), bare)
    hb3 = cap2.heartbeat()
    assert hb3["model"] == "synthetic" and hb3["decode_ms"] is None
    assert cap2.request_ms(2.0) == 48.0


@pytest.mark.slow
def test_profile_real_full_config():
    """Full-config detector real backend: measured step time is positive
    and the measured EMA lands within an order of magnitude of the
    surrogate estimate (calibration proper runs in bench_heterogeneity)."""
    prof = ServingProfile("armada-detector", calibration={})
    prof.attach_real(max_batch=2)
    m = prof.step_ms(2)
    est = prof.estimate_step_ms(2)
    assert np.isfinite(m) and m > 0.0
    assert prof.measured_ms() == pytest.approx(prof._real.ema())
    assert est > 0.0


def test_bench_serving_selection_smoke_profile():
    """The registered benchmark's --smoke profile runs in tier-1: the
    flash-crowd recovery scenario must show queueing-aware selection
    beating proximity-only on SLO violations (the full 100k profile
    adds the p99 separation)."""
    from benchmarks.bench_serving_selection import derive, run

    rows = run(smoke=True)
    by_name = {r[0]: r for r in rows}
    pre = next(n for n in by_name if n.endswith("/proximity"))[:-len(
        "proximity")]
    base = by_name[pre + "proximity/slo_viol_pct"][1]
    aware = by_name[pre + "queueing/slo_viol_pct"][1]
    assert np.isfinite(base) and np.isfinite(aware)
    # deterministic seeded scenario: the aware run evacuates the dense
    # cluster during recovery, the baseline strands part of it on the
    # drowned nodes
    assert aware < 0.5 * base
    us = {n: (ms * 1e3 if ms is not None else None) for n, ms, _ in rows}
    imp = derive(us)
    assert imp and "slo_viol=" in imp[0][2]
