#!/usr/bin/env python3
"""Smoke run of Armada's selection path on a TPU: the ``geo_topk``
kernel and the fused device tick, at deployment size, through the
entry points a user calls.

    python chip_smoke.py              # one chip: phases 1-3
    python chip_smoke.py --chips 4    # the mesh tick on four chips only

One chip:

1. Kernels.  ``ops.geo_topk`` at 100,000 users against 1,024 and 10,240
   node slots, in the layout ``tune`` picks.  The compiled program must
   hold the Pallas kernel (``tpu_custom_call``); its results are compared
   with ``geo_topk_reference`` on the same chip, and on a sample of users
   with the float64 numpy engine (``SelectionEngine.candidate_indices``).
2. Deployment.  The dense metro fleet of ``bench_client_scale`` (100,000
   users x 1,000 nodes, one replica per node, volunteer churn) through
   ``ClientPool(tick="device")``: 2 warm-up ticks, then 4 more that may
   not recompile or fire an overflow latch.
3. Reference.  The same fleet at 10,000 users for 5 ticks, the device
   tick against the host tick with the ``geo_topk`` backend, decision by
   decision.

Four chips (``--chips 4``): ``ClientPool(mesh=4)`` at 1,000,000 users x
10,000 nodes over the four metros of ``bench_mesh_scale``, its
single-chip control at 250,000 users, and a mesh-vs-single decision
comparison at 100,000 users.

Agreement rule: an index that differs between two paths must be a
near-tie, two candidates whose float64 scores differ by less than
``TIE_GAP``, scored from the inputs as the fp32 paths receive them.  A
tick-by-tick comparison stops at the first tick whose candidates part:
later ticks follow from different candidates.

Prints per-phase results and times (smoke timings, not benchmark
results).  The last line is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Exits non-zero, and prints no such line, where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

TIE_GAP = 1e-5
K = 8
SERVICE = "detect"
KERNEL_MARK = "tpu_custom_call"     # a Pallas kernel in compiled HLO


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(RuntimeError):
    """A phase's result disagrees with what it is checked against."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _f32(a) -> np.ndarray:
    return np.asarray(a, np.float32).astype(np.float64)


def _near_ties(got, want, arrays, free, locs, nets):
    """Differing entries of two (U, k) task-index matrices for users
    ``locs``/``nets``; each must be a near-tie, two candidates whose
    float64 scores differ by less than ``TIE_GAP``.  The scores are taken
    from the inputs as the fp32 paths receive them (coordinates and free
    fractions rounded to fp32).  That rounding alone moves the score of
    a node within a kilometre or so by up to ~1.5e-5 (fp32 longitudes
    near 93 degrees lie 0.6 m apart), so the float64 engine, which ranks
    the unrounded inputs, may order such a pair either way.  Returns
    (count, largest gap, largest gap of the unrounded inputs)."""
    from repro.core.selection import _score_rows
    rows, cols = np.nonzero(got != want)
    a, b = got[rows, cols], want[rows, cols]
    check(((a >= 0) & (b >= 0)).all(),
          f"{int(((a < 0) | (b < 0)).sum())} entries differ in whether a "
          "candidate exists at all")

    def scores(task, f):
        out = np.empty(task.size)
        for lo in range(0, task.size, 512):
            t, r = task[lo:lo + 512], rows[lo:lo + 512]
            out[lo:lo + 512] = np.diag(_score_rows(
                f(arrays.lat[t]), f(arrays.lon[t]), arrays.net_idx[t],
                f(free[t]), f(locs[r]), nets[r]))
        return out

    if not rows.size:
        return 0, 0.0, 0.0
    gap = np.abs(scores(a, _f32) - scores(b, _f32))
    raw = np.abs(scores(a, np.asarray) - scores(b, np.asarray))
    u = rows[np.argmax(gap)]
    check(gap.max() < TIE_GAP,
          f"{int((gap >= TIE_GAP).sum())} differing entries are no "
          f"near-tie: largest float64 gap {gap.max():.3e} (user {u}: "
          f"{got[u].tolist()} vs {want[u].tolist()})")
    return rows.size, float(gap.max()), float(raw.max())


def _metro_users(n_users: int, seed: int):
    """``bench_client_scale``'s population: uniform over the metro."""
    from benchmarks.bench_client_scale import _METRO
    rng = np.random.default_rng(seed + 1)
    return np.stack([_METRO[0] + rng.uniform(-0.5, 0.5, n_users),
                     _METRO[1] + rng.uniform(-0.5, 0.5, n_users)], axis=1)


# --------------------------------------------------------------- phase 1


def kernel_phase(n_users=100_000, node_counts=(1024, 10240), sample=2000,
                 ref_chunk=12_500, seed=0):
    import jax

    from benchmarks.bench_client_scale import _system
    from repro.core.selection import MIN_PROXIMITY_HITS
    from repro.kernels.geo_topk import ops, tune
    from repro.kernels.geo_topk.ref import geo_topk_reference

    reference = jax.jit(geo_topk_reference, static_argnames=("k", "need"))
    locs = _metro_users(n_users, seed)
    nets = np.random.default_rng(seed + 2).integers(0, 3, n_users)
    for n in node_counts:
        sys_ = _system(n, seed)
        eng, tasks = sys_.am.engine, sys_.am.tasks[SERVICE]
        run_ix, packed = eng.prepare_kernel_inputs(SERVICE, tasks, locs,
                                                   nets)
        check(run_ix.size == n, f"{run_ix.size} of {n} replicas running")
        layout = tune.get_config(n_users, n, K)
        t0 = time.perf_counter()
        op = jax.jit(functools.partial(ops.geo_topk, k=K)).lower(packed) \
            .compile()
        compile_s = time.perf_counter() - t0
        check(KERNEL_MARK in op.as_text(),
              "geo_topk compiled without its Pallas kernel")
        s, i = op(packed)
        s.block_until_ready()
        t0 = time.perf_counter()
        s, i = op(packed)
        s.block_until_ready()
        run_ms = (time.perf_counter() - t0) * 1e3
        s, i = np.asarray(s), np.asarray(i)

        # jnp reference on the same chip, in user chunks (its (U, N)
        # score matrix would not fit at once)
        need = min(MIN_PROXIMITY_HITS, n)
        chunk = min(ref_chunk, n_users)
        rs, ri = [], []
        for lo in range(0, n_users, chunk):
            sl = slice(lo, lo + chunk)
            part = type(packed)(*(a[sl] for a in packed[:4]), *packed[4:])
            a, b = reference(*part, k=K, need=need)
            rs.append(np.asarray(a))
            ri.append(np.asarray(b))
        rs, ri = np.concatenate(rs), np.concatenate(ri)
        real = rs > -1e29
        check(np.array_equal(s > -1e29, real),
              "kernel and reference disagree on which slots hold a "
              "candidate")
        ds = float(np.abs(s[real] - rs[real]).max()) if real.any() else 0.0
        check(ds < TIE_GAP, f"kernel vs reference scores differ by {ds}")
        # equal scores at every slot: an index that differs there picks a
        # candidate that ties the reference's to within TIE_GAP
        mism_ref = int(((i != ri) & real).sum())

        # float64 numpy engine on a user sample
        samp = np.sort(np.random.default_rng(seed + 3).choice(
            n_users, min(sample, n_users), replace=False))
        want = eng.candidate_indices(SERVICE, tasks, locs[samp], nets[samp],
                                     top_n=K)
        arr = eng.service_view(SERVICE, tasks)
        _, free = arr.dynamic_state()
        got = np.where(s[samp] > -1e29, run_ix[i[samp]], -1)
        mism64, gap, raw = _near_ties(got, want, arr, free, locs[samp],
                                      nets[samp])
        log(f"[kernels] U={n_users} N={n} layout(block_u, node_tile)="
            f"{layout} compile_s={compile_s:.1f} run_ms={run_ms:.2f} "
            f"tpu_custom_call=yes vs_reference: max_score_diff={ds:.2e} "
            f"index_mismatches={mism_ref} vs_float64({samp.size} users): "
            f"index_mismatches={mism64} (all near-ties, max gap "
            f"{gap:.2e}; {raw:.2e} on unrounded inputs)")
        del sys_, eng, tasks, packed
        gc.collect()


# --------------------------------------------------------------- phase 2


def _metro_pool(n_users, n_nodes, seed, tick, record_samples,
                mttf_factor=40.0):
    """``bench_client_scale._bench_case``'s deployment, unstarted clock."""
    from benchmarks.bench_client_scale import _system
    from repro.core.churn import ChurnModel
    sys_ = _system(n_nodes, seed)
    pool = sys_.make_client_pool(
        SERVICE, locs=_metro_users(n_users, seed), nets="wifi",
        transport="fluid", probe_period_ms=2000.0, frame_interval_ms=1000.0,
        selection_backend="geo_topk", tick=tick,
        record_samples=record_samples)
    sys_.sim.at(0.0, pool.start)
    churn = ChurnModel(sys_.sim, sys_.captains,
                       volunteer_mttf_ms=mttf_factor * 2000.0,
                       mttr_ms=5 * 2000.0)
    churn.start()
    return sys_, pool, churn


def _run_warm(tag, sys_, pool, churn, warm, ticks):
    """Warm up, then time ``ticks`` more ticks; the timed window may not
    recompile or fire a latch.  Returns wall ms per tick."""
    import repro.core.fused_tick as fused_tick
    period = pool.probe_period
    t0 = time.perf_counter()
    sys_.sim.run(until=(warm - 1) * period + 1.0)
    warm_s = time.perf_counter() - t0
    compiles = dict(fused_tick.COMPILE_COUNTS)
    ticks0, reqs0, fo0 = pool.ticks_run, pool.requests_sent, pool.failovers
    phase0 = dict(pool.phase_ms)
    t0 = time.perf_counter()
    sys_.sim.run(until=(warm + ticks - 1) * period + 1.0)
    wall_ms = (time.perf_counter() - t0) * 1e3
    check(not sys_.sim.truncated, "the simulator run was truncated")
    ran = pool.ticks_run - ticks0
    check(ran == ticks, f"{ran} ticks ran, {ticks} expected")
    reqs = pool.requests_sent - reqs0
    check(reqs > 0, "no request was served")
    grew = {k: v - compiles.get(k, 0)
            for k, v in fused_tick.COMPILE_COUNTS.items()
            if v != compiles.get(k, 0)}
    check(not grew, f"programs recompiled after warm-up: {grew}")
    check(not bool(np.asarray(pool._dev.state.ema_overflow).any()),
          "EMA slot overflow latched")
    # a border-band overflow raises inside the tick; refresh overflow
    # is counted by the incremental-refresh tracker
    check(pool._rt is None or pool._rt.fallbacks == 0,
          "refresh overflow latched")
    per_tick = wall_ms / ran
    phases = " ".join(f"{k}={(v - phase0.get(k, 0.0)) / ran:.1f}"
                      for k, v in sorted(pool.phase_ms.items()))
    leaves = sum(1 for e in churn.events if e["kind"] == "leave")
    log(f"[{tag}] U={pool.n_users} nodes={len(sys_.captains)} warm-up "
        f"({warm} ticks, compiles included)={warm_s:.1f}s wall_ms_per_tick="
        f"{per_tick:.1f} phase_ms_per_tick: {phases} requests={reqs} "
        f"failovers={pool.failovers - fo0} node_failures={leaves} "
        f"latches=none recompiles=0")
    return per_tick


def deployment_phase(n_users=100_000, n_nodes=1000, warm=2, ticks=4,
                     seed=0):
    sys_, pool, churn = _metro_pool(n_users, n_nodes, seed, "device",
                                    record_samples=False)
    _run_warm("deployment", sys_, pool, churn, warm, ticks)


# --------------------------------------------------------------- phase 3


class _DynamicStateLog:
    """Keeps the last ``dynamic_state`` sweep (the node free fractions a
    tick scored with), so a differing candidate can be re-scored in
    float64."""

    def __init__(self):
        from repro.core import selection
        orig = selection._ServiceArrays.dynamic_state
        log_ = self
        self.last = None

        def dynamic_state(arrays, *a, **kw):
            out = orig(arrays, *a, **kw)
            log_.last = (arrays, out)
            return out
        selection._ServiceArrays.dynamic_state = dynamic_state


def _lockstep(tag, ref, other, n_ticks, state_log):
    """Step two copies of one deployment tick by tick and compare their
    decisions.  ``ref`` and ``other`` are ``(system, pool)`` pairs."""
    (sa, pa), (sb, pb) = ref, other
    period = pa.probe_period
    for t in range(n_ticks):
        until = t * period + 1.0
        state_log.last = None
        sa.sim.run(until=until)
        swept = state_log.last
        sb.sim.run(until=until)
        part = (pa.cand_task != pb.cand_task).any(axis=1)
        if part.any():
            arrays, (mask, free) = swept
            rows = np.nonzero(part)[0]
            n_diff, gap, raw = _near_ties(
                pa.cand_task[rows], pb.cand_task[rows], arrays, free,
                pa.locs[rows], pa.net_ix[rows])
            same = ~part
            check(np.array_equal(pa.active[same], pb.active[same])
                  and np.array_equal(pa.pending[same], pb.pending[same]),
                  f"tick {t}: users with equal candidates differ in "
                  "active or pending")
            log(f"[{tag}] tick {t}: candidates part at {rows.size} of "
                f"{pa.n_users} users ({n_diff} entries), every one a "
                f"near-tie (max float64 gap {gap:.2e}; {raw:.2e} on "
                "unrounded inputs); the other users' "
                "actives and pending agree.  The paths follow different "
                "candidates from here, so the comparison stops.")
            return
        check(np.array_equal(pa.active, pb.active), f"tick {t}: active")
        check(np.array_equal(pa.pending, pb.pending), f"tick {t}: pending")
        check(pa.failovers == pb.failovers, f"tick {t}: failovers")
        check(pa.requests_sent == pb.requests_sent, f"tick {t}: requests")
        check(list(zip(pa.switch_t, pa.switch_user, pa.switch_from,
                       pa.switch_to))
              == list(zip(pb.switch_t, pb.switch_user, pb.switch_from,
                          pb.switch_to)), f"tick {t}: switch records")
    log(f"[{tag}] {n_ticks} ticks, {pa.n_users} users: candidates, "
        f"actives, pending, switch records ({len(pa.switch_t)}), failovers "
        f"({pa.failovers}) and requests ({pa.requests_sent}) identical")


def reference_phase(state_log, n_users=10_000, n_nodes=1000, n_ticks=5,
                    seed=0):
    host = _metro_pool(n_users, n_nodes, seed, "host", record_samples=True)
    dev = _metro_pool(n_users, n_nodes, seed, "device", record_samples=True)
    _lockstep("reference: host geo_topk tick vs device tick",
              host[:2], dev[:2], n_ticks, state_log)


# ---------------------------------------------------------------- 4 chips


def _mesh_pool(n_users, n_per_region, mesh, seed, record_samples):
    """``bench_mesh_scale._child_case``'s deployment, unstarted clock."""
    from benchmarks.bench_mesh_scale import (FRAME_MS, PROBE_MS, REGIONS,
                                             _build_system)
    from repro.core.churn import ChurnModel
    n_regions = len(REGIONS)
    sys_ = _build_system(n_per_region, n_regions, seed)
    rng = np.random.default_rng(seed + 1)
    region = rng.integers(0, n_regions, n_users)
    locs = np.asarray(REGIONS)[region] + rng.uniform(-0.3, 0.3, (n_users, 2))
    pool = sys_.make_client_pool(
        SERVICE, locs=locs, transport="fluid", probe_period_ms=PROBE_MS,
        frame_interval_ms=FRAME_MS, selection_backend="geo_topk",
        tick="device", mesh=mesh, record_samples=record_samples)
    sys_.sim.at(0.0, pool.start)
    churn = ChurnModel(sys_.sim, sys_.captains,
                       volunteer_mttf_ms=400 * PROBE_MS,
                       mttr_ms=5 * PROBE_MS)
    churn.start()
    return sys_, pool, churn


def _device_memory(devices) -> str:
    out = []
    for d in devices:
        st = d.memory_stats() or {}
        out.append(f"{d.id}:{st.get('bytes_in_use', 0) / 2**30:.2f}/"
                   f"{st.get('peak_bytes_in_use', 0) / 2**30:.2f}")
    return "device:GiB_in_use/peak " + " ".join(out)


def mesh_phase(state_log, n_users=1_000_000, n_per_region=2_500,
               control_users=250_000, cmp_users=100_000, warm=2, ticks=3,
               cmp_ticks=4, seed=0):
    import jax
    devices = jax.devices()[:4]

    sys_, pool, churn = _mesh_pool(n_users, n_per_region, 4, seed, False)
    _run_warm("mesh d4", sys_, pool, churn, warm, ticks)
    rows = {sh.device.id: sh.data.shape[0]
            for sh in pool._dev.state.cand.addressable_shards}
    check(len(rows) == 4, f"user state sits on devices {sorted(rows)}")
    log(f"[mesh d4] user rows per device {dict(sorted(rows.items()))} "
        f"{_device_memory(devices)}")
    del sys_, pool, churn
    gc.collect()

    sys_, pool, churn = _mesh_pool(control_users, n_per_region, None, seed,
                                   False)
    _run_warm("single d1 control", sys_, pool, churn, warm, ticks)
    log(f"[single d1 control] {_device_memory(devices)}")
    del sys_, pool, churn
    gc.collect()

    single = _mesh_pool(cmp_users, n_per_region, None, seed, True)
    mesh = _mesh_pool(cmp_users, n_per_region, 4, seed, True)
    _lockstep("mesh d4 vs single d1", single[:2], mesh[:2], cmp_ticks,
              state_log)


# ------------------------------------------------------------------- main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh tick and its comparisons")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX finds {devices[0].platform!r} "
              "devices only", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, JAX finds {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    log(f"device: {devices[0].device_kind} x{len(devices)}  jax "
        f"{jax.__version__}  compile cache: {cache}")
    state_log = _DynamicStateLog()
    if args.chips == 4:
        phases = [("mesh", functools.partial(mesh_phase, state_log))]
    else:
        phases = [("kernels", kernel_phase),
                  ("deployment", deployment_phase),
                  ("reference", functools.partial(reference_phase,
                                                  state_log))]
    for name, fn in phases:
        t0 = time.perf_counter()
        fn()
        log(f"[{name}] passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
