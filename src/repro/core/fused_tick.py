"""Device-resident fused probe tick for the fluid ``ClientPool``.

PR 2 vectorized the client control plane but each probe tick still
round-tripped device↔host: geo_topk scoring on device, then numpy for
the EMA fold, switch decision and failover pick.  This module runs the
whole tick as ONE jitted program over the pool's SoA state:

    connection breaks (host arrival order; only per-user columns change
                       across breaks, so the per-break loop gathers nothing)
      → EMA fold of the previous traffic window
      → scoring + candidate top-k (same fp32 math as geo_topk)
      → two-round ``switch_decide``
      → next-window traffic masks

When the ``SelectionEngine`` is region-sharded (``shard_precision``),
the scoring step routes each user chunk to its home-region shard — one
(U_s, Ts_pad) pass per shard over gathered node columns with the
proximity filter restricted to the shard prefix — plus one
fixed-capacity border pass (``shard_border_cap`` rows) over the full
node set for users the in-shard widening cannot satisfy.  All shapes
stay jit-stable under churn (per-shard task paddings, static user
routing); only a shard appearing/vanishing retraces, and a border band
larger than its capacity raises rather than dropping users.  Decisions
remain identical to the sharded host tick (tests/test_sharded_selection
pins this on the Fig. 8/10 scenarios).

``FusedTickState`` keeps every pool array resident on device across
ticks (the state buffers are donated, so they update in place); per
tick only small dynamic vectors cross host→device (free fractions,
validity masks, queued node deaths, jitter draws) and only
the per-user decisions the transport needs come back (candidates,
active/pending, switch confirmations, traffic masks).  Shapes are
jit-stable under churn: node/task arrays ride the engine's
``node_pad``-padded layout (``selection.PackedStatic``), the EMA table
is the host ``_EmaTable`` vectorized as fixed-width per-user slots
(see ``FusedTickState``), and breaks are processed through a
fixed-width queue with a dynamic trip count — ``COMPILE_COUNTS`` tracks
trace events so tests can pin "compiles exactly once per program".

Equivalence with the host tick (``ClientPool`` with ``tick="host"``,
``selection_backend="geo_topk"``) is exact in the decision stream —
same candidates, actives, pending nominations, switches and failovers —
because scoring consumes bit-identical fp32 inputs and the policy
functions are the same xp-generic code (``ema_fold``/``switch_decide``/
``failover_pick`` with ``xp=jnp``); EMA values and latencies agree to
fp32 rounding (the host folds in float64).  ``tests/test_fused_tick.py``
pins both on the paper's Fig. 8/10 scenarios.  Two deliberate
approximations, both outside the pinned scenarios: a user who loses
every candidate re-enters initial selection at the next tick boundary
(the host retries ~500 ms earlier), and baseline modes other than
``armada`` are not fused (they stay on the host tick).

The driver at the bottom owns the host glue that cannot leave the
simulator: ``Captain.arrive_batch`` fluid admission, RNG jitter draws in
the exact scalar order (``Simulator.jitter_batch`` parity), switch/break
bookkeeping, and metric mirrors.
"""
from __future__ import annotations

import collections
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.client_pool import (RTT_CLOUD_PENALTY_MS, RTT_LAST_MILE_MS,
                                    RTT_MS_PER_KM, ema_fold, failover_pick,
                                    switch_decide)
from repro.core.selection import MIN_PROXIMITY_HITS
from repro.kernels.geo_topk.ref import (haversine_km, score_matrix,
                                        score_matrix_restricted)

# trace-time counters: a body runs once per compile, so tests can assert
# shape stability under churn (no silent recompiles)
COMPILE_COUNTS: collections.Counter = collections.Counter()

DEATH_QUEUE_MAX = 128          # breaks processed per tick (fixed jit shape)

# the state argument is donated on every backend, so it updates in place
_DONATE = (0,)


class FusedTickState(NamedTuple):
    """Pool SoA state resident on device across ticks.

    The EMA table is the host ``_EmaTable`` vectorized, not densified: a
    fixed-width per-user slot map ``ema_nodes`` (node index, -1 free) /
    ``ema_vals`` (NaN = no sample; pops NaN the value but keep the slot,
    exactly like the host dict-pop semantics).  Memory stays
    O(U × slots), independent of fleet size — a dense (U, N) table would
    cap the very node counts the tiled kernel just unlocked.
    ``ema_overflow`` latches when a user outgrows the slot width (the
    host table would have grown; the driver raises with the remedy)."""
    ema_nodes: jnp.ndarray      # (U, S) i32 node index per slot, -1 free
    ema_vals: jnp.ndarray       # (U, S) f32 EMA per slot, NaN = no sample
    ema_overflow: jnp.ndarray   # () bool
    cand: jnp.ndarray           # (U, k) i32 candidate task positions, -1 pad
    active: jnp.ndarray         # (U,) i32 active task position, -1 none
    pending: jnp.ndarray        # (U,) i32 pending-switch task index, -1 none
    running: jnp.ndarray        # (U,) bool
    ticking: jnp.ndarray        # (U,) bool probe-tick membership
    reinit: jnp.ndarray         # (U,) bool lost every candidate; re-enter
    lat_probe: jnp.ndarray      # (U, k) f32 stashed window latencies, NaN=none
    lat_frame: jnp.ndarray      # (U, nf) f32
    cand_traffic: jnp.ndarray   # (U, k) i32 candidates the stash refers to
    active_traffic: jnp.ndarray  # (U,) i32
    frame_count: jnp.ndarray    # (U,) i32 aggregate frame stats
    frame_sum: jnp.ndarray      # (U,) f32
    failovers: jnp.ndarray      # () i32


class ShardIx(NamedTuple):
    """One region shard's index maps inside the fused tick: the user
    rows homed in this shard (a static partition — user locations never
    move) and the shard's padded global task positions (content changes
    under churn, shape does not).  The shard's user/node attribute
    arrays are gathered from the full ``FusedTickStatic`` on device, so
    these two index vectors are all a shard costs."""
    user_ix: jnp.ndarray        # (Us,) i32 user rows of this shard
    task_ix: jnp.ndarray        # (Ts_pad,) i32 global task positions, -1 pad


class FusedTickStatic(NamedTuple):
    """Per-pool device constants (rebuilt only on node-epoch change)."""
    user_lat: jnp.ndarray       # (U,) f32
    user_lon: jnp.ndarray       # (U,) f32
    user_net: jnp.ndarray       # (U,) i32
    user_code20: jnp.ndarray    # (U,) i32
    task_lat: jnp.ndarray       # (Tp,) f32
    task_lon: jnp.ndarray       # (Tp,) f32
    task_aff: jnp.ndarray       # (M, Tp) f32
    task_code20: jnp.ndarray    # (Tp,) i32
    task_cloud: jnp.ndarray     # (Tp,) f32
    task_node: jnp.ndarray      # (Tp,) i32 node index per task (-1 none)
    node_proc: jnp.ndarray      # (Np,) f32 proc_ms per node
    node_slots: jnp.ndarray     # (Np,) f32 slots per node
    shards: Optional[Tuple[ShardIx, ...]] = None   # region-sharded scoring


class TickOuts(NamedTuple):
    """Per-user decisions handed back to the transport each tick."""
    cand: jnp.ndarray           # (U, k) i32
    active: jnp.ndarray         # (U,) i32
    pending: jnp.ndarray        # (U,) i32
    confirm: jnp.ndarray        # (U,) bool switches confirmed this tick
    from_node: jnp.ndarray      # (U,) i32 pre-switch active node
    probe_ok: jnp.ndarray       # (U, k) bool probes to send this window
    frame_ok: jnp.ndarray       # (U,) bool frames to send this window
    failovers: jnp.ndarray      # () i32 running total
    border_overflow: jnp.ndarray  # () bool sharded border band > capacity
    refresh_fallback: jnp.ndarray  # () bool dirty set > refresh_cap (the
    #                               tick fell back to the dense scan)


# ---------------------------------------------------------------------------
# traced building blocks (shared by the tick and flush programs)
# ---------------------------------------------------------------------------

def _ema_get(nodes_tab, vals_tab, node):
    """Per-row EMA lookup for ``node`` (U,) — NaN when absent; matches
    ``_EmaTable.get`` (including the quirk that node == -1 matches a
    free slot, whose value is NaN anyway)."""
    eq = nodes_tab == node[:, None]                    # (U, S)
    rows = jnp.arange(nodes_tab.shape[0])
    v = vals_tab[rows, eq.argmax(axis=1)]
    return jnp.where(eq.any(axis=1), v, jnp.nan)


def _ema_get_matrix(nodes_tab, vals_tab, node_mat):
    """(U, k) lookup — ``_EmaTable.get_matrix``."""
    return jnp.stack([_ema_get(nodes_tab, vals_tab, node_mat[:, c])
                      for c in range(node_mat.shape[1])], axis=1)


def _ema_fold_into(nodes_tab, vals_tab, overflow, node, lat, m, alpha):
    """One EMA step per row at ``node`` where ``m``: reuse the matching
    slot, else claim the first free one (``_EmaTable.fold`` semantics).
    A row with no free slot latches ``overflow`` — the host table would
    have grown; the driver surfaces it."""
    rows = jnp.arange(nodes_tab.shape[0])
    eq = nodes_tab == node[:, None]
    has = eq.any(axis=1)
    free = nodes_tab == -1
    can_alloc = free.any(axis=1)
    slot = jnp.where(has, eq.argmax(axis=1), free.argmax(axis=1))
    do = m & (has | can_alloc)
    overflow = overflow | (m & ~has & ~can_alloc).any()
    claim = do & ~has
    nodes_tab = nodes_tab.at[rows, slot].set(
        jnp.where(claim, node, nodes_tab[rows, slot]))
    prev = vals_tab[rows, slot]
    prev = jnp.where(has, prev, jnp.nan)               # fresh slot: no prior
    new = jnp.where(do, ema_fold(prev, lat, alpha, xp=jnp),
                    vals_tab[rows, slot])
    return nodes_tab, vals_tab.at[rows, slot].set(new), overflow


def _select(masks, cols, fill):
    """Per row, the column whose mask is set (at most one is), else
    ``fill``: element-wise selects over static columns, no gather."""
    out = fill
    for m, col in zip(masks, cols):
        out = jnp.where(m, col, out)
    return out


def _replay_deaths(state, tn, deaths, n_deaths):
    """The replay proper (``_process_deaths`` runs it only when the
    queue is non-empty)."""
    k = state.cand.shape[1]
    running = state.running
    nodes_tab, vals_tab = state.ema_nodes, state.ema_vals
    cand_node = jnp.where(state.cand >= 0, tn[jnp.clip(state.cand, 0)], -1)
    act_node = jnp.where(state.active >= 0,
                         tn[jnp.clip(state.active, 0)], -1)
    cand_ema = _ema_get_matrix(nodes_tab, vals_tab, cand_node)
    # every node that can hit a user during the replay
    tracked = [cand_node[:, j] for j in range(k)] + [act_node]

    def step(i, carry):
        c, n, e, active, an, reinit, failovers, popped = carry
        d = jax.lax.dynamic_index_in_dim(deaths, i, keepdims=False)
        on_d = an == d
        for nj in n:
            on_d = on_d | (nj == d)
        hit = running & on_d
        popped = [p | (hit & (t == d)) for p, t in zip(popped, tracked)]
        # left-compact the kept columns (``compact_rows`` semantics):
        # output column j takes the kept column of rank j, which can only
        # sit at column j or later
        rank, seen = [], jnp.zeros_like(active)
        for cj, nj in zip(c, n):
            kj = (cj >= 0) & (nj != d)
            seen = seen + kj.astype(seen.dtype)
            rank.append(jnp.where(kj, seen - 1, -1))
        c, n, e = [[jnp.where(hit, _select([r == j for r in rank[j:]],
                                           x[j:], fill), x[j])
                    for j in range(k)]
                   for x, fill in ((c, -1), (n, -1), (e, jnp.nan))]
        act_dead = hit & ((active < 0) | (an == d))
        slot = failover_pick(jnp.stack(c, axis=1), jnp.stack(e, axis=1),
                             xp=jnp)
        at = [slot == j for j in range(k)]
        fail = act_dead & (slot >= 0)
        lost = act_dead & (slot < 0)
        active = jnp.where(fail, _select(at, c, -1), active)
        an = jnp.where(fail, _select(at, n, -1), an)
        active = jnp.where(lost, -1, active)
        an = jnp.where(lost, -1, an)
        failovers = failovers + jnp.sum(fail.astype(jnp.int32))
        reinit = reinit | lost
        return c, n, e, active, an, reinit, failovers, popped

    carry = ([state.cand[:, j] for j in range(k)], tracked[:k],
             [cand_ema[:, j] for j in range(k)], state.active, act_node,
             state.reinit, state.failovers,
             [jnp.zeros_like(running) for _ in tracked])
    c, _, _, active, _, reinit, failovers, popped = jax.lax.fori_loop(
        0, n_deaths, step, carry)
    pop = jnp.zeros(nodes_tab.shape, bool)
    for p, t in zip(popped, tracked):
        pop = pop | (p[:, None] & (nodes_tab == t[:, None]))
    vals_tab = jnp.where(pop, jnp.nan, vals_tab)
    return (nodes_tab, vals_tab, jnp.stack(c, axis=1), active, reinit,
            failovers)


def _process_deaths(state, tn, deaths, n_deaths):
    """Replay queued connection breaks in arrival order — each step is
    ``ClientPool.on_connection_break``'s fluid/armada branch: pop the
    dead node's EMAs for affected users, left-compact their candidate
    rows, instant-failover users whose active died (best known EMA, else
    first candidate, else mark for re-initialization).
    ``deaths[:n_deaths]`` are node indices (>= 0).

    Only the per-user decision vectors change across breaks, so the rest
    is hoisted out of the per-break loop, exactly:

    * pops are deferred, so the EMA slot map and values do not change
      inside the loop: each candidate's EMA is looked up once, before it,
      and travels with its column through compaction (a dead node's
      column is dropped before ``failover_pick`` reads the EMAs);
    * candidates only leave a row, and the active only moves to one of
      the row's candidates (or to -1), so the nodes that can hit a user
      are its k candidate nodes and its active node at loop entry; a
      node leaves that set only at its own first break.

    The loop therefore carries per-user (U,) columns: each candidate, its
    node and its EMA; the active and its node; a "popped" flag for each
    of the k + 1 tracked nodes; ``reinit`` and the ``failovers`` total.
    A step is element-wise compares and selects over the k static
    columns, with no gather or scatter.  After the loop one (U, S) pass
    NaNs the slots of the popped nodes; the fold that could re-seed them
    runs later.  An empty queue skips all of it (``lax.cond``) and
    returns the state's arrays as they are."""
    def skip(state, tn, deaths, n_deaths):
        return (state.ema_nodes, state.ema_vals, state.cand, state.active,
                state.reinit, state.failovers)

    return jax.lax.cond(n_deaths > 0, _replay_deaths, skip,
                        state, tn, deaths, n_deaths)


def _fold_window(state, nodes_tab, vals_tab, tn, alpha):
    """Fold the stashed window's latencies into the EMA table in the
    host flush order: candidate slots left-to-right (== per-(user, node)
    occurrence rank), then frame rounds in arrival order."""
    u, k = state.cand_traffic.shape
    nf = state.lat_frame.shape[1]
    overflow = state.ema_overflow

    ct = state.cand_traffic
    for c in range(k):
        tc = ct[:, c]
        lat = state.lat_probe[:, c]
        node = jnp.where(tc >= 0, tn[jnp.clip(tc, 0)], -1)
        nodes_tab, vals_tab, overflow = _ema_fold_into(
            nodes_tab, vals_tab, overflow, node, lat,
            (node >= 0) & ~jnp.isnan(lat), alpha)
    at_ = state.active_traffic
    fnode = jnp.where(at_ >= 0, tn[jnp.clip(at_, 0)], -1)
    fc, fs = state.frame_count, state.frame_sum
    for j in range(nf):
        lat = state.lat_frame[:, j]
        m = (fnode >= 0) & ~jnp.isnan(lat)
        nodes_tab, vals_tab, overflow = _ema_fold_into(
            nodes_tab, vals_tab, overflow, fnode, lat, m, alpha)
        fc = fc + m.astype(fc.dtype)
        fs = fs + jnp.where(m, lat, 0.0).astype(fs.dtype)
    return nodes_tab, vals_tab, overflow, fc, fs


def _base_rtt(static, tasks):
    """``default_rtt_model`` on device (same constants, fp32)."""
    safe = jnp.clip(tasks, 0)
    ul, uo = static.user_lat, static.user_lon
    if tasks.ndim == 2:
        ul, uo = ul[:, None], uo[:, None]
    d = haversine_km(ul, uo, static.task_lat[safe], static.task_lon[safe])
    return RTT_LAST_MILE_MS + RTT_MS_PER_KM * d \
        + jnp.where(static.task_cloud[safe] > 0, RTT_CLOUD_PENALTY_MS, 0.0)


# ---------------------------------------------------------------------------
# jitted programs
# ---------------------------------------------------------------------------

def _sharded_candidates(static, free, sched, need, k, p_min, border_cap,
                        tick_mask):
    """Region-sharded candidate refresh: each shard's users score only
    that shard's gathered node columns (filter restricted to
    ``p >= p_min``); the border band — users the in-shard widening could
    not satisfy — is gathered into a fixed-capacity buffer
    (``border_cap`` rows, jit-stable) and scored against the full node
    set with the unrestricted filter.  Per-shard (U_s, k) results merge
    by scatter in global task-position space; ``lax.top_k``'s min-index
    ties match the unsharded pass because shard task columns keep
    ascending global order.  Returns ``(new_cand, border_overflow)`` —
    an overflowing border band means dropped users, so the driver
    raises with the remedy instead of serving wrong candidates."""
    u = static.user_lat.shape[0]
    new_cand = jnp.full((u, k), -1, jnp.int32)
    sat_all = jnp.zeros(u, bool)
    for sh in static.shards:
        safe_t = jnp.clip(sh.task_ix, 0)
        t_ok = (sh.task_ix >= 0).astype(jnp.float32)
        s_scores, sat = score_matrix_restricted(
            static.user_lat[sh.user_ix], static.user_lon[sh.user_ix],
            static.user_net[sh.user_ix], static.user_code20[sh.user_ix],
            static.task_lat[safe_t], static.task_lon[safe_t],
            free[safe_t] * t_ok, static.task_aff[:, safe_t],
            static.task_code20[safe_t], sched[safe_t] * t_ok, need, p_min)
        kk = min(k, sh.task_ix.shape[0])
        top_s, top_i = jax.lax.top_k(s_scores, kk)
        g = sh.task_ix[top_i]
        cand_s = jnp.where(top_s > -1e29, g.astype(jnp.int32), -1)
        if kk < k:
            cand_s = jnp.pad(cand_s, ((0, 0), (0, k - kk)),
                             constant_values=-1)
        new_cand = new_cand.at[sh.user_ix].set(cand_s)
        sat_all = sat_all.at[sh.user_ix].set(sat)
    border = tick_mask & ~sat_all
    b_count = border.sum()
    # fill_value=u: out-of-range rows are dropped by the scatter below
    b_ix, = jnp.nonzero(border, size=border_cap, fill_value=u)
    safe_b = jnp.clip(b_ix, 0, u - 1)
    b_scores = score_matrix(
        static.user_lat[safe_b], static.user_lon[safe_b],
        static.user_net[safe_b], static.user_code20[safe_b],
        static.task_lat, static.task_lon, free, static.task_aff,
        static.task_code20, sched, need)
    top_s, top_i = jax.lax.top_k(b_scores, k)
    cand_b = jnp.where(top_s > -1e29, top_i.astype(jnp.int32), -1)
    new_cand = new_cand.at[b_ix].set(cand_b)
    return new_cand, b_count > border_cap


def _shard_refresh_caps(static, refresh_cap: int) -> tuple:
    """Static per-shard sparse-gather capacities: ``refresh_cap`` rows
    per shard, clamped to the shard's population."""
    return tuple(min(int(sh.user_ix.shape[0]), refresh_cap)
                 for sh in static.shards)


def _sharded_candidates_sparse(static, free, sched, need, k, p_min,
                               border_cap, refresh_cap, dirty, cand):
    """Sparse variant of ``_sharded_candidates``: gather only each
    shard's *dirty* rows (``jnp.nonzero(size=cap)`` — the border-band
    idiom, jit-stable shapes under any churn) and scatter their top-k
    straight back into the resident candidate matrix.  Callers must
    guarantee no shard's dirty count exceeds its capacity (the tick
    latches overflow OUTSIDE and takes the dense branch instead — a
    dropped dirty user would silently keep wrong candidates).  Returns
    ``(cand, border_overflow)`` with the refresh already applied; rows
    outside ``dirty`` are untouched bit-for-bit."""
    u = static.user_lat.shape[0]
    sat_all = jnp.zeros(u, bool)
    caps = _shard_refresh_caps(static, refresh_cap)
    for sh, cap_s in zip(static.shards, caps):
        us = sh.user_ix.shape[0]
        l_ix, = jnp.nonzero(dirty[sh.user_ix], size=cap_s, fill_value=us)
        g_ix = sh.user_ix[jnp.clip(l_ix, 0, us - 1)]
        # pad rows (l_ix == us) must drop at the scatter, not clobber the
        # shard's last user — send them out of range
        g_put = jnp.where(l_ix < us, g_ix, u)
        safe_t = jnp.clip(sh.task_ix, 0)
        t_ok = (sh.task_ix >= 0).astype(jnp.float32)
        s_scores, sat = score_matrix_restricted(
            static.user_lat[g_ix], static.user_lon[g_ix],
            static.user_net[g_ix], static.user_code20[g_ix],
            static.task_lat[safe_t], static.task_lon[safe_t],
            free[safe_t] * t_ok, static.task_aff[:, safe_t],
            static.task_code20[safe_t], sched[safe_t] * t_ok, need, p_min)
        kk = min(k, sh.task_ix.shape[0])
        top_s, top_i = jax.lax.top_k(s_scores, kk)
        g = sh.task_ix[top_i]
        cand_s = jnp.where(top_s > -1e29, g.astype(jnp.int32), -1)
        if kk < k:
            cand_s = jnp.pad(cand_s, ((0, 0), (0, k - kk)),
                             constant_values=-1)
        cand = cand.at[g_put].set(cand_s)
        sat_all = sat_all.at[g_put].set(sat)
    # dirty users the in-shard widening could not satisfy (plus dirty
    # users homed to no shard at all) ride the standard border pass
    border = dirty & ~sat_all
    b_count = border.sum()
    b_ix, = jnp.nonzero(border, size=border_cap, fill_value=u)
    safe_b = jnp.clip(b_ix, 0, u - 1)
    b_scores = score_matrix(
        static.user_lat[safe_b], static.user_lon[safe_b],
        static.user_net[safe_b], static.user_code20[safe_b],
        static.task_lat, static.task_lon, free, static.task_aff,
        static.task_code20, sched, need)
    top_s, top_i = jax.lax.top_k(b_scores, k)
    cand_b = jnp.where(top_s > -1e29, top_i.astype(jnp.int32), -1)
    cand = cand.at[b_ix].set(cand_b)
    return cand, b_count > border_cap


def _refresh_candidates(static, cand, reinit, tick_mask, free, sched, need,
                        refresh_ok, dirty, p_min, border_cap,
                        refresh_cap):
    """Step 3 of the tick, the candidate refresh: returns
    ``(cand, border_overflow, refresh_fallback)``."""
    u, k = cand.shape
    if refresh_cap == 0:
        # every-tick refresh (the historical semantics, bit-for-bit)
        refresh_mask = tick_mask & refresh_ok
        if static.shards is None:
            scores = score_matrix(
                static.user_lat, static.user_lon, static.user_net,
                static.user_code20, static.task_lat, static.task_lon, free,
                static.task_aff, static.task_code20, sched, need)
            top_s, top_i = jax.lax.top_k(scores, k)
            new_cand = jnp.where(top_s > -1e29,
                                 top_i.astype(jnp.int32), -1)
            border_overflow = jnp.zeros((), bool)
        else:
            new_cand, border_overflow = _sharded_candidates(
                static, free, sched, need, k, p_min, border_cap,
                refresh_mask)
        cand = jnp.where(refresh_mask[:, None], new_cand, cand)
        refresh_fallback = jnp.zeros((), bool)
    else:
        # incremental refresh: rescore only the dirty rows (host-supplied
        # marks, plus users who just lost every candidate), gathered into
        # a fixed-capacity buffer.  If the dirty set outgrows the buffer
        # the whole tick falls back to the dense scan *applied to exactly
        # the same rows* — identical decisions, latched as
        # ``refresh_fallback`` so the driver can account for it
        dirty_full = (dirty | reinit) & tick_mask & refresh_ok
        if static.shards is None:
            over = dirty_full.sum() > refresh_cap

            def dense_fn(cand_in):
                scores = score_matrix(
                    static.user_lat, static.user_lon, static.user_net,
                    static.user_code20, static.task_lat, static.task_lon,
                    free, static.task_aff, static.task_code20, sched, need)
                top_s, top_i = jax.lax.top_k(scores, k)
                nc = jnp.where(top_s > -1e29, top_i.astype(jnp.int32), -1)
                return (jnp.where(dirty_full[:, None], nc, cand_in),
                        jnp.zeros((), bool))

            def sparse_fn(cand_in):
                d_ix, = jnp.nonzero(dirty_full, size=refresh_cap,
                                    fill_value=u)
                safe_d = jnp.clip(d_ix, 0, u - 1)
                scores = score_matrix(
                    static.user_lat[safe_d], static.user_lon[safe_d],
                    static.user_net[safe_d], static.user_code20[safe_d],
                    static.task_lat, static.task_lon, free,
                    static.task_aff, static.task_code20, sched, need)
                top_s, top_i = jax.lax.top_k(scores, k)
                nc = jnp.where(top_s > -1e29, top_i.astype(jnp.int32), -1)
                # fill rows (d_ix == u) drop at the scatter
                return cand_in.at[d_ix].set(nc), jnp.zeros((), bool)

        else:
            caps = _shard_refresh_caps(static, refresh_cap)
            counts = [dirty_full[sh.user_ix].sum()
                      for sh in static.shards]
            over = jnp.zeros((), bool)
            for c, cap_s in zip(counts, caps):
                over = over | (c > cap_s)

            def dense_fn(cand_in):
                nc, b_over = _sharded_candidates(
                    static, free, sched, need, k, p_min, border_cap,
                    dirty_full)
                return jnp.where(dirty_full[:, None], nc, cand_in), b_over

            def sparse_fn(cand_in):
                return _sharded_candidates_sparse(
                    static, free, sched, need, k, p_min, border_cap,
                    refresh_cap, dirty_full, cand_in)

        cand, border_overflow = jax.lax.cond(over, dense_fn, sparse_fn,
                                             cand)
        refresh_fallback = over
    return cand, border_overflow, refresh_fallback


def _tick_impl(state, static, free, sched, alive, need, deaths, n_deaths,
               alpha, margin, refresh_ok, dirty, p_min, border_cap,
               refresh_cap):
    COMPILE_COUNTS["tick"] += 1
    u, k = state.cand.shape
    rows = jnp.arange(u)
    tn = static.task_node

    # 1. queued connection breaks (before the fold — host breaks happen
    #    mid-window, after traffic was scheduled but before it is folded)
    with jax.named_scope("deaths"):
        enodes, evals, cand, active, reinit, failovers = _process_deaths(
            state, tn, deaths, n_deaths)

    # 2. fold the previous window
    with jax.named_scope("fold"):
        enodes, evals, overflow, fc, fs = _fold_window(
            state, enodes, evals, tn, alpha)

    # 3. candidate refresh: fused scoring + top-k (lax.top_k — the exact
    #    op the geo_topk kernel path dispatches to, same min-index ties) —
    #    one (U, Tp) pass unsharded, or per-shard (U_s, Ts_pad) passes
    #    plus the fixed-capacity border pass when the engine is sharded.
    #    ``refresh_ok`` gates the refresh only: users inside a Beacon
    #    re-discovery window keep (and keep probing) their stale
    #    candidates, exactly like the host tick's filtered ``_refresh``
    tick_mask = state.running & state.ticking
    with jax.named_scope("refresh"):
        cand, border_overflow, refresh_fallback = _refresh_candidates(
            static, cand, reinit, tick_mask, free, sched, need, refresh_ok,
            dirty, p_min, border_cap, refresh_cap)

    # users who lost every candidate re-enter initial selection: active
    # is the best-base-RTT candidate (Client start semantics)
    with jax.named_scope("switch"):
        base = jnp.where(cand >= 0, _base_rtt(static, cand), jnp.inf)
        init_slot = jnp.argmin(base, axis=1)
        has_cand = (cand >= 0).any(axis=1)
        init_active = jnp.where(has_cand, cand[rows, init_slot], -1)
        do_init = reinit & tick_mask
        active = jnp.where(do_init, init_active, active)
        reinit = jnp.where(do_init & has_cand, False, reinit)

        # 4. two-round confirmed switch on the freshly folded EMAs.  The
        #    pending target is judged from the EMA table + task-alive
        #    mask directly (not via candidate-list membership — the
        #    candidate set rotates under load feedback)
        cand_node = jnp.where(cand >= 0, tn[jnp.clip(cand, 0)], -1)
        act_node = jnp.where(active >= 0, tn[jnp.clip(active, 0)], -1)
        cand_ema = _ema_get_matrix(enodes, evals, cand_node)
        act_ema = _ema_get(enodes, evals, act_node)
        pend = state.pending
        pend_node = jnp.where(pend >= 0, tn[jnp.clip(pend, 0)], -1)
        pend_ema = _ema_get(enodes, evals, pend_node)
        pend_alive = (pend >= 0) & alive[jnp.clip(pend, 0)]
        confirm, target, new_pending = switch_decide(
            cand, cand_ema, active, act_ema, pend, pend_ema, pend_alive,
            margin, xp=jnp)
        confirm = confirm & tick_mask
        pending = jnp.where(tick_mask, new_pending, state.pending)
        active = jnp.where(confirm, target, active)

        # 5. next-window traffic: probes to every live candidate, frames
        #    to the live active
        probe_ok = (cand >= 0) & alive[jnp.clip(cand, 0)] \
            & tick_mask[:, None]
        frame_ok = (active >= 0) & alive[jnp.clip(active, 0)] & tick_mask

    nf = state.lat_frame.shape[1]
    new_state = FusedTickState(
        ema_nodes=enodes, ema_vals=evals, ema_overflow=overflow,
        cand=cand, active=active, pending=pending,
        running=state.running, ticking=state.ticking, reinit=reinit,
        lat_probe=jnp.full((u, k), jnp.nan, jnp.float32),
        lat_frame=jnp.full((u, nf), jnp.nan, jnp.float32),
        cand_traffic=cand, active_traffic=active,
        frame_count=fc, frame_sum=fs, failovers=failovers)
    outs = TickOuts(cand=cand, active=active, pending=pending,
                    confirm=confirm, from_node=act_node,
                    probe_ok=probe_ok, frame_ok=frame_ok,
                    failovers=failovers, border_overflow=border_overflow,
                    refresh_fallback=refresh_fallback)
    return new_state, outs


def _traffic_impl(state, static, work0, net_rate, probe_ok, frame_ok,
                  e_rtt_p, e_proc_p, e_back_p, e_rtt_f, e_proc_f, e_back_f,
                  data_f, scale, frame_interval):
    """Fluid-window latencies for the traffic the tick scheduled, stashed
    into the state for the next tick's fold.  Mirrors the host
    ``_traffic_fluid`` arithmetic: ``wait(tau) = max(0, work0 +
    net_rate * tau) / slots``, multiplicative jitter on rtt/proc/back.
    ``data_f`` is the (U,) per-user in-situ data-access term (zeros when
    the pool has no data profile), computed host-side from each user's
    active node and added to FRAME latencies only — probes stay pure
    network/queue measurements, exactly like the host tick."""
    COMPILE_COUNTS["traffic"] += 1
    tn = static.task_node
    nf = state.lat_frame.shape[1]

    ct = state.cand_traffic
    node_p = jnp.clip(tn[jnp.clip(ct, 0)], 0)
    base_p = _base_rtt(static, ct)
    rtt = base_p * (1 + 0.08 * e_rtt_p)
    wait_p = jnp.maximum(0.0, work0[node_p]) / static.node_slots[node_p]
    proc_p = (static.node_proc[node_p] * scale) * (1 + 0.06 * e_proc_p)
    back = (rtt / 2) * (1 + 0.08 * e_back_p)
    lat_p = rtt / 2 + wait_p + jnp.maximum(proc_p, 0.1) + back
    lat_probe = jnp.where(probe_ok, lat_p, jnp.nan)

    at_ = state.active_traffic
    node_f = jnp.clip(tn[jnp.clip(at_, 0)], 0)
    base_f = _base_rtt(static, at_)[:, None]
    tau = ((jnp.arange(nf) + 0.5) * frame_interval)[None, :]
    rtt_f = base_f * (1 + 0.08 * e_rtt_f)
    wait_f = jnp.maximum(
        0.0, work0[node_f][:, None] + net_rate[node_f][:, None] * tau
    ) / static.node_slots[node_f][:, None]
    proc_f = (static.node_proc[node_f][:, None] * scale) \
        * (1 + 0.06 * e_proc_f)
    back_f = (rtt_f / 2) * (1 + 0.08 * e_back_f)
    lat_f = rtt_f / 2 + wait_f + jnp.maximum(proc_f, 0.1) + back_f \
        + data_f[:, None]
    lat_frame = jnp.where(frame_ok[:, None], lat_f, jnp.nan)
    return state._replace(lat_probe=lat_probe, lat_frame=lat_frame)


def _flush_impl(state, static, deaths, n_deaths, alpha):
    """Fold-only step: process queued breaks then fold the open window —
    what the host tick does lazily when metrics are read mid-window."""
    COMPILE_COUNTS["flush"] += 1
    u, k = state.cand.shape
    nf = state.lat_frame.shape[1]
    tn = static.task_node
    with jax.named_scope("deaths"):
        enodes, evals, cand, active, reinit, failovers = _process_deaths(
            state, tn, deaths, n_deaths)
    with jax.named_scope("fold"):
        enodes, evals, overflow, fc, fs = _fold_window(
            state, enodes, evals, tn, alpha)
    return state._replace(
        ema_nodes=enodes, ema_vals=evals, ema_overflow=overflow,
        cand=cand, active=active, reinit=reinit,
        failovers=failovers, frame_count=fc, frame_sum=fs,
        lat_probe=jnp.full((u, k), jnp.nan, jnp.float32),
        lat_frame=jnp.full((u, nf), jnp.nan, jnp.float32))


_fused_tick = jax.jit(_tick_impl, donate_argnums=_DONATE,
                      static_argnames=("p_min", "border_cap",
                                       "refresh_cap"))
_fused_traffic = jax.jit(_traffic_impl, donate_argnums=_DONATE)
_fused_flush = jax.jit(_flush_impl, donate_argnums=_DONATE)


# ---------------------------------------------------------------------------
# mesh-sharded programs (ClientPool(mesh=...))
# ---------------------------------------------------------------------------

class MeshPrograms(NamedTuple):
    tick: object
    traffic: object
    flush: object


def _make_mesh_programs(mesh, users_axis: str, p_min: int, border_cap: int,
                        sharded: bool, refresh_cap: int = 0) -> MeshPrograms:
    """Build the shard_map-wrapped tick/traffic/flush programs for one
    mesh layout.  Each device runs the *same* ``_tick_impl`` body over
    its own (Ud, ...) user block — the block's shards collapse into one
    synthetic union shard whose task list is that device's concatenated
    region task lists (see ``MeshTickDriver``), which is exactly the
    per-shard loop because at ``p >= shard_precision`` a user's prefix
    cells only ever match home-region tasks.  The border band stays a
    *local* fixed-capacity pass against the replicated full node set
    (replicating O(N) node columns is far cheaper than a cross-device
    gather at edge-fleet sizes), so the body needs no collectives at
    all: one SPMD program serves every device, and churn — which changes
    task-list *content*, never shapes — re-traces nothing."""
    from jax.sharding import PartitionSpec as P

    ps_u = P(users_axis)        # leading dim sharded over the population
    ps_r = P()                  # replicated
    static_spec = FusedTickStatic(
        user_lat=ps_u, user_lon=ps_u, user_net=ps_u, user_code20=ps_u,
        task_lat=ps_r, task_lon=ps_r, task_aff=ps_r, task_code20=ps_r,
        task_cloud=ps_r, task_node=ps_r, node_proc=ps_r, node_slots=ps_r,
        shards=None)

    def tick_body(state, static, local_task, free, sched, alive, need,
                  deaths, n_deaths, alpha, margin, refresh_ok, dirty):
        COMPILE_COUNTS["mesh_tick"] += 1
        if sharded:
            ud = state.cand.shape[0]
            st = static._replace(shards=(ShardIx(
                user_ix=jnp.arange(ud, dtype=jnp.int32),
                task_ix=local_task[0]),))
        else:
            st = static
        new_state, outs = _tick_impl(
            state, st, free, sched, alive, need, deaths, n_deaths,
            alpha, margin, refresh_ok, dirty, p_min, border_cap,
            refresh_cap)
        # lift per-device () scalars to (1,) so the global outputs carry
        # one element per device ((D,) — reduced on the host)
        return new_state, outs._replace(
            border_overflow=outs.border_overflow.reshape(1),
            refresh_fallback=outs.refresh_fallback.reshape(1))

    def traffic_body(state, static, work0, net_rate, probe_ok, frame_ok,
                     e1p, e2p, e3p, e1f, e2f, e3f, data_f, scale,
                     frame_interval):
        COMPILE_COUNTS["mesh_traffic"] += 1
        return _traffic_impl(state, static, work0, net_rate, probe_ok,
                             frame_ok, e1p, e2p, e3p, e1f, e2f, e3f,
                             data_f, scale, frame_interval)

    def flush_body(state, static, deaths, n_deaths, alpha):
        COMPILE_COUNTS["mesh_flush"] += 1
        return _flush_impl(state, static, deaths, n_deaths, alpha)

    tick = jax.jit(jax.shard_map(
        tick_body, mesh=mesh,
        in_specs=(ps_u, static_spec, ps_u, ps_r, ps_r, ps_r, ps_r,
                  ps_r, ps_r, ps_r, ps_r, ps_u, ps_u),
        out_specs=ps_u, check_vma=False), donate_argnums=_DONATE)
    traffic = jax.jit(jax.shard_map(
        traffic_body, mesh=mesh,
        in_specs=(ps_u, static_spec, ps_r, ps_r, ps_u, ps_u,
                  ps_u, ps_u, ps_u, ps_u, ps_u, ps_u, ps_u, ps_r, ps_r),
        out_specs=ps_u, check_vma=False), donate_argnums=_DONATE)
    flush = jax.jit(jax.shard_map(
        flush_body, mesh=mesh,
        in_specs=(ps_u, static_spec, ps_r, ps_r, ps_r),
        out_specs=ps_u, check_vma=False), donate_argnums=_DONATE)
    return MeshPrograms(tick=tick, traffic=traffic, flush=flush)


# ---------------------------------------------------------------------------
# host-side driver
# ---------------------------------------------------------------------------

class FusedTickDriver:
    """Owns the device state for one ``ClientPool`` (``tick="device"``)
    and the host glue a tick still needs: fluid admission through the
    captains, jitter draws on the simulator RNG in scalar order, switch
    records and mirror updates.  The pool delegates its probe-tick chain
    here; everything else (start/refresh bookkeeping, metrics surface)
    stays on the pool."""

    def __init__(self, pool, node_pad: int = 256, ema_slots: int = 32):
        self.pool = pool
        self.node_pad = node_pad
        self.ema_slots = ema_slots
        self.deaths: List[int] = []
        self._epoch = -1
        self.static: Optional[FusedTickStatic] = None
        self.state: Optional[FusedTickState] = None
        self.nf = int(pool.probe_period // pool.frame_interval)
        self._stash_dirty = False       # an unfolded window is stashed
        # region sharding (engine-configured): static user→shard routing
        # plus the two static knobs the jitted tick needs.  Routing also
        # depends on the engine's Beacon ownership map — a Beacon handoff
        # (owner_version bump) re-routes the dead domain's users to the
        # adopting shard, a one-time transient like a shard appearing
        self._u_shard = None    # ((precision, owner_version), routed codes)
        self._u_codes = None            # raw (U,) full-precision codes
        self._owner_version = -1
        self.p_min = 0                  # 0 = unsharded scoring
        self.border_cap = 0
        self._all_refresh = None        # cached all-True refresh mask
        self._no_dirty = None           # cached all-False dirty input
        # incremental refresh: sparse-gather capacity (0 = every-tick
        # dense refresh, the bit-for-bit historical program)
        self.refresh_cap = 0
        if pool.refresh_period is not None:
            self.refresh_cap = pool.refresh_cap \
                if pool.refresh_cap is not None \
                else self._default_border_cap()

    def _default_border_cap(self) -> int:
        """Fixed border-band capacity: the cross-shard pass costs
        O(border_cap × N) every tick regardless of how many users are
        actually in the band, so it defaults to U/8 (128-aligned) —
        generous for region-clustered populations, overridable via
        ``ClientPool(shard_border_cap=...)``.  Overflow raises rather
        than dropping users."""
        u = self.pool.n_users
        return min(u, max(128, -(-u // 8 // 128) * 128))

    # ------------------------------------------------------------ setup

    def _packed_user(self):
        from repro.core import geohash
        from repro.kernels.geo_topk.ops import pack_user_inputs
        from repro.core.selection import CODE_PRECISION
        pool = self.pool
        codes = geohash.encode_batch(pool.locs[:, 0], pool.locs[:, 1],
                                     CODE_PRECISION)
        return pack_user_inputs(pool.locs[:, 0], pool.locs[:, 1],
                                pool.net_ix, codes)

    def _node_cap(self) -> int:
        npad = self.node_pad
        return max(npad, -(-len(self.pool._node_ids) // npad) * npad)

    def _host_static_arrays(self, view):
        """Shared host-side assembly of the per-pool constants: packed
        task arrays, node->task map, node proc/slots, packed users."""
        pool = self.pool
        st = view.packed_static(self.node_pad)
        np_cap = self._node_cap()
        if self.static is not None:
            if np_cap != self.static.node_proc.shape[0] or \
                    st.n_pad != self.static.task_lat.shape[0]:
                raise RuntimeError(
                    "fused tick: node/task set outgrew its padding "
                    f"(tasks {st.n_pad}, nodes {np_cap}) — restart the "
                    "pool with a larger node_pad")
        tn = np.full(st.n_pad, -1, np.int32)
        tn[:len(pool.task_node)] = pool.task_node
        proc = np.zeros(np_cap, np.float32)
        slots = np.ones(np_cap, np.float32)
        for i, cap in enumerate(pool._node_caps):
            if cap is not None:
                # serving-profile unit time: static per node-epoch by the
                # linearity contract (request_ms(s) == request_ms()·s), so
                # the device program's node_proc·scale matches the host
                proc[i] = cap.request_ms()
                slots[i] = max(cap.spec.slots, 1)
        ulat, ulon, unet, ucode = self._packed_user()
        return st, tn, proc, slots, ulat, ulon, unet, ucode

    def _count_static(self, view, st):
        """Count a static rebuild, and the node arrays the view uploads
        once per node epoch (``packed_static``)."""
        spans = self.pool.spans
        spans.count("static_rebuilds", 1)
        if view.epoch != self._epoch:
            spans.count("h2d_bytes", sum(
                x.nbytes for x in (st.lat, st.lon, st.aff, st.code20,
                                   st.cloud)))

    def _rebuild_static(self, view):
        pool = self.pool
        st, tn, proc, slots, ulat, ulon, unet, ucode = \
            self._host_static_arrays(view)
        self._count_static(view, st)
        pool.spans.h2d(ulat, ulon, unet, ucode, tn, proc, slots)
        self.static = FusedTickStatic(
            user_lat=jnp.asarray(ulat), user_lon=jnp.asarray(ulon),
            user_net=jnp.asarray(unet), user_code20=jnp.asarray(ucode),
            task_lat=st.lat, task_lon=st.lon, task_aff=st.aff,
            task_code20=st.code20, task_cloud=st.cloud,
            task_node=jnp.asarray(tn), node_proc=jnp.asarray(proc),
            node_slots=jnp.asarray(slots),
            shards=self._build_shards())
        self._epoch = view.epoch
        self._owner_version = pool.am.engine.owner_version

    def _build_shards(self) -> Optional[tuple]:
        """Per-shard index maps for the sharded scoring step (None when
        the engine is unsharded).  User→shard routing is computed once —
        locations never move; a shard's ``task_ix`` content changes under
        churn while its padded shape stays put (reused device arrays via
        the engine's per-shard adoption).  A shard appearing or vanishing
        changes the static pytree and retraces the tick once — a rare,
        coarse-region event, unlike per-tick churn."""
        pool = self.pool
        engine = pool.am.engine
        shard_view = engine.shard_view(
            pool.service_id, pool.am.tasks.get(pool.service_id, ()))
        if shard_view is None:
            self.p_min = 0
            self.border_cap = 0
            return None
        route_key = (shard_view.precision, shard_view.owner_version)
        if self._u_shard is None or self._u_shard[0] != route_key:
            if self._u_codes is None:
                from repro.core import geohash
                from repro.core.selection import CODE_PRECISION
                self._u_codes = geohash.encode_batch(
                    pool.locs[:, 0], pool.locs[:, 1], CODE_PRECISION)
            self._u_shard = (route_key, shard_view.route(self._u_codes))
        u_shard = self._u_shard[1]
        entries = []
        for sh in shard_view.shards:
            user_ix = np.nonzero(u_shard == sh.code)[0]
            if user_ix.size == 0:
                continue        # border pass covers its nodes if needed
            user_ix = user_ix.astype(np.int32)
            task_ix = sh.task_ix_padded(self.node_pad)
            pool.spans.h2d(user_ix, task_ix)
            entries.append(ShardIx(user_ix=jnp.asarray(user_ix),
                                   task_ix=jnp.asarray(task_ix)))
        self.p_min = shard_view.precision
        self.border_cap = pool.shard_border_cap \
            if pool.shard_border_cap is not None \
            else self._default_border_cap()
        return tuple(entries)

    def init_state(self):
        """Upload the pool mirrors (populated by the host-side initial
        refresh) as the resident device state."""
        pool = self.pool
        view = pool._view()
        self._rebuild_static(view)
        u, k = pool.cand_task.shape
        self.state = FusedTickState(
            ema_nodes=jnp.full((u, self.ema_slots), -1, jnp.int32),
            ema_vals=jnp.full((u, self.ema_slots), jnp.nan, jnp.float32),
            ema_overflow=jnp.zeros((), bool),
            cand=jnp.asarray(pool.cand_task),
            active=jnp.asarray(pool.active),
            pending=jnp.asarray(pool.pending),
            running=jnp.asarray(pool.running),
            ticking=jnp.asarray(pool.ticking),
            reinit=jnp.zeros(u, bool),
            lat_probe=jnp.full((u, k), jnp.nan, jnp.float32),
            lat_frame=jnp.full((u, self.nf), jnp.nan, jnp.float32),
            cand_traffic=jnp.full((u, k), -1, jnp.int32),
            active_traffic=jnp.full(u, -1, jnp.int32),
            frame_count=jnp.zeros(u, jnp.int32),
            frame_sum=jnp.zeros(u, jnp.float32),
            failovers=jnp.zeros((), jnp.int32))

    # ------------------------------------------------------------- tick

    def _drain_deaths(self):
        deaths = self.deaths
        self.deaths = []
        if len(deaths) > DEATH_QUEUE_MAX:
            raise RuntimeError(
                f"{len(deaths)} breaks in one window > DEATH_QUEUE_MAX")
        arr = np.full(DEATH_QUEUE_MAX, -1, np.int32)
        arr[:len(deaths)] = deaths
        self.pool.spans.count("breaks", len(deaths))
        # programs that run the replay loop (queue non-empty)
        self.pool.spans.count("replay_ticks", int(len(deaths) > 0))
        return arr, np.int32(len(deaths))

    def _refresh_mask(self):
        """(U,) bool — False for users inside a Beacon re-discovery
        window (``discovery_ms``); they keep their stale candidates for
        the tick, exactly like the host tick's filtered ``_refresh``."""
        m = self.pool._discovery_refresh_mask()
        if m is None:
            if self._all_refresh is None:
                self._all_refresh = np.ones(self.pool.n_users, bool)
            m = self._all_refresh
        return m

    def _dirty_input(self):
        """(U,) bool dirty rows for the tick program (pool order), or the
        cached all-False array when refresh is every-tick."""
        pool = self.pool
        if pool._rt is None:
            if self._no_dirty is None:
                self._no_dirty = np.zeros(pool.n_users, bool)
            return self._no_dirty
        with pool.spans.span("refresh_track"):
            return pool._rt.dirty_mask(pool.sim.now)

    def _note_refreshed(self, dirty, r_ok, outs):
        """Mirror the program's refresh set back into the tracker: clear
        marks, re-arm deadlines, account dirty fraction and fallbacks.
        (In-program reinit rows refresh too but have no host mark — the
        tracker only ever over-refreshes, never misses.)"""
        pool = self.pool
        rt = pool._rt
        if rt is None:
            return
        refreshed = dirty & pool.running & pool.ticking & r_ok
        if bool(pool.spans.d2h(outs.refresh_fallback).any()):
            rt.fallbacks += 1
        rt.note_refreshed(refreshed, pool.sim.now)
        rt.dirty_counts.append(int(refreshed.sum()))

    def _run_tick(self, free, sched, alive, need, deaths, n_deaths):
        """Run the tick program; returns per-user decision arrays in the
        pool's (original) user order."""
        pool = self.pool
        dirty = self._dirty_input()
        r_ok = self._refresh_mask()
        with pool.spans.span("fused_tick.dispatch"):
            pool.spans.h2d(free, sched, alive, need, deaths, n_deaths,
                           r_ok, dirty)
            self.state, outs = _fused_tick(
                self.state, self.static, free, sched, alive, need, deaths,
                n_deaths, pool.alpha, pool.switch_margin, r_ok, dirty,
                p_min=self.p_min, border_cap=self.border_cap,
                refresh_cap=self.refresh_cap)
        self._stash_dirty = False       # tick folded the previous window
        with pool.spans.span("fused_tick.wait"):
            border_overflow = pool.spans.d2h(outs.border_overflow)
        if bool(border_overflow.any()):
            raise RuntimeError(
                f"fused tick: border band exceeded {self.border_cap} "
                "users — restart the pool with a larger shard_border_cap "
                "(or a coarser shard_precision)")
        self._note_refreshed(dirty, r_ok, outs)
        return outs

    def tick(self):
        pool = self.pool
        span = pool.spans.span
        with span("transport"):
            with span("transport.static"):
                view = pool._view()
                engine = pool.am.engine
                if view.epoch != self._epoch \
                        or engine.owner_version != self._owner_version:
                    # node-epoch change, or a Beacon handoff/re-home
                    # re-routed regions (the transient: shard structure
                    # may retrace once)
                    self._rebuild_static(view)
            with span("transport.dynamic"):
                free, sched, alive = view.padded_dynamic(
                    self.node_pad, hidden=engine.hidden_nodes,
                    locality=engine.data_locality.get(pool.service_id),
                    queueing=engine.queueing.get(pool.service_id))
                need = np.int32(min(MIN_PROXIMITY_HITS, int(sched.sum())))
                deaths, n_deaths = self._drain_deaths()

        with span("fused_tick"):
            outs = self._run_tick(free, sched, alive, need, deaths,
                                  n_deaths)
            with span("fused_tick.pull"):
                cand = self._pull(outs.cand)
                active = self._pull(outs.active)
                probe_ok = self._pull(outs.probe_ok)
                frame_ok = self._pull(outs.frame_ok)
                confirm = self._pull(outs.confirm)

        with span("transport"):
            with span("transport.mirrors"):
                # mirrors + switch records (scalar-identical
                # timestamps/order)
                pool.cand_task = cand
                pool.active = active
                pool.pending = self._pull(outs.pending)
                pool.failovers = int(pool.spans.d2h(outs.failovers).sum())
                self.check_overflow()
                self._record_switches(confirm, active, outs)
            self._send_traffic(cand, active, probe_ok, frame_ok)

        if bool((pool.running & pool.ticking).any()):
            pool.ticks_run += 1
            pool.sim.after(pool.probe_period, self.tick)

    def _record_switches(self, confirm, active, outs):
        """Per-switch records match the host tick's (time, user, from,
        to) stream; population-scale runs opt out via
        record_samples=False (the host tick has no such toggle — it pays
        the append cost)."""
        pool = self.pool
        rows = np.nonzero(confirm)[0]
        if not (rows.size and pool.record_samples):
            return
        from_node = self._pull(outs.from_node)
        now = pool.sim.now
        for u in rows:
            pool.switch_t.append(now)
            pool.switch_user.append(int(u))
            pool.switch_from.append(pool._node_ids[int(from_node[u])])
            pool.switch_to.append(
                pool._node_ids[pool.task_node[int(active[u])]])

    def _send_traffic(self, cand, active, probe_ok, frame_ok):
        """Admit one window of fluid traffic and stash its latencies:
        per-node ``arrive_batch`` in ascending node order, then the three
        jitter draws in the host tick's exact element order (probes
        row-major, then frames user-major)."""
        pool = self.pool
        span = pool.spans.span
        nf = self.nf
        p_cnt = int(probe_ok.sum())
        f_cnt = int(frame_ok.sum())
        total = p_cnt + f_cnt * nf
        with span("transport.admit"):
            p_tasks = cand[probe_ok]
            p_nodes = pool.task_node[p_tasks]
            f_nodes = pool.task_node[active[frame_ok]]
            n_nodes = len(pool._node_ids)
            counts = np.bincount(p_nodes, minlength=n_nodes)
            counts += nf * np.bincount(f_nodes, minlength=n_nodes)
            pool.watch_node_indices(np.nonzero(counts)[0])
            if total == 0:
                return
            np_cap = self._node_cap()
            work0 = np.zeros(np_cap, np.float32)
            net_rate = np.zeros(np_cap, np.float32)
            now = pool.sim.now
            for nix in np.nonzero(counts)[0]:
                cap = pool._node_caps[nix]
                w0, in_rate, cap_rate = cap.arrive_batch(
                    int(counts[nix]), pool.workload_scale,
                    pool.probe_period, now)
                work0[nix] = w0
                net_rate[nix] = in_rate - cap_rate
            pool.requests_sent += total

        def split(e):
            dp = np.zeros(probe_ok.shape, np.float32)
            dp[probe_ok] = e[:p_cnt]
            df = np.zeros((len(frame_ok), nf), np.float32)
            df[frame_ok] = e[p_cnt:].reshape(-1, nf)
            return dp, df

        with span("transport.jitter"):
            eps = [pool.sim.rng.standard_normal(total) for _ in range(3)]
            (e1p, e1f), (e2p, e2f), (e3p, e3f) = map(split, eps)
        # in-situ data access rides the frame (request) path only — the
        # per-user term is host-computed once and injected into every
        # backend identically (decision identity by construction)
        data_f = np.zeros(len(frame_ok), np.float32)
        data = pool._data_node_ms()
        if data is not None and f_nodes.size:
            data_f[frame_ok] = data[f_nodes]
            nearest, reps = pool._data_reps
            reads = pool.data_profile.reads_per_request * nf
            rep_counts = np.bincount(nearest[f_nodes],
                                     minlength=len(reps)) * reads
            pool.am.cargo_manager.note_read_load(
                pool.service_id, reps, rep_counts, pool.probe_period)
        with span("transport.push"):
            self._push_traffic(work0, net_rate, probe_ok, frame_ok, data_f,
                               ((e1p, e1f), (e2p, e2f), (e3p, e3f)))
        self._stash_dirty = True
        if pool._lat_hist is not None:
            # frame-latency histogram (latency_hist=True): each window's
            # latency stash is pulled exactly once, right after it is
            # computed — one device round-trip per tick, bench-only
            lat = self._pull(self.state.lat_frame)
            lat = lat[np.isfinite(lat)]
            if lat.size:
                pool._lat_hist += np.histogram(
                    lat, bins=pool._lat_edges)[0]

    def _push_traffic(self, work0, net_rate, probe_ok, frame_ok, data_f,
                      splits):
        pool = self.pool
        (e1p, e1f), (e2p, e2f), (e3p, e3f) = splits
        pool.spans.h2d(work0, net_rate, probe_ok, frame_ok, e1p, e2p, e3p,
                       e1f, e2f, e3f, data_f)
        self.state = _fused_traffic(
            self.state, self.static, work0, net_rate, probe_ok, frame_ok,
            e1p, e2p, e3p, e1f, e2f, e3f, data_f, pool.workload_scale,
            pool.frame_interval)

    # ------------------------------------------------------- maintenance

    def _pull(self, arr) -> np.ndarray:
        """Device per-user array -> host numpy in pool (original) user
        order (its bytes counted); the mesh driver overrides with the
        inverse permutation."""
        return self.pool.spans.d2h(arr)

    def _run_flush(self, deaths, n_deaths):
        self.state = _fused_flush(self.state, self.static, deaths,
                                  n_deaths, self.pool.alpha)

    def flush(self):
        """Process queued breaks + fold the open window (metric reads).
        Free when nothing is pending — no device round-trip."""
        if self.state is None or not (self._stash_dirty or self.deaths):
            return
        deaths, n_deaths = self._drain_deaths()
        self._stash_dirty = False
        self._run_flush(deaths, n_deaths)
        pool = self.pool
        pool.cand_task = self._pull(self.state.cand)
        pool.active = self._pull(self.state.active)
        pool.failovers = int(pool.spans.d2h(self.state.failovers).sum())

    def sync_aggregates(self):
        self.flush()
        pool = self.pool
        pool.frame_count = self._pull(self.state.frame_count)\
            .astype(np.int64)
        pool.frame_sum = self._pull(self.state.frame_sum)\
            .astype(np.float64)

    def reset_aggregates(self):
        self.flush()
        self.state = self.state._replace(
            frame_count=jnp.zeros_like(self.state.frame_count),
            frame_sum=jnp.zeros_like(self.state.frame_sum))

    def set_running(self, running: np.ndarray):
        self.state = self.state._replace(running=jnp.asarray(running))

    def on_break(self, node_ix: int):
        self.deaths.append(int(node_ix))

    def check_overflow(self):
        if bool(self.pool.spans.d2h(self.state.ema_overflow).any()):
            raise RuntimeError(
                f"fused tick: a user outgrew its {self.ema_slots} EMA "
                "slots — restart the pool with a larger ema_slots")

    def _row(self, u: int) -> int:
        """Pool user index -> device state row (mesh driver permutes)."""
        return u

    def ema_dict(self, u: int):
        """Per-user node-id -> EMA map (tests/metrics; mirrors
        ``_EmaTable.as_dict``)."""
        self.flush()
        r = self._row(u)
        nodes = np.asarray(self.state.ema_nodes[r])
        vals = np.asarray(self.state.ema_vals[r], np.float64)
        ids = self.pool._node_ids
        return {ids[n]: float(v) for n, v in zip(nodes, vals)
                if n >= 0 and not np.isnan(v)}


# ---------------------------------------------------------------------------
# mesh driver
# ---------------------------------------------------------------------------

# pad-row fill per state field (device blocks are padded to a uniform
# per-device row count; pad rows are permanently not-running)
_STATE_PAD_FILL = dict(
    ema_nodes=-1, ema_vals=np.nan, cand=-1, active=-1, pending=-1,
    running=False, ticking=False, reinit=False, lat_probe=np.nan,
    lat_frame=np.nan, cand_traffic=-1, active_traffic=-1,
    frame_count=0, frame_sum=0.0)


class MeshTickDriver(FusedTickDriver):
    """Mesh-sharded fused tick (``ClientPool(mesh=...)``): the user
    population is split into per-device blocks by home region — region
    shards are bin-packed onto devices by user count — and every device
    runs the same SPMD tick body over only its own block.

    Identity with the single-device tick is structural, not numeric
    luck: a device block's region shards collapse into one synthetic
    union shard (its concatenated task lists), and at
    ``p >= shard_precision`` a user's proximity cells only ever match
    home-region tasks, so the union pass computes exactly the per-shard
    loop — same scores, same ascending-global-order ties.  Users the
    in-region widening cannot satisfy escalate to a per-device
    fixed-capacity border pass over the *replicated* full node set,
    which is verbatim the unsharded scoring pass — so even a user
    straddling a device boundary gets bit-identical candidates; device
    placement can only ever cost border capacity, never correctness.

    The host-visible decision stream stays in pool (original) user
    order: ``_perm``/``_pos`` translate between pool order and
    device-block order, so RNG draws, arrive_batch admission and switch
    records replay in the exact single-device sequence.  A Beacon
    handoff or node-epoch change that re-routes users across device
    boundaries re-homes them wholesale: state is pulled to pool order
    under the old placement and re-uploaded under the new one (at most
    one retrace — block shapes only ever grow, and churn changes task
    *content*, never shapes, so steady-state ticks never retrace)."""

    def __init__(self, pool, mesh, node_pad: int = 256,
                 ema_slots: int = 32):
        super().__init__(pool, node_pad=node_pad, ema_slots=ema_slots)
        from repro.distributed.sharding import make_pool_rules
        if len(mesh.axis_names) != 1:
            raise ValueError(
                "ClientPool mesh must be 1-D (a single users axis); "
                f"got axes {mesh.axis_names}")
        self.mesh = mesh
        self.users_axis = mesh.axis_names[0]
        self.n_dev = int(mesh.devices.size)
        self.rules = make_pool_rules(mesh)
        self._sharded = False
        self._ud = 0            # per-device user rows (monotonic)
        self._tloc = 0          # per-device task columns (monotonic)
        self._perm = None       # (Up,) device row -> pool user, -1 pad
        self._pos = None        # (U,) pool user -> device row
        self._valid = None      # (Up,) bool real rows
        self._local_task = None  # (D, Tloc) device-resident task lists
        self._programs = {}
        self._state_sh = None
        self._static_sh = None
        self._lt_sh = None

    # --------------------------------------------------------- placement

    def _default_border_cap(self) -> int:
        """Per-device border capacity (the border pass is local — each
        device escalates only its own block's unsatisfied users).  Also
        the per-device default for ``refresh_cap`` — before placement
        (``_ud`` unset) it returns a placeholder that
        ``_compute_placement`` re-derives."""
        ud = max(getattr(self, "_ud", 0), 1)
        return min(ud, max(128, -(-ud // 8 // 128) * 128))

    def _compute_placement(self):
        """Route users to region shards, bin-pack shards onto devices,
        and derive the block permutation + per-device task lists."""
        pool = self.pool
        engine = pool.am.engine
        D = self.n_dev
        u = pool.n_users
        if self._u_codes is None:
            from repro.core import geohash
            from repro.core.selection import CODE_PRECISION
            self._u_codes = geohash.encode_batch(
                pool.locs[:, 0], pool.locs[:, 1], CODE_PRECISION)
        shard_view = engine.shard_view(
            pool.service_id, pool.am.tasks.get(pool.service_id, ()))
        if shard_view is None:
            # unsharded engine: contiguous blocks, each device scores
            # its users against the full replicated set — identity by
            # construction (no region structure to exploit)
            self._sharded = False
            self.p_min = 0
            blocks = [b for b in
                      np.array_split(np.arange(u, dtype=np.int64), D)]
            local_cols = [np.full(1, -1, np.int32) for _ in range(D)]
        else:
            self._sharded = True
            from repro.core.selection import assign_shards_to_devices
            route_key = (shard_view.precision, shard_view.owner_version)
            if self._u_shard is None or self._u_shard[0] != route_key:
                self._u_shard = (route_key,
                                 shard_view.route(self._u_codes))
            u_shard = self._u_shard[1]
            shards = [(sh, np.nonzero(u_shard == sh.code)[0])
                      for sh in shard_view.shards]
            shards = [(sh, ix) for sh, ix in shards if ix.size]
            assign, _ = assign_shards_to_devices(
                [ix.size for _, ix in shards], D)
            users_d = [[] for _ in range(D)]
            tasks_d = [[] for _ in range(D)]
            for (sh, ix), d in zip(shards, assign):
                users_d[d].append(ix)
                tasks_d[d].append(sh.task_ix_padded(self.node_pad))
            # users routed to no shard always escalate to the (local,
            # full-set) border pass — park them on the lightest device
            homed = np.zeros(u, bool)
            for _, ix in shards:
                homed[ix] = True
            orphans = np.nonzero(~homed)[0]
            if orphans.size:
                d = int(np.argmin([sum(x.size for x in b)
                                   for b in users_d]))
                users_d[d].append(orphans)
            blocks = [np.concatenate(b).astype(np.int64) if b
                      else np.empty(0, np.int64) for b in users_d]
            local_cols = [np.concatenate(t) if t
                          else np.full(1, -1, np.int32) for t in tasks_d]
            self.p_min = shard_view.precision
        # uniform per-device sizes, monotonic: a handoff can only grow
        # them (one retrace), steady-state churn changes content only
        need_ud = max(1, max(b.size for b in blocks))
        self._ud = max(self._ud, -(-need_ud // 64) * 64)
        self._tloc = max(self._tloc, max(c.size for c in local_cols))
        up = D * self._ud
        perm = np.full(up, -1, np.int64)
        for d, b in enumerate(blocks):
            perm[d * self._ud: d * self._ud + b.size] = b
        valid = perm >= 0
        pos = np.empty(u, np.int64)
        pos[perm[valid]] = np.nonzero(valid)[0]
        self._perm, self._pos, self._valid = perm, pos, valid
        lt = np.full((D, self._tloc), -1, np.int32)
        for d, c in enumerate(local_cols):
            lt[d, :c.size] = c
        self.border_cap = pool.shard_border_cap \
            if pool.shard_border_cap is not None \
            else self._default_border_cap()
        if pool.refresh_period is not None and pool.refresh_cap is None:
            # per-device sparse capacity needs _ud — re-derive now that
            # placement fixed it (monotonic, so the program cache key
            # changes at most when a block grows)
            self.refresh_cap = self._default_border_cap()
        return lt

    def _to_dev(self, arr, fill=0):
        """Pool-order (U, ...) host array -> padded device-order
        (Up, ...)."""
        arr = np.asarray(arr)
        out = np.full((self._perm.shape[0],) + arr.shape[1:], fill,
                      arr.dtype)
        out[self._valid] = arr[self._perm[self._valid]]
        return out

    def _pull(self, arr) -> np.ndarray:
        return self.pool.spans.d2h(arr)[self._pos]

    def _row(self, u: int) -> int:
        return int(self._pos[u])

    # ------------------------------------------------------------ setup

    def _rebuild_static(self, view):
        from repro.distributed.sharding import (POOL_LOCAL_TASK_AXES,
                                                POOL_STATE_AXES,
                                                POOL_STATIC_AXES,
                                                pool_shardings)
        pool = self.pool
        st, tn, proc, slots, ulat, ulon, unet, ucode = \
            self._host_static_arrays(view)
        old = (self._perm, self._pos) if self._perm is not None else None
        lt = self._compute_placement()
        if self._static_sh is None:
            self._static_sh = pool_shardings(
                self.mesh, POOL_STATIC_AXES, self.rules)
            self._state_sh = pool_shardings(
                self.mesh, POOL_STATE_AXES, self.rules)
            self._lt_sh = pool_shardings(
                self.mesh, POOL_LOCAL_TASK_AXES,
                self.rules)["local_task"]
        self._count_static(view, st)
        d2h = pool.spans.d2h
        host = dict(
            user_lat=self._to_dev(ulat), user_lon=self._to_dev(ulon),
            user_net=self._to_dev(unet), user_code20=self._to_dev(ucode),
            task_lat=d2h(st.lat), task_lon=d2h(st.lon),
            task_aff=d2h(st.aff), task_code20=d2h(st.code20),
            task_cloud=d2h(st.cloud), task_node=tn,
            node_proc=proc, node_slots=slots)
        pool.spans.h2d(lt, *host.values())
        self.static = FusedTickStatic(
            shards=None,
            **{k: jax.device_put(v, self._static_sh[k])
               for k, v in host.items()})
        self._local_task = jax.device_put(lt, self._lt_sh)
        self._epoch = view.epoch
        self._owner_version = pool.am.engine.owner_version
        if self.state is not None and old is not None and \
                not (old[0].shape == self._perm.shape
                     and np.array_equal(old[0], self._perm)):
            self._repack_state(old[1])

    def _upload_state(self, host, *, failovers: int, overflow: bool):
        """Upload pool-order host state under the current placement."""
        dev = {f: self._to_dev(host[f], _STATE_PAD_FILL[f])
               for f in _STATE_PAD_FILL}
        fo = np.zeros(self.n_dev, np.int32)
        fo[0] = failovers               # (D,) — the host reads the sum
        ov = np.zeros(self.n_dev, bool)
        ov[0] = overflow
        dev["failovers"] = fo
        dev["ema_overflow"] = ov
        self.pool.spans.h2d(*dev.values())
        self.state = FusedTickState(
            **{k: jax.device_put(v, self._state_sh[k])
               for k, v in dev.items()})

    def _repack_state(self, old_pos):
        """Re-home protocol: a handoff re-routed users across device
        boundaries — pull the state to pool order under the old
        placement, re-upload under the new one."""
        s = self.state
        d2h = self.pool.spans.d2h
        host = {f: d2h(getattr(s, f))[old_pos] for f in _STATE_PAD_FILL}
        self._upload_state(
            host, failovers=int(d2h(s.failovers).sum()),
            overflow=bool(d2h(s.ema_overflow).any()))

    def init_state(self):
        pool = self.pool
        view = pool._view()
        self._rebuild_static(view)
        u, k = pool.cand_task.shape
        host = dict(
            ema_nodes=np.full((u, self.ema_slots), -1, np.int32),
            ema_vals=np.full((u, self.ema_slots), np.nan, np.float32),
            cand=np.asarray(pool.cand_task),
            active=np.asarray(pool.active),
            pending=np.asarray(pool.pending),
            running=np.asarray(pool.running),
            ticking=np.asarray(pool.ticking),
            reinit=np.zeros(u, bool),
            lat_probe=np.full((u, k), np.nan, np.float32),
            lat_frame=np.full((u, self.nf), np.nan, np.float32),
            cand_traffic=np.full((u, k), -1, np.int32),
            active_traffic=np.full(u, -1, np.int32),
            frame_count=np.zeros(u, np.int32),
            frame_sum=np.zeros(u, np.float32))
        self._upload_state(host, failovers=0, overflow=False)

    # ------------------------------------------------------------- tick

    def _programs_for(self) -> MeshPrograms:
        key = (self.p_min, self.border_cap, self._sharded,
               self.refresh_cap)
        prog = self._programs.get(key)
        if prog is None:
            prog = _make_mesh_programs(self.mesh, self.users_axis,
                                       self.p_min, self.border_cap,
                                       self._sharded, self.refresh_cap)
            self._programs[key] = prog
        return prog

    def _run_tick(self, free, sched, alive, need, deaths, n_deaths):
        pool = self.pool
        prog = self._programs_for()
        dirty = self._dirty_input()
        r_ok = self._refresh_mask()
        with pool.spans.span("fused_tick.dispatch"):
            r_dev = self._to_dev(r_ok, False)
            d_dev = self._to_dev(dirty, False)
            pool.spans.h2d(free, sched, alive, need, deaths, n_deaths,
                           r_dev, d_dev)
            self.state, outs = prog.tick(
                self.state, self.static, self._local_task, free, sched,
                alive, need, deaths, n_deaths, pool.alpha,
                pool.switch_margin, r_dev, d_dev)
        self._stash_dirty = False
        with pool.spans.span("fused_tick.wait"):
            border_overflow = pool.spans.d2h(outs.border_overflow)
        if bool(border_overflow.any()):
            raise RuntimeError(
                f"fused tick: a device's border band exceeded "
                f"{self.border_cap} users — restart the pool with a "
                "larger shard_border_cap (or a coarser shard_precision)")
        self._note_refreshed(dirty, r_ok, outs)
        return outs

    def _push_traffic(self, work0, net_rate, probe_ok, frame_ok, data_f,
                      splits):
        pool = self.pool
        prog = self._programs_for()
        td = self._to_dev
        (e1p, e1f), (e2p, e2f), (e3p, e3f) = splits
        host = (td(probe_ok, False), td(frame_ok, False), td(e1p), td(e2p),
                td(e3p), td(e1f), td(e2f), td(e3f), td(data_f))
        pool.spans.h2d(work0, net_rate, *host)
        self.state = prog.traffic(
            self.state, self.static, work0, net_rate, *host,
            pool.workload_scale, pool.frame_interval)

    def _run_flush(self, deaths, n_deaths):
        prog = self._programs_for()
        self.state = prog.flush(self.state, self.static, deaths,
                                n_deaths, self.pool.alpha)

    # ------------------------------------------------------- maintenance

    def reset_aggregates(self):
        self.flush()
        up = self._perm.shape[0]
        self.state = self.state._replace(
            frame_count=jax.device_put(
                np.zeros(up, self.state.frame_count.dtype),
                self._state_sh["frame_count"]),
            frame_sum=jax.device_put(
                np.zeros(up, self.state.frame_sum.dtype),
                self._state_sh["frame_sum"]))

    def set_running(self, running: np.ndarray):
        self.state = self.state._replace(running=jax.device_put(
            self._to_dev(running, False), self._state_sh["running"]))
