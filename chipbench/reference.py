"""Plain float64 reference of Armada's client control plane.

Written from the paper's description (arXiv 2111.12002, Algorithm 1 and
section 3.3) and the policy the configuration states; it imports nothing
of the program under test.

* Selection: ``score = 0.5 * free + 0.2 * net_affinity + 0.3 * proximity``
  with ``proximity = 1 / (1 + km / 10)``, over the replicas that pass the
  adaptive geohash filter: for ``p = 4 .. 1`` keep the schedulable
  replicas whose first ``p`` geohash characters match the user's; the
  first ``p`` with at least ``min(4, schedulable)`` hits wins, else every
  schedulable replica counts.  The top ``k`` by score are the candidates
  (ties to the lower replica index).
* Free fraction of a node at time ``now``: ``1 - load``, floored at 0,
  with ``load = (busy + queued + fluid backlog in requests) / slots`` and
  the fluid backlog drained at ``slots`` work-ms per ms since its update.
* Traffic of one probe window: a probe to every live candidate at the
  window's start and ``probe_period / frame_interval`` frames to the
  live active node, frame ``j`` at ``(j + 0.5) * frame_interval``.  Each
  node admits its requests as fluid work; a request waits ``max(0,
  backlog + (arrival rate - slots) * tau) / slots``.  Its latency is
  ``rtt / 2 + wait + max(proc, floor) + back`` with ``rtt = base * (1 +
  j_rtt * e1)``, ``proc = proc_ms * scale * (1 + j_proc * e2)``, ``back
  = rtt / 2 * (1 + j_back * e3)`` and ``base = last_mile + per_km * km``;
  ``e1, e2, e3`` are three runs of standard normals from the simulator's
  generator, probes row by row, then each user's frames.
* EMA fold: each latency, probes in candidate order and then frames in
  order, moves the user's EMA of that node to ``alpha * ms + (1 - alpha)
  * ema``, or sets it where there is none.
* Failover: the breaks of a window, in order, take the failed node out
  of each touched user's candidates and EMAs; a user whose active node
  failed (or who had none) moves to its candidate with the lowest EMA,
  else its first candidate, else re-enters initial selection (nearest
  candidate by base round-trip time) at the next tick.
* Two-round switch: a user moves to its pending nomination when that
  node is alive, has a latency EMA, and beats the active node's EMA by
  the margin; otherwise it nominates the candidate with the lowest known
  EMA when that beats the active one by the margin.
* The controls are the same computations in a lower precision
  (``lowp_candidates``, and ``rounder`` in the latencies and the fold):
  what the comparison must reject.
"""
from __future__ import annotations

import numpy as np

W_RESOURCE, W_AFFINITY, W_PROXIMITY = 0.5, 0.2, 0.3
MIN_HITS = 4
FILTER_CHARS = 4
EARTH_KM = 6371.0
NETS = ("ethernet", "wifi", "lte", "other")
_AFFINITY = {
    ("ethernet", "ethernet"): 1.0, ("ethernet", "wifi"): 0.7,
    ("wifi", "ethernet"): 0.7, ("wifi", "wifi"): 0.6,
    ("lte", "lte"): 0.5, ("lte", "wifi"): 0.4, ("wifi", "lte"): 0.4,
    ("lte", "ethernet"): 0.5, ("ethernet", "lte"): 0.5,
}
MISSING_GAP = 1.0       # a candidate missing or outside the filter


def affinity(node_net, user_net: str) -> np.ndarray:
    """(N,) affinity of each node's network type for ``user_net``."""
    return np.asarray([_AFFINITY.get((n, user_net), 0.5) for n in node_net])


def geohash_prefix(lat, lon, chars: int = FILTER_CHARS) -> np.ndarray:
    """Integer of the first ``chars`` geohash characters (5 bits each,
    longitude bit first, ``>=`` the midpoint sends a point up)."""
    lat = np.asarray(lat, np.float64)
    lon = np.asarray(lon, np.float64)
    code = np.zeros(lat.shape, np.int64)
    lo = [np.full(lat.shape, -180.0), np.full(lat.shape, -90.0)]
    hi = [np.full(lat.shape, 180.0), np.full(lat.shape, 90.0)]
    vals = (lon, lat)
    for b in range(5 * chars):
        axis = b % 2
        mid = (lo[axis] + hi[axis]) / 2
        up = vals[axis] >= mid
        code = (code << 1) | up
        lo[axis] = np.where(up, mid, lo[axis])
        hi[axis] = np.where(up, hi[axis], mid)
    return code


def haversine_km(lat1, lon1, lat2, lon2):
    p1, p2 = np.radians(lat1), np.radians(lat2)
    dp = p2 - p1
    dl = np.radians(lon2 - lon1)
    a = np.sin(dp / 2) ** 2 + np.cos(p1) * np.cos(p2) * np.sin(dl / 2) ** 2
    return 2 * EARTH_KM * np.arcsin(np.sqrt(np.clip(a, 0.0, 1.0)))


def filter_mask(user_code, node_code, sched) -> np.ndarray:
    """(S, N) bool: the adaptive geohash filter over schedulable nodes."""
    need = min(MIN_HITS, int(sched.sum()))
    out = np.broadcast_to(sched[None, :], (len(user_code), len(sched))).copy()
    done = np.zeros(len(user_code), bool)
    for p in range(FILTER_CHARS, 0, -1):
        shift = 5 * (FILTER_CHARS - p)
        eq = ((user_code[:, None] >> shift) == (node_code[None, :] >> shift)) \
            & sched[None, :]
        use = (eq.sum(axis=1) >= need) & ~done
        out[use] = eq[use]
        done |= use
    return out


def scores(users, user_net, fleet, free, sched) -> np.ndarray:
    """(S, N) float64 scores of users (S, 2) against every node; -inf
    outside the filter."""
    d = haversine_km(users[:, 0:1], users[:, 1:2],
                     fleet.lat[None, :], fleet.lon[None, :])
    s = (W_RESOURCE * free[None, :]
         + W_AFFINITY * affinity(fleet.net, user_net)[None, :]
         + W_PROXIMITY / (1.0 + d / 10.0))
    mask = filter_mask(geohash_prefix(users[:, 0], users[:, 1]),
                       geohash_prefix(fleet.lat, fleet.lon), sched)
    return np.where(mask, s, -np.inf)


def top_k(s: np.ndarray, k: int) -> np.ndarray:
    """(S, k) node indices by descending score, ties to the lower index;
    -1 where fewer than ``k`` nodes pass the filter."""
    order = np.argsort(-s, axis=1, kind="stable")[:, :k]
    return np.where(np.isfinite(np.take_along_axis(s, order, 1)), order, -1)


def candidate_gap(s: np.ndarray, chosen: np.ndarray, k: int) -> np.ndarray:
    """(S,) widest gap by which the j-th best of the chosen candidates
    scores below the reference's j-th best, over j < k.  A chosen node
    outside the filter, a repeat or a missing candidate where the
    reference has one reads ``MISSING_GAP``."""
    best = -np.sort(-s, axis=1)[:, :k]
    rows = np.arange(len(s))[:, None]
    got = np.where(chosen >= 0, s[rows, np.clip(chosen, 0, None)], -np.inf)
    srt = np.sort(chosen, axis=1)
    dup = np.zeros(chosen.shape, bool)
    dup[:, 1:] = (srt[:, 1:] == srt[:, :-1]) & (srt[:, 1:] >= 0)
    n_bad = dup.sum(axis=1) + ((chosen >= 0) & ~np.isfinite(got)).sum(1)
    got = -np.sort(-got, axis=1)
    both = np.isfinite(best) & np.isfinite(got)
    gap = np.where(both, best - got, 0.0)
    gap = np.where(np.isfinite(best) != np.isfinite(got), MISSING_GAP, gap)
    gap = np.maximum(gap, 0.0).max(axis=1)
    return np.where(n_bad > 0, MISSING_GAP, gap)


def free_fraction(work, updated, busy, queued, slots, proc_ms, now):
    """(N,) free fractions at ``now`` from each node's backlog."""
    slots = np.maximum(slots, 1).astype(np.float64)
    dt = now - updated
    left = np.where(dt > 0, work - slots * dt, work)
    fluid = np.where(work > 0, np.maximum(left, 0.0)
                     / np.maximum(proc_ms, 1e-9), 0.0)
    return np.maximum(0.0, 1.0 - (busy + queued + fluid) / slots)


def switch(cand, cand_ema, active, act_ema, pending, pend_ema, pend_alive,
           margin, band=0.0):
    """Two-round switch for rows of users: returns (active, pending,
    ambiguous).  Node indices, -1 for none; EMAs in float64, NaN when
    unknown.  ``ambiguous`` marks rows where two compared EMAs (the two
    best candidates, or one of them against ``margin`` times the active
    node's) lie within ``band`` of each other, relative: there a
    rounding of the EMAs may decide either way."""
    valid = cand >= 0
    known = valid & ~np.isnan(cand_ema)
    eligible = valid.any(1) & known.any(1) & (active >= 0)
    masked = np.where(known, cand_ema, np.inf)
    slot = np.argmin(masked, axis=1)
    rows = np.arange(len(cand))
    best_ema, best = masked[rows, slot], cand[rows, slot]
    act_ok = ~np.isnan(act_ema)
    bar = margin * act_ema
    better = eligible & (best != active) & act_ok & (best_ema < bar)
    confirm = ((pending >= 0) & pend_alive & ~np.isnan(pend_ema)
               & (pending != active) & (active >= 0) & act_ok
               & (pend_ema < bar))
    new_active = np.where(confirm, pending, active)
    new_pending = np.where(confirm, -1, np.where(
        better, best, np.where(eligible, -1, pending)))

    def near(a, b):
        with np.errstate(invalid="ignore"):
            return np.isfinite(a) & np.isfinite(b) \
                & (np.abs(a - b) <= band * np.abs(b))

    second = np.sort(masked, axis=1)[:, 1] if cand.shape[1] > 1 \
        else np.full(len(cand), np.inf)
    ambiguous = near(second, best_ema) | near(best_ema, bar) \
        | near(pend_ema, bar)
    return new_active, new_pending, ambiguous


def rounder(dtype=None):
    """x -> x rounded to ``dtype`` and back to float64 (identity for
    None): applied after every operation, it computes in ``dtype``."""
    if dtype is None:
        return lambda x: x
    return lambda x: np.asarray(x, np.float64).astype(dtype) \
        .astype(np.float64)


def fluid_rates(work, updated, now, slots, proc_ms, counts, scale,
                window):
    """(N,) backlog at the window's start (work-ms, drained at ``slots``
    work-ms per ms since ``updated``) and net work rate (arrivals less
    drain) of each node admitting ``counts`` requests over ``window``."""
    slots = np.maximum(slots, 1).astype(np.float64)
    dt = now - updated
    work0 = np.where(dt > 0, np.maximum(0.0, work - slots * dt), work)
    return work0, counts * proc_ms * scale / window - slots


def window_latencies(rng_state, probe_ok, frame_ok, nf, rows, locs, cand,
                     active, fleet, work0, net_rate, scale, frame_interval,
                     model, r=rounder()):
    """Latencies of the window's requests of users ``rows``, in the order
    the EMA folds them: a list per row of (node, ms).  ``probe_ok`` (U,
    k) and ``frame_ok`` (U,) are every user's requests; ``rng_state`` is
    the simulator generator's state when the window's traffic was
    drawn."""
    gen = np.random.default_rng()
    gen.bit_generator.state = rng_state
    p_cnt = int(probe_ok.sum())
    total = p_cnt + int(frame_ok.sum()) * nf
    e1, e2, e3 = (gen.standard_normal(total) for _ in range(3))
    p_pos = (np.cumsum(probe_ok.ravel()) - 1).reshape(probe_ok.shape)[rows]
    f_pos = p_cnt + (np.cumsum(frame_ok) - 1)[rows, None] * nf \
        + np.arange(nf)[None, :]
    p_node = np.clip(cand[rows], 0, None)
    f_node = np.repeat(np.clip(active[rows], 0, None)[:, None], nf, 1)
    tau = np.broadcast_to((np.arange(nf) + 0.5) * frame_interval,
                          f_node.shape)
    slots = np.maximum(fleet.slots, 1).astype(np.float64)

    def lat(node, pos, tau, user):
        pos = np.clip(pos, 0, max(total - 1, 0))
        km = haversine_km(locs[user, 0], locs[user, 1], fleet.lat[node],
                          fleet.lon[node])
        base = r(model["rtt_last_mile_ms"] + r(model["rtt_ms_per_km"] * km))
        rtt = r(base * r(1 + r(model["jitter_rtt"] * e1[pos])))
        half = r(rtt / 2)
        wait = r(r(np.maximum(0.0, r(work0[node] + r(net_rate[node] * tau))))
                 / slots[node])
        proc = r(r(fleet.proc_ms[node] * scale)
                 * r(1 + r(model["jitter_proc"] * e2[pos])))
        back = r(half * r(1 + r(model["jitter_back"] * e3[pos])))
        return r(r(r(half + wait) + np.maximum(proc, model["proc_floor_ms"]))
                 + back)

    users = np.asarray(rows)
    lp = lat(p_node, p_pos, 0.0, users[:, None])
    lf = lat(f_node, f_pos, tau, users[:, None])
    out = []
    for i, u in enumerate(users):
        pairs = [(int(cand[u, c]), float(lp[i, c]))
                 for c in range(cand.shape[1]) if probe_ok[u, c]]
        if frame_ok[u]:
            pairs += [(int(active[u]), float(lf[i, j])) for j in range(nf)]
        out.append(pairs)
    return out


def fold(ema: dict, pairs, alpha: float, r=rounder()) -> dict:
    """Folds (node, ms) pairs in order into ``ema`` (node -> EMA)."""
    for node, ms in pairs:
        prev = ema.get(node)
        ema[node] = ms if prev is None else \
            float(r(r(alpha * ms) + r(r(1 - alpha) * prev)))
    return ema


def replay_deaths(cand: list, active: int, ema: dict, deaths) -> tuple:
    """One user's breaks of a window in order: returns (candidates,
    active, reinit, failed over).  ``ema`` loses the failed nodes."""
    reinit, failed_over = active < 0, False
    for d in deaths:
        if d not in cand and active != d:
            continue
        ema.pop(d, None)
        cand = [c for c in cand if c != d]
        if active < 0 or active == d:
            known = [(ema[c], i) for i, c in enumerate(cand) if c in ema]
            if known:
                active = cand[min(known)[1]]
            elif cand:
                active = cand[0]
            else:
                active, reinit = -1, True
            failed_over |= active >= 0
    return cand, active, reinit, failed_over


def ema_gap(got: dict, want: dict) -> float:
    """Widest relative gap between two users' EMA maps; a node in one
    and not the other reads ``MISSING_GAP``."""
    if got.keys() != want.keys():
        return MISSING_GAP
    return max((abs(got[n] - w) / abs(w) for n, w in want.items()),
               default=0.0)


def lowp_candidates(users, user_net, fleet, free, sched, k, dtype):
    """The selection with distances in float32 and the score arithmetic
    (proximity transform, weighted terms, sum) in ``dtype``; runs on the
    default JAX device.  Returns (S, k) node indices."""
    import jax
    import jax.numpy as jnp

    mask = filter_mask(geohash_prefix(users[:, 0], users[:, 1]),
                       geohash_prefix(fleet.lat, fleet.lon), sched)

    @jax.jit
    def run(ulat, ulon, nlat, nlon, free, aff, mask):
        rad = jnp.float32(np.pi / 180)
        p1, p2 = ulat[:, None] * rad, nlat[None, :] * rad
        dp = p2 - p1
        dl = (nlon[None, :] - ulon[:, None]) * rad
        a = jnp.sin(dp / 2) ** 2 + jnp.cos(p1) * jnp.cos(p2) \
            * jnp.sin(dl / 2) ** 2
        d = (2 * EARTH_KM * jnp.arcsin(jnp.sqrt(jnp.clip(a, 0, 1)))) \
            .astype(dtype)
        one = jnp.asarray(1, dtype)
        prox = one / (one + d / jnp.asarray(10, dtype))
        s = (jnp.asarray(W_RESOURCE, dtype) * free.astype(dtype)[None, :]
             + jnp.asarray(W_AFFINITY, dtype) * aff.astype(dtype)[None, :]
             + jnp.asarray(W_PROXIMITY, dtype) * prox)
        s = jnp.where(mask, s.astype(jnp.float32), -jnp.inf)
        top_s, top_i = jax.lax.top_k(s, k)
        return jnp.where(jnp.isfinite(top_s), top_i, -1)

    f32 = np.float32
    return np.asarray(run(users[:, 0].astype(f32), users[:, 1].astype(f32),
                          fleet.lat.astype(f32), fleet.lon.astype(f32),
                          free.astype(f32),
                          affinity(fleet.net, user_net).astype(f32), mask))
