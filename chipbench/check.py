"""The comparison that decides ``correct``.

Probe ticks are ticks whose inputs and outputs are read at the system's
boundary while the simulation runs: node liveness, backlogs and the
simulator's random generator just before the tick, the pool's decision
mirrors before and after it, and each node's count of admitted requests.
Some probe ticks fall inside the measured window (drawn from the seed).
Two more follow it: the pre-audit tick, before which the sampled users'
latency EMAs are read through ``ClientPool.ema_of`` (which folds the
open window first), and the audit tick, after which they are read again.
Between the two reads the audit tick's own program replays the pre-audit
window's breaks, folds its latencies and decides the switch; the read
after it folds the audit window's latencies too.  After the window the
float64 reference (``reference.py``) recomputes what the ticks had to
decide:

* ``cand_gap``: widest score gap by which a sampled user's candidates
  fall below the reference's top-k (selection: scoring, filter, top-k,
  the per-shard and border passes where the fleet is sharded);
* ``fold_gap``: widest relative gap between a sampled user's EMAs after
  the audit tick and the reference's: the pre-audit EMAs with the
  breaks replayed and both windows' latencies recomputed from the
  traffic model and folded (EMA fold, fluid queueing delays);
* ``failover_err``: sampled users whose active node failed in the
  pre-audit window and whose active or pending node after the audit
  tick differs from the reference's failover pick followed by the
  switch;
* ``switch_err``: the other sampled users with such a difference
  (two-round switch), leaving out decisions that lie within the
  ``fold_gap`` limit of a tie;
* ``switch_rule``: users whose active node moved to anything but their
  pending nomination at a window tick, while that node neither failed
  nor failed and came back since the tick before;
* ``stale_active``: users left on a node whose failure was delivered to
  the pool before the tick, or with candidates and no active node;
* ``admit_err``: nodes whose admitted request count differs from the
  probes and frames the tick sent them, plus one if the pool's request
  counter differs from their sum (fluid admission).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from chipbench import reference

EARLY_MS = 1.0          # a failure this long before a tick is delivered
JUST_BEFORE_MS = 1e-6
AFTER_MS = 0.5
CHUNK = 1024


@dataclasses.dataclass
class ProbeTick:
    t: float
    kind: str               # "window", "pre" (pre-audit) or "audit"
    alive_early: np.ndarray = None
    alive: np.ndarray = None
    free: np.ndarray = None
    work: np.ndarray = None
    updated: np.ndarray = None
    rng_state: dict = None
    processed_pre: np.ndarray = None
    processed_post: np.ndarray = None
    requests_pre: int = 0
    requests_post: int = 0
    active_pre: np.ndarray = None
    pending_pre: np.ndarray = None
    cand_post: np.ndarray = None
    active_post: np.ndarray = None
    pending_post: np.ndarray = None
    ema: list = None        # per sampled user, node index -> EMA: before
    #                         a pre-audit tick, after an audit tick


class Probe:
    """Schedules the probe ticks' reads on the deployment's simulator."""

    def __init__(self, dep, cell: dict, seed: int):
        self.dep = dep
        self.cell = cell
        rng = np.random.default_rng([seed, 7])
        u = dep.pool.n_users
        self.users = np.sort(rng.choice(u, min(cell["sample_users"], u),
                                        replace=False))
        self.window_ticks = np.sort(rng.choice(
            cell["probe_span"], cell["probe_ticks"], replace=False))
        names = {n: i for i, n in enumerate(dep.fleet.names)}
        service = dep.cfg["service"]
        self.task_node = np.asarray(
            [names[t.captain.node_id]
             for t in dep.system.am.tasks[service]], np.int64)
        self.ticks: list = []

    def schedule(self, t: float, kind: str = "window") -> None:
        pt = ProbeTick(t, kind)
        self.ticks.append(pt)
        sim = self.dep.sim
        sim.at(t - EARLY_MS, self._early, pt)
        sim.at(t - JUST_BEFORE_MS, self._before, pt)
        sim.at(t + AFTER_MS, self._after, pt)

    def _nodes(self, attr):
        return np.asarray([getattr(c, attr) for c in self.dep.captains])

    def _node_of(self, task):
        task = np.asarray(task)
        return np.where(task >= 0, self.task_node[np.clip(task, 0, None)],
                        -1)

    def _emas(self) -> list:
        pool = self.dep.pool
        index = {n: i for i, n in enumerate(self.dep.fleet.names)}
        return [{index[n]: v for n, v in pool.ema_of(int(u)).items()}
                for u in self.users]

    def _early(self, pt):
        pt.alive_early = self._nodes("alive").astype(bool)

    def _before(self, pt):
        pool, fleet = self.dep.pool, self.dep.fleet
        caps = self.dep.captains
        if pt.kind == "pre":
            pt.ema = self._emas()
        pt.alive = self._nodes("alive").astype(bool)
        pt.work = self._nodes("fluid_work")
        pt.updated = self._nodes("fluid_updated")
        pt.free = reference.free_fraction(
            pt.work, pt.updated, self._nodes("busy"),
            np.asarray([len(c.queue) for c in caps]),
            fleet.slots, fleet.proc_ms, pt.t)
        pt.rng_state = self.dep.sim.rng.bit_generator.state
        pt.processed_pre = self._nodes("processed")
        pt.requests_pre = pool.requests_sent
        pt.active_pre = self._node_of(pool.active)
        pt.pending_pre = self._node_of(pool.pending)

    def _after(self, pt):
        pool = self.dep.pool
        pt.processed_post = self._nodes("processed")
        pt.requests_post = pool.requests_sent
        pt.cand_post = self._node_of(pool.cand_task)
        pt.active_post = self._node_of(pool.active)
        pt.pending_post = self._node_of(pool.pending)
        if pt.kind == "audit":
            pt.ema = self._emas()

    # ------------------------------------------------------- comparison

    def readings(self, lowp_dtype=None) -> dict:
        """Each compared number over every probe tick that completed.
        With ``lowp_dtype`` the reference in that precision takes the
        program's place for ``cand_gap`` and ``fold_gap`` (the
        controls)."""
        out = dict(cand_gap=0.0, fold_gap=0.0, switch_err=0,
                   failover_err=0, switch_rule=0, stale_active=0,
                   admit_err=0)
        done = [pt for pt in self.ticks if pt.processed_post is not None]
        for pt in done:
            out["cand_gap"] = max(out["cand_gap"],
                                  self._cand_gap(pt, lowp_dtype))
            out["stale_active"] += self._stale_active(pt)
            out["admit_err"] += self._admit_err(pt)
            if pt.kind != "audit":
                out["switch_rule"] += self._switch_rule(pt)
        pre = [pt for pt in done if pt.kind == "pre"]
        audit = [pt for pt in done if pt.kind == "audit"]
        if pre and audit:
            out.update(self._audit(pre[0], audit[0], lowp_dtype))
        out["probe_ticks"] = len(done)
        out["audit_ticks"] = len(audit)
        return out
    def _cand_gap(self, pt, lowp_dtype) -> float:
        dep = self.dep
        k = dep.cfg["top_n"]
        worst = 0.0
        for lo in range(0, len(self.users), CHUNK):
            rows = self.users[lo:lo + CHUNK]
            locs = dep.user_locs[rows]
            s = reference.scores(locs, dep.user_net, dep.fleet, pt.free,
                                 pt.alive)
            if lowp_dtype is None:
                chosen = pt.cand_post[rows]
            else:
                chosen = reference.lowp_candidates(
                    locs, dep.user_net, dep.fleet, pt.free, pt.alive, k,
                    lowp_dtype)
            worst = max(worst, float(
                reference.candidate_gap(s, chosen, k).max()))
        return worst

    def _stale_active(self, pt) -> int:
        gone = ~pt.alive_early & ~pt.alive
        act = pt.active_post
        on_dead = (act >= 0) & gone[np.clip(act, 0, None)]
        orphan = (act < 0) & (pt.cand_post >= 0).any(axis=1)
        return int((on_dead | orphan).sum())

    def _switch_rule(self, pt) -> int:
        # a node that failed since the tick before and came back still
        # sent its users to failover at this tick
        hit = np.zeros(len(pt.alive), bool)
        for e in self.dep.churn.events:
            if e["kind"] == "leave" and pt.t - self.dep.period_ms < e["t"] \
                    < pt.t:
                hit[e["node"]] = True
        pre = pt.active_pre
        stayed_alive = (pre >= 0) & (pt.alive & ~hit)[np.clip(pre, 0, None)]
        moved = pt.active_post != pre
        return int((stayed_alive & moved
                    & (pt.active_post != pt.pending_pre)).sum())

    def _traffic(self, pt):
        """(probe_ok, frame_ok, per-node request counts) of the window a
        tick sent."""
        n = len(self.dep.fleet.names)
        nf = self._frames()
        cand, act = pt.cand_post, pt.active_post
        probe_ok = (cand >= 0) & pt.alive[np.clip(cand, 0, None)]
        frame_ok = (act >= 0) & pt.alive[np.clip(act, 0, None)]
        counts = np.bincount(cand[probe_ok], minlength=n) \
            + nf * np.bincount(act[frame_ok], minlength=n)
        return probe_ok, frame_ok, counts

    def _frames(self) -> int:
        traffic = self.dep.traffic
        return int(traffic["probe_period_ms"] // traffic["frame_interval_ms"])

    def _admit_err(self, pt) -> int:
        want = self._traffic(pt)[2]
        got = pt.processed_post - pt.processed_pre
        sent = pt.requests_post - pt.requests_pre
        return int((got != want).sum()) + int(sent != int(want.sum()))

    def _latencies(self, pt, r) -> list:
        """The reference's latencies of the sampled users' requests in
        the window that tick ``pt`` sent."""
        dep = self.dep
        cfg, fleet = dep.cfg, dep.fleet
        probe_ok, frame_ok, counts = self._traffic(pt)
        work0, net_rate = reference.fluid_rates(
            pt.work, pt.updated, pt.t, fleet.slots, fleet.proc_ms, counts,
            cfg["workload_scale"], dep.period_ms)
        return reference.window_latencies(
            pt.rng_state, probe_ok, frame_ok, self._frames(), self.users,
            dep.user_locs, pt.cand_post, pt.active_post, fleet, work0,
            net_rate, cfg["workload_scale"],
            float(dep.traffic["frame_interval_ms"]), cfg["latency_model"],
            r)

    def _audit(self, pre, aud, lowp_dtype) -> dict:
        """The audit tick against the reference: breaks of the pre-audit
        window replayed over its decisions and EMAs, its latencies
        folded, the switch over the audit tick's candidates, and the
        audit window's latencies folded."""
        dep = self.dep
        cfg, fleet = dep.cfg, dep.fleet
        alpha = cfg["ema_alpha"]
        band = self.cell["limits"]["fold_gap"]
        deaths = [e["node"] for e in dep.churn.events
                  if e["kind"] == "leave" and pre.t < e["t"] < aud.t]
        exact = reference.rounder()
        lat_pre = self._latencies(pre, exact)
        lat_aud = self._latencies(aud, exact)
        users = self.users
        k = aud.cand_post.shape[1]
        s = len(users)
        act = np.full(s, -1, np.int64)
        act_ema, pend_ema = np.full(s, np.nan), np.full(s, np.nan)
        cand_ema = np.full((s, k), np.nan)
        failed_over = np.zeros(s, bool)
        mids = []
        for i, u in enumerate(users):
            ema = dict(pre.ema[i])
            cand = [int(c) for c in pre.cand_post[u] if c >= 0]
            _, a, reinit, failed_over[i] = reference.replay_deaths(
                cand, int(pre.active_post[u]), ema, deaths)
            reference.fold(ema, lat_pre[i], alpha)
            new = aud.cand_post[u]
            if reinit and (new >= 0).any():
                live = new[new >= 0]
                km = reference.haversine_km(
                    dep.user_locs[u, 0], dep.user_locs[u, 1],
                    fleet.lat[live], fleet.lon[live])
                a = int(live[np.argmin(km)])
            act[i] = a
            act_ema[i] = ema.get(a, np.nan)
            pend_ema[i] = ema.get(int(pre.pending_post[u]), np.nan)
            cand_ema[i] = [ema.get(int(c), np.nan) if c >= 0 else np.nan
                           for c in new]
            mids.append(ema)
        pend = pre.pending_post[users]
        pend_alive = (pend >= 0) & aud.alive[np.clip(pend, 0, None)]
        want_a, want_p, ambiguous = reference.switch(
            aud.cand_post[users], cand_ema, act, act_ema, pend, pend_ema,
            pend_alive, cfg["switch_margin"], band)
        bad = ((want_a != aud.active_post[users])
               | (want_p != aud.pending_post[users])) & ~ambiguous

        if lowp_dtype is not None:
            # the control: the whole replay and fold in ``lowp_dtype``
            low = reference.rounder(lowp_dtype)
            low_pre = self._latencies(pre, low)
            low_aud = self._latencies(aud, low)
        fold_gap = 0.0
        for i, u in enumerate(users):
            want = reference.fold(dict(mids[i]), lat_aud[i], alpha)
            got = aud.ema[i]
            if lowp_dtype is not None:
                got = dict(pre.ema[i])
                reference.replay_deaths(
                    [int(c) for c in pre.cand_post[u] if c >= 0],
                    int(pre.active_post[u]), got, deaths)
                reference.fold(got, low_pre[i], alpha, low)
                reference.fold(got, low_aud[i], alpha, low)
            fold_gap = max(fold_gap, reference.ema_gap(got, want))
        return dict(fold_gap=fold_gap,
                    failover_err=int((bad & failed_over).sum()),
                    switch_err=int((bad & ~failed_over).sum()),
                    switch_ambiguous=int(ambiguous.sum()),
                    failed_over=int(failed_over.sum()))
