"""Chip benchmark of Armada's client control plane: wall time per probe
tick of the fused device tick (``ClientPool(tick="device")``).

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``configs/``: fleet
and population), a traffic mix (``traffic/``: probe period, frames,
churn, refresh policy) and its own file under ``cells/`` (the sample the
comparison reads and the limit of each compared number).  The run builds
the deployment from the seed, warms up a few whole probe periods (every
program the window uses is compiled or loaded from the cache then), and
advances the simulator one probe period at a time until ``--seconds`` of
wall time have passed; each period is one sample.  Then two more ticks,
the pre-audit and the audit tick, run for the comparison (``check.py``).

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, each read by ``metrics/<name>.py`` from the run's
timings, the pool's phase counters and the profiler trace of the window.
The last line of standard output is one JSON object; every number the
comparison uses is also printed with its limit as the last lines of
standard error.  Exits non-zero, printing no result, where JAX finds no
TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse                 # noqa: E402
import gc                       # noqa: E402
import hashlib                  # noqa: E402
import importlib.util           # noqa: E402
import json                     # noqa: E402
import pathlib                  # noqa: E402
import shutil                   # noqa: E402
import sys                      # noqa: E402
import tempfile                 # noqa: E402
import traceback                # noqa: E402
import types                    # noqa: E402

import numpy as np              # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import check, deploy, xplane  # noqa: E402

WARM_PERIODS = 3
HERE = pathlib.Path(__file__).resolve().parent


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileCounter:
    """Counts programs lowered (compiled, or loaded from the persistent
    cache) through ``jax.monitoring``."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring
        self.counts = dict.fromkeys(self.EVENTS, 0)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event in self.counts:
            self.counts[event] += 1

    def close(self) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on)

    def snapshot(self) -> dict:
        return dict(self.counts)

    def since(self, snap: dict) -> dict:
        return {e.rsplit("/", 1)[-1]: self.counts[e] - snap[e]
                for e in self.EVENTS}


class GcClock:
    """Host time spent in the garbage collector, by generation."""

    def __init__(self):
        self.seconds = [0.0, 0.0, 0.0]
        self.count = [0, 0, 0]
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            g = info["generation"]
            self.seconds[g] += time.perf_counter() - self._t0
            self.count[g] += 1
            self._t0 = None

    def total(self) -> float:
        return sum(self.seconds)

    def close(self) -> None:
        gc.callbacks.remove(self._on)


def load_reader(name: str):
    """``metrics/<name>.py``'s ``read(ctx)``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str, kind: str) -> list:
    return [m for m in bench[kind]
            if cell in m.get("workloads", [cell])]


def sync() -> None:
    import jax
    jax.block_until_ready(jax.live_arrays())


def results_digest() -> str:
    """sha256 of the calibration file the program reads, if any: the
    benchmark must not change it."""
    path = ROOT / "artifacts" / "bench" / "results.json"
    if not path.is_file():
        return "absent"
    return hashlib.sha256(path.read_bytes()).hexdigest()


def ema_slots_used(pool) -> tuple:
    """(most EMA slots any user holds, slots per user) of the device
    tick's table: a slot is never freed, and a user past the last stops
    the tick."""
    nodes = pool._dev.state.ema_nodes
    return int((np.asarray(nodes) != -1).sum(axis=1).max()), nodes.shape[1]


def run_cell(name: str, cfg: dict, traffic: dict, spec: dict, seed: int,
             seconds: float, trace: bool, device) -> dict:
    """Builds, warms up, measures and checks one cell; returns the
    result's fields.  ``device`` is the JAX device the run reports."""
    import jax

    counter = CompileCounter()
    gc_clock = GcClock()
    setup = {}
    dep = deploy.build(cfg, traffic, seed, setup)
    pool, sim = dep.pool, dep.sim
    period = dep.period_ms
    probe = check.Probe(dep, spec, seed)
    log(f"[build] {name} seed={seed} users={pool.n_users} "
        f"nodes={len(dep.fleet.names)} fleet_s={setup['fleet_s']:.3f}")

    attempted = failed = 0
    samples = []
    err = None
    window = {}
    try:
        t0 = time.perf_counter()
        sim.run(until=1.0)
        sync()
        setup["start_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        for k in range(1, WARM_PERIODS + 1):
            sim.run(until=k * period + 1.0)
        sync()
        setup["warm_s"] = time.perf_counter() - t0
        first = WARM_PERIODS + 1
        for j in probe.window_ticks:
            probe.schedule((first + int(j)) * period)
        setup["setup_s"] = time.perf_counter() - T_START
        log(f"[setup] fleet_s={setup['fleet_s']:.3f} start_s="
            f"{setup['start_s']:.3f} warm_s={setup['warm_s']:.3f} "
            f"setup_s={setup['setup_s']:.3f} programs_built="
            f"{counter.snapshot()}")

        trace_dir = tempfile.mkdtemp(prefix="chipbench-") if trace else None
        snap = counter.snapshot()
        phase0 = dict(pool.phase_ms)
        ticks0, reqs0, fo0 = pool.ticks_run, pool.requests_sent, \
            pool.failovers
        gc_periods = []
        gc0 = (list(gc_clock.seconds), list(gc_clock.count))
        if trace_dir:
            # the Python tracer would slow the host glue it measures
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        k = first
        w0 = time.perf_counter()
        while True:
            with jax.profiler.StepTraceAnnotation("probe_period",
                                                  step_num=k):
                t0 = time.perf_counter()
                g0 = gc_clock.total()
                attempted += 1
                sim.run(until=k * period + 1.0)
                samples.append(time.perf_counter() - t0)
                gc_periods.append(gc_clock.total() - g0)
            k += 1
            if (time.perf_counter() - w0 >= seconds
                    and len(samples) >= spec["probe_span"]):
                break
        sync()
        wall = time.perf_counter() - w0
        if trace_dir:
            jax.profiler.stop_trace()
        built = counter.since(snap)
        window = dict(
            wall_s=wall, ticks=len(samples), samples=samples,
            phase_ms={p: v - phase0.get(p, 0.0)
                      for p, v in pool.phase_ms.items()},
            ticks_run=pool.ticks_run - ticks0,
            requests=pool.requests_sent - reqs0,
            failovers=pool.failovers - fo0, built=built,
            gc_s=[a - b for a, b in zip(gc_clock.seconds, gc0[0])],
            gc_n=[a - b for a, b in zip(gc_clock.count, gc0[1])])
        if sim.truncated:
            raise RuntimeError("the simulator run was truncated")
        if window["ticks_run"] != len(samples):
            raise RuntimeError(f"{window['ticks_run']} ticks ran in "
                               f"{len(samples)} probe periods")
        if any(built.values()):
            # a program built inside the window: warm-up missed a shape
            failed += 1
        stats = device.memory_stats() or {}
        window["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
        window["ema_slots"] = ema_slots_used(pool)
        log(f"[window] periods={len(samples)} wall_s={wall:.4f} "
            f"programs_built_in_window={built} requests="
            f"{window['requests']} failovers={window['failovers']} "
            f"node_failures={sum(e['kind'] == 'leave' for e in dep.churn.events)} "
            f"memory_peak_bytes={window['memory_peak_bytes']} "
            f"ema_slots_used={window['ema_slots'][0]}/"
            f"{window['ema_slots'][1]} phase_ms_per_tick=" + " ".join(
                f"{p}={v / len(samples):.3f}"
                for p, v in sorted(window["phase_ms"].items())))
        log("[window] period_ms=" + ",".join(
            f"{s * 1e3:.3f}" for s in samples))
        slow = np.argsort(samples)[::-1][:5]
        log(f"[window] gc_s_by_generation={window['gc_s']} "
            f"gc_collections={window['gc_n']} slowest_periods=" + ",".join(
                f"{int(i)}:{samples[i] * 1e3:.1f}ms(gc {gc_periods[i] * 1e3:.1f}ms)"
                for i in slow))
        if pool.dirty_counts is not None:
            tracker = getattr(pool, "_rt", None)
            log(f"[window] dirty_counts={pool.dirty_counts[-len(samples):]}"
                f" refresh_fallbacks={getattr(tracker, 'fallbacks', None)}")
        window["trace"] = None
        if trace_dir:
            try:
                window["trace"] = xplane.summarize(xplane.find(trace_dir))
            finally:
                shutil.rmtree(trace_dir, ignore_errors=True)

        probe.schedule(k * period, kind="pre")
        probe.schedule((k + 1) * period, kind="audit")
        sim.run(until=(k + 1) * period + 1.0)
    except Exception as e:       # a tick that raises fails the run
        failed += 1
        err = e
        traceback.print_exc()
    finally:
        counter.close()
        gc_clock.close()

    readings = probe.readings() if err is None else {}
    return dict(name=name, setup=setup, window=window, readings=readings,
                attempted=attempted, failed=failed, error=err, dep=dep,
                probe=probe)


def judge(readings: dict, limits: dict) -> tuple:
    """(correct, {name: {value, limit}}) for every limited number."""
    checks = {}
    ok = bool(readings)
    for key, limit in limits.items():
        value = readings.get(key)
        checks[key] = {"value": value, "limit": limit}
        ok = ok and value is not None and value <= limit
    ok = ok and readings.get("audit_ticks", 0) == 1 \
        and readings.get("probe_ticks", 0) >= 2
    return ok, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"chipbench: no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    cell = cells[args.workload]

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: no TPU: JAX finds {devices[0].platform!r} "
              "devices only", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"chipbench: {args.workload} needs {cell['chips']} TPU chips,"
              f" JAX finds {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"[device] {devices[0].device_kind} x{len(devices)} jax "
        f"{jax.__version__} compile_cache={cache} "
        f"calibration_sha256={results_digest()}")

    spec = deploy.load_json("cells", args.workload)
    res = run_cell(args.workload, deploy.load_json("configs", cell["config"]),
                   deploy.load_json("traffic", cell["traffic"]), spec,
                   args.seed, args.seconds, bool(args.trace), devices[0])
    correct, checks = judge(res["readings"], spec["limits"])
    log(f"[check] readings={json.dumps(res['readings'])}")

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": res["window"].get("memory_peak_bytes", 0)}
    metrics, breakdown = {}, None
    kind = "per_layer" if args.trace else "end_to_end"
    ctx = types.SimpleNamespace(setup=res["setup"], window=res["window"],
                                trace=res["window"].get("trace"))
    for m in cell_metrics(bench, args.workload, kind):
        value = end_to_end(m["name"], ctx) if kind == "end_to_end" \
            else load_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    tr = ctx.trace
    if tr is not None:
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        breakdown = {"device_ops": tr.top_ops, "idle_gaps": tr.gaps}

    for key, c in checks.items():
        print(f"check {key}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    out = {"correct": correct and res["error"] is None,
           "attempted": res["attempted"], "failed": res["failed"],
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    print(json.dumps(out), flush=True)
    return 0 if res["error"] is None else 1


def end_to_end(name: str, ctx) -> float:
    w = ctx.window
    if not w:
        return None
    if name == "setup_s":
        return ctx.setup["setup_s"]
    if name == "tick_ms":
        return w["wall_s"] * 1e3 / w["ticks"]
    if name == "tick_p90_ms":
        return float(np.percentile(np.asarray(w["samples"]) * 1e3, 90))
    raise KeyError(f"no end-to-end metric {name!r}")


if __name__ == "__main__":
    sys.exit(main())
