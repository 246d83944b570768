"""A cell's traced run, split further than the benchmark's readers go:
the tick program's device milliseconds by named stage and the device's
idle milliseconds by host span (``scopes.py``), and the pool's counters
(``ClientPool.counts``: bytes each way, static rebuilds, breaks), all
per probe period of the traced window.

    python3 chipbench/trace_split.py --workload <cell> --seed <n> \
        --seconds <s> [--out <file.json>]

The run is ``run.py --trace 1`` itself, with its output; this script
wraps three of its calls (``deploy.build``, the profiler's start and
stop, ``xplane.find``) to read the pool and the trace before ``run.py``
deletes it, then prints one ``[split]`` JSON line and writes it to
``--out``.  Exits with ``run.py``'s code.
"""
import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import deploy, run, scopes, xplane  # noqa: E402


def split(sc: scopes.Scopes, counts0: dict, counts1: dict) -> dict:
    """Per-period figures of one traced window."""
    n = sc.steps
    counts = {k: (v - counts0.get(k, 0)) / n for k, v in counts1.items()}
    idle = sc.per_step(sc.idle_ms)
    idle_total = sum(idle.values())
    stages = sc.per_step(sc.stage_ms)
    breaks = counts.get("breaks")
    deaths = stages.get("deaths")
    return {
        "periods": n,
        "program_ms": sc.program_ms / n,
        "stage_ms": stages,
        "stage_share": sum(stages.values()) * n / sc.program_ms
        if sc.program_ms else None,
        "idle_ms": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        "idle_unnamed_share": idle.get(scopes.UNNAMED, 0.0) / idle_total
        if idle_total else None,
        "counts": counts,
        "h2d_mb": counts["h2d_bytes"] / 1e6 if "h2d_bytes" in counts
        else None,
        "d2h_mb": counts["d2h_bytes"] / 1e6 if "d2h_bytes" in counts
        else None,
        "replay_ms_per_break": deaths / breaks if deaths and breaks
        else None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax
    seen = {}
    build, find = deploy.build, xplane.find
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace

    def counts():
        return dict(getattr(seen["pool"], "counts", {}))

    def build_(*a, **k):
        dep = build(*a, **k)
        seen["pool"] = dep.pool
        return dep

    def start_(*a, **k):
        seen["counts0"] = counts()
        return start(*a, **k)

    def stop_(*a, **k):
        out = stop(*a, **k)
        seen["counts1"] = counts()
        return out

    def find_(trace_dir):
        path = find(trace_dir)
        seen["scopes"] = scopes.read(path)
        return path

    deploy.build, xplane.find = build_, find_
    jax.profiler.start_trace, jax.profiler.stop_trace = start_, stop_
    try:
        rc = run.main(["--workload", args.workload, "--seed",
                       str(args.seed), "--seconds", str(args.seconds),
                       "--trace", "1"])
    finally:
        deploy.build, xplane.find = build, find
        jax.profiler.start_trace, jax.profiler.stop_trace = start, stop
    if "scopes" not in seen:
        print("trace_split: the run made no trace", file=sys.stderr)
        return rc or 1
    out = split(seen["scopes"], seen["counts0"], seen["counts1"])
    line = json.dumps(out)
    print("[split] " + line, flush=True)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(line + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
