"""Readings that the limits of ``cells/<cell>.json`` are set from, on the
chip at the cell's own size, several seeds in one process:

    python chipbench/control.py --workload <cell> --seconds 4 \
        --seeds 11 12 13

For each seed it builds the deployment and runs a short window with the
harness's own probe ticks and audit ticks (``run.run_cell``), then
prints one JSON line with the program's readings and the control's: the
float64 reference with its score, latency and EMA-fold arithmetic in
bfloat16, put in the program's place at the same ticks.  The control
has to fail ``cand_gap`` or ``fold_gap``.
"""
import argparse
import gc
import json
import sys

import run

from chipbench import deploy


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"control: no TPU: JAX finds {device.platform!r} devices",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    cfg = deploy.load_json("configs", cell["config"])
    traffic = deploy.load_json("traffic", cell["traffic"])
    spec = deploy.load_json("cells", args.workload)
    for seed in args.seeds:
        res = run.run_cell(args.workload, cfg, traffic, spec, seed,
                           args.seconds, False, device)
        row = {"seed": seed, "error": repr(res["error"]),
               "program": res["readings"]}
        if res["error"] is None:
            row["control_bf16"] = res["probe"].readings(
                lowp_dtype=jnp.bfloat16)
        print("[control] " + json.dumps(row), flush=True)
        del res
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
