"""geo_topk kernel autotune sweep: (block_u, node_tile) per backend.

Times every VMEM-admissible layout of the fused selection kernel —
untiled (all nodes resident) vs node-tiled (streamed with a running
top-k merge) — on synthetic metro-area queries, and caches the winner in
``repro.kernels.geo_topk.tune`` so subsequent ``ops.geo_topk`` calls on
this backend pick it up.  Winners are also persisted to
``artifacts/autotune/geo_topk.json``.

The full profile runs on a TPU only, where the timings rank real kernel
layouts, and refuses any other backend.  The ``--smoke`` profile (tiny
shapes, two configs) runs the kernels through the Pallas interpreter
(``interpret=True``): tier-1 runs it to keep the autotuner exercised
without a TPU, and its timings rank nothing.
"""
from __future__ import annotations

import argparse
import pathlib

import jax

from repro.kernels.geo_topk import tune

CACHE_PATH = pathlib.Path(__file__).resolve().parents[1] \
    / "artifacts" / "autotune" / "geo_topk.json"

# (U, N, k) shape buckets of interest: the pool refresh (wide U, metro
# node counts) and the past-the-VMEM-wall regime the tiled kernel opens
FULL_SWEEP = [(8192, 4096, 8), (8192, 32768, 8), (4096, 131072, 8)]
SMOKE_SWEEP = [(32, 128, 4)]            # interpreter-priced: keep tiny
SMOKE_CONFIGS = [(32, None), (32, 64)]


def run(smoke: bool = False):
    if not smoke and jax.default_backend() != "tpu":
        raise RuntimeError(
            "bench_autotune: the full sweep times kernels on a TPU, and the "
            f"backend is {jax.default_backend()!r}; --smoke runs the "
            "interpreter profile")
    interpret = smoke
    sweep = SMOKE_SWEEP if smoke else FULL_SWEEP
    rows = []
    for u, n, k in sweep:
        res = tune.autotune(
            u, n, k, interpret=interpret,
            configs=SMOKE_CONFIGS if smoke else None,
            repeats=1 if smoke else 3)
        for (bu, nt), ms in sorted(res["timings_ms"].items(),
                                   key=lambda kv: kv[1]):
            tag = f"autotune/geo_topk/u{u}_n{n}_k{k}/bu{bu}_nt{nt}"
            rows.append((tag, ms,
                         f"backend={jax.default_backend()};"
                         f"interpret={interpret};"
                         f"winner={res['best'] == (bu, nt)}"))
    tune.save_cache(CACHE_PATH)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sweep through the interpreter (tier-1)")
    args = ap.parse_args()
    print("name,ms_per_call,derived")
    for name, ms, derived in run(smoke=args.smoke):
        print(f"{name},{ms:.2f},{derived}")
