"""(block_u, node_tile) autotuner for the geo_topk kernels.

The single-tile kernel shipped with a fixed ``block_u=128`` and an
all-nodes-in-VMEM layout; past the VMEM wall the node-tiled variant
opens a second axis.  This module sweeps both per backend — the same
scheme the attention kernels use for their block sizes — and caches the
winner so ``ops.geo_topk`` picks it up transparently:

* ``candidate_configs(u, n, k)`` enumerates ``(block_u, node_tile)``
  pairs whose VMEM need fits the kernels' scoped limit
  (``kernel.VMEM_LIMIT_BYTES``; ``node_tile=None`` means the untiled
  kernel, admissible only while ``vmem_bytes`` fits);
* ``autotune(u, n, k)`` times each config on synthetic inputs shaped
  like the query, stores the best per ``(backend, bucket(u), bucket(n),
  k)`` and returns the full timing table;
* ``get_config(u, n, k)`` serves the cached winner, falling back to a
  VMEM-safe heuristic when nothing was tuned;
* ``save_cache`` / ``load_cache`` persist winners as JSON (e.g. under
  ``artifacts/autotune/``) so a tuned deployment skips the sweep.

``benchmarks/bench_autotune.py`` drives the sweep; its ``--smoke``
profile (tiny shapes, ``interpret=True``) runs in tier-1 so the whole
path stays exercised without a TPU.
"""
from __future__ import annotations

import json
import pathlib
import time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.kernels.geo_topk.kernel import (VMEM_LIMIT_BYTES, geo_topk_pallas,
                                           geo_topk_tiled_pallas, vmem_bytes,
                                           vmem_bytes_tiled)

BLOCK_U_CANDIDATES = (64, 128, 256)
NODE_TILE_CANDIDATES = (512, 1024, 2048, 4096, 8192)
DEFAULT_NODE_TILE = 2048

Config = Tuple[int, Optional[int]]          # (block_u, node_tile|None)

_CACHE: Dict[Tuple, Config] = {}


def _bucket(x: int) -> int:
    """Next power of two — tuning transfers across nearby shapes."""
    b = 1
    while b < x:
        b *= 2
    return b


def _backend() -> str:
    return jax.default_backend()


def cache_key(u: int, n: int, k: int) -> Tuple:
    return (_backend(), _bucket(u), _bucket(n), k)


def _fits(cfg: Config, n: int, k: int) -> bool:
    bu, nt = cfg
    need = vmem_bytes(bu, n, k) if nt is None else vmem_bytes_tiled(bu, nt, k)
    return need <= VMEM_LIMIT_BYTES


def candidate_configs(u: int, n: int, k: int) -> List[Config]:
    """VMEM-admissible (block_u, node_tile) pairs for a (U, N, k) query."""
    out: List[Config] = []
    for bu in BLOCK_U_CANDIDATES:
        if bu > max(8, _bucket(u)):
            continue
        if _fits((bu, None), n, k):
            out.append((bu, None))
        for nt in NODE_TILE_CANDIDATES:
            if nt >= n or nt < k:
                continue                 # tiling only pays below N
            if _fits((bu, nt), n, k):
                out.append((bu, nt))
    if not out:                          # degenerate shapes: smallest tile
        out.append((min(BLOCK_U_CANDIDATES), min(NODE_TILE_CANDIDATES)))
    return out


def default_config(u: int, n: int, k: int) -> Config:
    """Heuristic used when nothing was tuned: untiled while it fits the
    scoped VMEM limit, else 2,048-node tiles.  Compile time grows with
    the tile (for v5e: 8 s at 128 x 2,048, 54 s at 128 x 8,192), and
    ranking the tiles by speed is ``autotune``'s job."""
    if _fits((128, None), n, k):
        return (128, None)
    return (128, DEFAULT_NODE_TILE)


def get_config(u: int, n: int, k: int) -> Config:
    """Cached winner for the shape bucket, re-checked against THIS
    query's VMEM need (a winner tuned at the small end of a bucket may
    not be admissible at the large end), else the heuristic default."""
    cfg = _CACHE.get(cache_key(u, n, k))
    if cfg is not None and _fits(cfg, n, k):
        return cfg
    return default_config(u, n, k)


def _synthetic_inputs(u: int, n: int, seed: int = 0):
    from repro.core import geohash
    from repro.kernels.geo_topk.ops import pack_inputs
    rng = np.random.default_rng(seed)
    base = (44.97, -93.22)
    ulat = base[0] + rng.uniform(-0.5, 0.5, u)
    ulon = base[1] + rng.uniform(-0.5, 0.5, u)
    nlat = base[0] + rng.uniform(-0.5, 0.5, n)
    nlon = base[1] + rng.uniform(-0.5, 0.5, n)
    return pack_inputs(
        ulat, ulon, rng.integers(0, 3, u),
        geohash.encode_batch(ulat, ulon, 9),
        nlat, nlon, rng.uniform(0, 1, n), rng.integers(0, 3, n),
        geohash.encode_batch(nlat, nlon, 9))


def _run_config(packed, cfg: Config, k: int, need: int, interpret: bool):
    bu, nt = cfg
    if nt is None:
        return geo_topk_pallas(*packed, k=k, need=need, block_u=bu,
                               interpret=interpret)
    return geo_topk_tiled_pallas(*packed, k=k, need=need, block_u=bu,
                                 node_tile=nt, interpret=interpret)


def autotune(u: int, n: int, k: int = 8, *, need: int = 4,
             configs: Optional[List[Config]] = None, repeats: int = 3,
             interpret: bool = False, seed: int = 0) -> Dict:
    """Time every admissible config on a synthetic (U, N, k) query and
    cache the winner for this backend.  Returns ``{"best": config,
    "timings_ms": {config: best-of-repeats}}``.

    ``interpret=True`` runs the kernels through the Pallas interpreter —
    functional end-to-end on CPU (the tier-1 smoke path), with timings
    that only rank Python-level work.  A config that fails to compile or
    run raises: every candidate is meant to fit the chip.
    """
    packed = _synthetic_inputs(u, n, seed=seed)
    configs = candidate_configs(u, n, k) if configs is None else configs
    timings: Dict[Config, float] = {}
    for cfg in configs:
        s, i = _run_config(packed, cfg, k, need, interpret)
        s.block_until_ready()                # compile outside the clock
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            s, i = _run_config(packed, cfg, k, need, interpret)
            s.block_until_ready()
            best = min(best, (time.perf_counter() - t0) * 1e3)
        timings[cfg] = best
    winner = min(timings, key=timings.get)
    _CACHE[cache_key(u, n, k)] = winner
    return {"best": winner, "timings_ms": timings}


# ----------------------------------------------------------- persistence

def save_cache(path) -> None:
    path = pathlib.Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    rows = [{"key": list(key), "block_u": cfg[0], "node_tile": cfg[1]}
            for key, cfg in _CACHE.items()]
    path.write_text(json.dumps(rows, indent=1))


def load_cache(path) -> int:
    """Merge winners from ``save_cache`` output; returns entries loaded."""
    rows = json.loads(pathlib.Path(path).read_text())
    for r in rows:
        _CACHE[tuple(r["key"])] = (r["block_u"], r["node_tile"])
    return len(rows)


def clear_cache() -> None:
    _CACHE.clear()
