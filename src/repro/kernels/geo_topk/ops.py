"""Public fused geo-selection op: Pallas on TPU, jnp oracle elsewhere.

The kernel runs through the Pallas interpreter only when a caller asks
for it (``interpret=True``); ``force_pallas`` off the TPU without it
raises rather than interpret in silence.

``pack_inputs`` flattens a (users, replicas) query into the dtype-correct
arrays both backends consume (``pack_user_inputs`` / ``pack_node_inputs``
split the two halves so callers with a static replica set can cache the
node half — see ``SelectionEngine``'s node-epoch cache); ``geo_topk``
dispatches and returns per-user ``(scores, indices)`` top-k.  On TPU the
kernel layout — untiled vs node-tiled — and its ``(block_u, node_tile)``
come from ``repro.kernels.geo_topk.tune``'s per-backend autotune cache.
``geo_topk_shard`` is the region-sharded entry point: one invocation per
shard over that shard's padded layout, filter restricted to the shard
prefix, with a per-user "satisfied" mask so border users can escalate to
a cross-shard pass.  ``SelectionEngine`` in ``repro.core.selection`` maps
indices back to Task objects.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import numpy as np

from repro.core.selection import CODE_PRECISION
from repro.kernels.geo_topk.kernel import (geo_topk_pallas,
                                           geo_topk_tiled_pallas)
from repro.kernels.geo_topk.ref import MIN_PROXIMITY_HITS, geo_topk_reference

PREFIX_SHIFT = 5 * CODE_PRECISION - 20   # keep the top 4 chars = 20 bits


class GeoTopKInputs(NamedTuple):
    user_lat: np.ndarray      # (U,) fp32
    user_lon: np.ndarray      # (U,) fp32
    user_net: np.ndarray      # (U,) int32 net-type index
    user_code20: np.ndarray   # (U,) int32, top-4-char Morton prefix
    node_lat: np.ndarray      # (N,) fp32
    node_lon: np.ndarray      # (N,) fp32
    node_free: np.ndarray     # (N,) fp32 free-slot fraction
    node_aff: np.ndarray      # (M, N) fp32 affinity columns per node
    node_code20: np.ndarray   # (N,) int32
    node_valid: np.ndarray    # (N,) fp32 1.0 = schedulable


def code20(code45) -> np.ndarray:
    """45-bit engine Morton codes -> kernel 20-bit prefixes (int32)."""
    return (np.asarray(code45, np.int64) >> PREFIX_SHIFT).astype(np.int32)


def pack_user_inputs(user_lat, user_lon, user_net, user_code45):
    """User half of a query as kernel-ready arrays."""
    return (np.asarray(user_lat, np.float32),
            np.asarray(user_lon, np.float32),
            np.asarray(user_net, np.int32),
            code20(user_code45))


def pack_node_inputs(node_lat, node_lon, node_free, node_net,
                     node_code45, node_valid=None):
    """Node half of a query.  ``node_valid`` marks schedulable rows
    (1.0); pass zeros for padding rows added to stabilize jit shapes —
    they score ``NEG`` and fall out of the top-k."""
    from repro.core.selection import AFFINITY_TABLE
    node_net = np.asarray(node_net, np.int64)
    if node_valid is None:
        node_valid = np.ones(len(node_lat), np.float32)
    return (np.asarray(node_lat, np.float32),
            np.asarray(node_lon, np.float32),
            np.asarray(node_free, np.float32),
            AFFINITY_TABLE[node_net, :].T.astype(np.float32),
            code20(node_code45),
            np.asarray(node_valid, np.float32))


def pack_inputs(user_lat, user_lon, user_net, user_code45,
                node_lat, node_lon, node_free, node_net,
                node_code45, node_valid=None) -> GeoTopKInputs:
    """45-bit engine codes + net indices -> kernel-ready arrays."""
    return GeoTopKInputs(
        *pack_user_inputs(user_lat, user_lon, user_net, user_code45),
        *pack_node_inputs(node_lat, node_lon, node_free, node_net,
                          node_code45, node_valid))


@functools.partial(jax.jit, static_argnames=("k", "need", "force_pallas",
                                             "interpret", "block_u",
                                             "node_tile"))
def _dispatch(packed: GeoTopKInputs, k: int, need: int, force_pallas: bool,
              interpret: bool, block_u: Optional[int],
              node_tile: Optional[int]):
    if force_pallas or jax.default_backend() == "tpu":
        kw = dict(k=k, need=need, interpret=interpret)
        if block_u is not None:
            kw["block_u"] = block_u
        if node_tile is not None:
            return geo_topk_tiled_pallas(*packed, node_tile=node_tile, **kw)
        return geo_topk_pallas(*packed, **kw)
    return geo_topk_reference(*packed, k=k, need=need)


@functools.partial(jax.jit, static_argnames=("k", "need", "p_min"))
def _dispatch_shard(packed: GeoTopKInputs, k: int, need: int, p_min: int):
    from repro.kernels.geo_topk.ref import score_matrix_restricted
    scores, sat = score_matrix_restricted(*packed, need=need, p_min=p_min)
    top_s, top_i = jax.lax.top_k(scores, k)
    return top_s, top_i, sat


def geo_topk_shard(packed: GeoTopKInputs, *, k: int, need: int,
                   p_min: int, interpret: bool = False):
    """Per-shard top-k over one region's padded node layout: the
    adaptive proximity filter runs restricted to precisions
    ``p >= p_min`` (the shard prefix length) with no global fallback.

    Returns ``(scores, indices, satisfied)`` — ``indices`` are positions
    into THIS shard's padded layout (callers map them to global task
    positions via the shard's ``task_ix_padded``), and rows with
    ``satisfied == False`` carry no result: the in-shard widening could
    not reach ``need`` hits, so the caller must escalate them to a
    cross-shard pass (``geo_topk`` over the adjacent shards' union).
    ``need`` is the caller's *global* hit target — per-shard counts at
    ``p >= p_min`` equal global counts because geohash cells nest.

    jnp oracle on every backend (the per-shard matrices are already a
    1/S slice of the work the Pallas kernels tile; ``interpret`` is
    accepted for call-site symmetry with ``geo_topk``).
    """
    del interpret
    return _dispatch_shard(packed, k, need, p_min)


def geo_topk(packed: GeoTopKInputs, *, k: int, need: int = None,
             force_pallas: bool = False, interpret: bool = False,
             block_u: Optional[int] = None, node_tile: Optional[int] = None):
    """Per-user top-k replica (scores, indices) over the packed query.

    When the Pallas path is taken and no explicit ``block_u``/``node_tile``
    is given, the layout comes from the autotune cache (heuristic default
    until ``tune.autotune`` has run for this shape bucket).
    """
    n = len(packed.node_lat)
    if need is None:
        need = min(MIN_PROXIMITY_HITS, n)
    on_tpu = jax.default_backend() == "tpu"
    if force_pallas and not (on_tpu or interpret):
        raise RuntimeError(
            "geo_topk: the Pallas kernel compiles for a TPU only, and the "
            f"backend is {jax.default_backend()!r}; pass interpret=True "
            "to run it through the Pallas interpreter")
    # consult the autotune cache only when the caller pinned NEITHER
    # knob — an explicit node_tile (or block_u) is a layout request
    if (force_pallas or on_tpu) and block_u is None and node_tile is None:
        from repro.kernels.geo_topk import tune
        block_u, node_tile = tune.get_config(len(packed.user_lat), n, k)
    return _dispatch(packed, k, need, force_pallas, interpret, block_u,
                     node_tile)
