"""Fused geo-selection top-k as a Pallas TPU kernel.

One grid step scores a (BU,)-user tile against a node tile:

* haversine + 1/(1+d/10) proximity on the VPU (fp32 elementwise over the
  (BU, N) tile);
* net affinity as each user's row of the (M, N) affinity table, picked
  by M selects (``ref.affinity``: exact, where a one-hot matmul on the
  MXU would round the table through bf16);
* the paper's adaptive-precision geohash filter on 20-bit Morton codes —
  int32 compares + row reductions, no int64 on the TPU;
* iterative max-extract top-k (k is static and small, the loop unrolls);
  ties pick the lowest index, matching ``jax.lax.top_k``.

Two layouts share the scoring math:

* ``geo_topk_pallas`` — 1-D grid over user tiles, ALL nodes broadcast to
  each step.  The (BU, N) working set stays in VMEM (see
  ``vmem_bytes``), which caps it at N ≈ 10k for BU=128.
* ``geo_topk_tiled_pallas`` — 2-D grid (user tiles x node tiles): node
  blocks of ``node_tile`` stream HBM→VMEM while a running top-k carry
  (scores + global indices) lives in fp32/int32 scratch across the
  sequential node dimension, merged by the same min-index-tie extraction.
  The adaptive prefix filter needs *global* per-precision hit counts, so
  a first 2-D pass (``_prefix_count_kernel``) accumulates them and the
  per-user precision choice is made between the two ``pallas_call``s.
  VMEM is ``vmem_bytes_tiled(block_u, node_tile)`` — independent of N,
  which lifts the all-nodes-in-VMEM limit to 100k+ nodes.

``repro.kernels.geo_topk.tune`` sweeps (block_u, node_tile) per backend
and caches the winner; ``ops.geo_topk`` consults that cache.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.geo_topk.ref import (NEG, PREFIX_CHARS, W_AFFINITY,
                                        W_PROXIMITY, W_RESOURCE, affinity,
                                        haversine_km)


# scoped-VMEM limit each kernel is compiled with (above the compiler's
# default; a v5e core has 128 MiB).  ``tune`` admits only layouts whose
# ``vmem_bytes``/``vmem_bytes_tiled`` stay inside it
VMEM_LIMIT_BYTES = 32 * 2**20


def _compiler_params(dims, interpret: bool) -> dict:
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=dims, vmem_limit_bytes=VMEM_LIMIT_BYTES)}


def _geo_topk_kernel(ulat_ref, ulon_ref, unet_ref, ucode_ref,
                     nlat_ref, nlon_ref, nfree_ref, naff_ref, ncode_ref,
                     nvalid_ref, scores_ref, idx_ref, *, k, need, np_):
    ulat = ulat_ref[:, 0:1]                       # (BU, 1)
    ulon = ulon_ref[:, 0:1]
    unet = unet_ref[:, 0:1]                       # (BU, 1) int32
    ucode = ucode_ref[:, 0:1]                     # (BU, 1) int32
    nlat = nlat_ref[0:1, :]                       # (1, N)
    nlon = nlon_ref[0:1, :]
    nfree = nfree_ref[0:1, :]
    ncode = ncode_ref[0:1, :]                     # (1, N) int32
    valid = (nvalid_ref[0:1, :] > 0).astype(jnp.int32)   # (1, N) 0/1

    bu = ulat.shape[0]

    # ---- proximity term (VPU, fp32): shares the oracle's exact formula
    d = haversine_km(ulat, ulon, nlat, nlon)      # (BU,1) x (1,N)
    prox = 1.0 / (1.0 + d / 10.0)                 # (BU, N)

    # ---- affinity term: each user's row of the per-node affinity table
    aff = affinity(unet, naff_ref[...])

    scores = W_RESOURCE * nfree + W_AFFINITY * aff + W_PROXIMITY * prox

    # ---- adaptive-precision geohash filter (int32 prefix compares).  The
    #      masks are int32 0/1: Mosaic cannot select or broadcast i1 values
    local = jnp.broadcast_to(valid, (bu, valid.shape[1]))
    done = jnp.zeros((bu, 1), jnp.int32)
    for p in range(PREFIX_CHARS, 0, -1):
        shift = 5 * (PREFIX_CHARS - p)
        eq = jnp.where((ucode >> shift) == (ncode >> shift), valid, 0)
        use = jnp.where(jnp.sum(eq, axis=1, keepdims=True) >= need,
                        1 - done, 0)
        local = jnp.where(use > 0, eq, local)
        done = done | use
    scores = jnp.where(local > 0, scores, jnp.float32(NEG))

    # ---- top-k by repeated max extraction (ties -> lowest index)
    iota = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    top_s, top_i = [], []
    for _ in range(k):
        best = jnp.max(scores, axis=1, keepdims=True)           # (BU, 1)
        at = jnp.where(scores >= best, iota, np_)
        ix = jnp.min(at, axis=1, keepdims=True)                 # (BU, 1)
        top_s.append(best)
        top_i.append(ix)
        scores = jnp.where(iota == ix, jnp.float32(NEG * 2), scores)
    scores_ref[...] = jnp.concatenate(top_s, axis=1)
    idx_ref[...] = jnp.concatenate(top_i, axis=1)


def _pad_query(user_lat, user_lon, user_net, user_code20,
               node_lat, node_lon, node_free, node_aff, node_code20,
               node_valid, pu: int, pn: int):
    """Shared pad/reshape prologue: users -> (U+pu, 1) columns, nodes ->
    (1, N+pn) rows, affinity rows padded to a whole sublane tile (8)."""
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    ul = jnp.pad(f32(user_lat), (0, pu)).reshape(-1, 1)
    uo = jnp.pad(f32(user_lon), (0, pu)).reshape(-1, 1)
    un = jnp.pad(i32(user_net), (0, pu)).reshape(-1, 1)
    uc = jnp.pad(i32(user_code20), (0, pu)).reshape(-1, 1)
    nl = jnp.pad(f32(node_lat), (0, pn)).reshape(1, -1)
    no = jnp.pad(f32(node_lon), (0, pn)).reshape(1, -1)
    nf = jnp.pad(f32(node_free), (0, pn)).reshape(1, -1)
    nc = jnp.pad(i32(node_code20), (0, pn)).reshape(1, -1)
    nv = jnp.pad(f32(node_valid), (0, pn)).reshape(1, -1)
    m = node_aff.shape[0]
    pm = -m % 8
    na = jnp.pad(f32(node_aff), ((0, pm), (0, pn)))
    return (ul, uo, un, uc), (nl, no, nf, na, nc, nv), m + pm


def geo_topk_pallas(user_lat, user_lon, user_net, user_code20,
                    node_lat, node_lon, node_free, node_aff, node_code20,
                    node_valid, *, k: int, need: int, block_u: int = 128,
                    interpret: bool = False):
    """-> (scores (U, k) fp32, indices (U, k) int32).

    Users: (U,) fp32 lat/lon, int32 net index + 20-bit Morton code.
    Nodes: (N,) fp32 lat/lon/free/valid, int32 codes, (M, N) affinity
    columns.  Pads U to ``block_u`` and N to a lane multiple internally.
    """
    u = user_lat.shape[0]
    n = node_lat.shape[0]
    bu = min(block_u, max(8, u))
    pu = -u % bu
    pn = -n % 128
    (ul, uo, un, uc), (nl, no, nf, na, nc, nv), mp = _pad_query(
        user_lat, user_lon, user_net, user_code20, node_lat, node_lon,
        node_free, node_aff, node_code20, node_valid, pu, pn)

    up, np_ = u + pu, n + pn
    grid = (up // bu,)
    user_spec = pl.BlockSpec((bu, 1), lambda i: (i, 0))
    node_spec = pl.BlockSpec((1, np_), lambda i: (0, 0))

    scores, idx = pl.pallas_call(
        functools.partial(_geo_topk_kernel, k=k, need=need, np_=np_),
        grid=grid,
        in_specs=[user_spec, user_spec, user_spec, user_spec,
                  node_spec, node_spec, node_spec,
                  pl.BlockSpec((mp, np_), lambda i: (0, 0)),
                  node_spec, node_spec],
        out_specs=[pl.BlockSpec((bu, k), lambda i: (i, 0)),
                   pl.BlockSpec((bu, k), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((up, k), jnp.float32),
                   jax.ShapeDtypeStruct((up, k), jnp.int32)],
        interpret=interpret,
        **_compiler_params(("parallel",), interpret),
    )(ul, uo, un, uc, nl, no, nf, na, nc, nv)
    return scores[:u], idx[:u]


def _work_bytes(block_u: int, width: int) -> int:
    """Scoped VMEM the TPU compiler allocates for one grid step's
    (block_u, width) temporaries, bounded from above.  Bisecting
    ``vmem_limit_bytes`` on v5e (block_u 64..256, widths 512..10240)
    found about 0.5 MiB fixed plus ~22 fp32 (block_u, width) matrices
    live at widths up to 1024 lanes, and at most 6 at wider rows."""
    live = 24 if width <= 1024 else 6
    return 2**20 + live * block_u * width * 4


def vmem_bytes(block_u: int, n: int, k: int = 8, m: int = 8) -> int:
    """Scoped VMEM one untiled grid step needs (fp32 everywhere)."""
    user_tiles = 4 * block_u * 4
    node_tiles = (5 + m) * n * 4
    out = 2 * block_u * k * 4
    return 2 * (user_tiles + node_tiles + out) + _work_bytes(block_u, n)


# ---------------------------------------------------------------------------
# node-tiled variant: streams node blocks with a running top-k merge
# ---------------------------------------------------------------------------

# shift amounts of the adaptive filter, finest precision first (p = 4..1)
_SHIFTS = tuple(5 * (PREFIX_CHARS - p) for p in range(PREFIX_CHARS, 0, -1))
_NO_FILTER_SHIFT = 5 * PREFIX_CHARS      # 20-bit codes >> 20 == 0: all pass
_COUNT_LANES = 128                       # count columns padded to one lane
_IDX_SENTINEL = 2**31 - 1


def _prefix_count_kernel(ucode_ref, ncode_ref, nvalid_ref, counts_ref):
    """Accumulate per-user hit counts for every filter precision across
    node tiles: counts[:, i] = #valid nodes matching the user's first
    ``PREFIX_CHARS - i`` geohash chars (columns beyond len(_SHIFTS) stay
    zero — lane padding)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    ucode = ucode_ref[:, 0:1]                     # (BU, 1)
    ncode = ncode_ref[0:1, :]                     # (1, BN)
    valid = nvalid_ref[0:1, :] > 0
    cols = [jnp.sum((((ucode >> s) == (ncode >> s)) & valid)
                    .astype(jnp.int32), axis=1, keepdims=True)
            for s in _SHIFTS]
    bu = ucode.shape[0]
    pad = jnp.zeros((bu, _COUNT_LANES - len(cols)), jnp.int32)
    counts_ref[...] += jnp.concatenate(cols + [pad], axis=1)


def _geo_topk_tiled_kernel(ulat_ref, ulon_ref, unet_ref, ucode_ref,
                           ushift_ref, nlat_ref, nlon_ref, nfree_ref,
                           naff_ref, ncode_ref, nvalid_ref,
                           scores_ref, idx_ref, s_scr, i_scr, *, k, bn, nj):
    """One (user tile, node tile) step: score the tile, merge into the
    running top-k carry held in scratch across the node grid dimension."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        s_scr[...] = jnp.full_like(s_scr, jnp.float32(NEG))
        i_scr[...] = jnp.full_like(i_scr, _IDX_SENTINEL)

    ulat = ulat_ref[:, 0:1]
    ulon = ulon_ref[:, 0:1]
    unet = unet_ref[:, 0:1]
    ucode = ucode_ref[:, 0:1]
    ushift = ushift_ref[:, 0:1]                   # (BU, 1) int32
    nlat = nlat_ref[0:1, :]
    nlon = nlon_ref[0:1, :]
    nfree = nfree_ref[0:1, :]
    ncode = ncode_ref[0:1, :]
    valid = nvalid_ref[0:1, :] > 0
    bu = ulat.shape[0]

    d = haversine_km(ulat, ulon, nlat, nlon)
    prox = 1.0 / (1.0 + d / 10.0)
    aff = affinity(unet, naff_ref[...])
    scores = W_RESOURCE * nfree + W_AFFINITY * aff + W_PROXIMITY * prox

    # per-user precision chosen from the global count pass; shift == 20
    # (no filter) degenerates to 0 == 0, keeping every valid node
    local = ((ucode >> ushift) == (ncode >> ushift)) & valid
    scores = jnp.where(local, scores, jnp.float32(NEG))

    # running top-k merge: carry columns keep their global indices, tile
    # columns get theirs from the node-grid position; min-index tie rule
    # matches jax.lax.top_k across tile boundaries because earlier tiles
    # always carry smaller global indices
    gidx = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bu, bn), 1)
    full_s = jnp.concatenate([s_scr[...], scores], axis=1)
    full_i = jnp.concatenate([i_scr[...], gidx], axis=1)
    top_s, top_i = [], []
    for _ in range(k):
        best = jnp.max(full_s, axis=1, keepdims=True)
        at = jnp.where(full_s >= best, full_i, _IDX_SENTINEL)
        ix = jnp.min(at, axis=1, keepdims=True)
        top_s.append(best)
        top_i.append(ix)
        full_s = jnp.where(full_i == ix, jnp.float32(NEG * 2), full_s)
    s_scr[...] = jnp.concatenate(top_s, axis=1)
    i_scr[...] = jnp.concatenate(top_i, axis=1)

    @pl.when(j == nj - 1)
    def _out():
        scores_ref[...] = s_scr[...]
        idx_ref[...] = i_scr[...]


def geo_topk_tiled_pallas(user_lat, user_lon, user_net, user_code20,
                          node_lat, node_lon, node_free, node_aff,
                          node_code20, node_valid, *, k: int, need: int,
                          block_u: int = 128, node_tile: int = 2048,
                          interpret: bool = False):
    """Node-streaming ``geo_topk_pallas``: same results, VMEM independent
    of N (see module docstring).  ``node_tile`` must hold at least ``k``
    entries so every merge sees enough real candidates."""
    from jax.experimental.pallas import tpu as pltpu

    u = user_lat.shape[0]
    n = node_lat.shape[0]
    bu = min(block_u, max(8, u))
    bn = max(128, -(-node_tile // 128) * 128)
    if bn < k:
        raise ValueError(f"node_tile {bn} < k {k}")
    pu = -u % bu
    pn = -n % bn
    (ul, uo, un, uc), (nl, no, nf, na, nc, nv), mp = _pad_query(
        user_lat, user_lon, user_net, user_code20, node_lat, node_lon,
        node_free, node_aff, node_code20, node_valid, pu, pn)

    up, np_ = u + pu, n + pn
    ui, nj = up // bu, np_ // bn
    grid = (ui, nj)
    user_spec = pl.BlockSpec((bu, 1), lambda i, j: (i, 0))
    node_spec = pl.BlockSpec((1, bn), lambda i, j: (0, j))
    out_spec = pl.BlockSpec((bu, k), lambda i, j: (i, 0))
    kwargs = _compiler_params(("parallel", "arbitrary"), interpret)

    # pass 1: global per-precision hit counts (the adaptive filter decides
    # on totals over ALL nodes, which no single tile can see)
    counts = pl.pallas_call(
        _prefix_count_kernel,
        grid=grid,
        in_specs=[user_spec, node_spec, node_spec],
        out_specs=pl.BlockSpec((bu, _COUNT_LANES), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((up, _COUNT_LANES), jnp.int32),
        interpret=interpret,
        **kwargs,
    )(uc, nc, nv)

    # choose the finest precision with enough hits (reference scan order:
    # p = 4..1, first match wins, else no filter)
    shift = jnp.full((up, 1), _NO_FILTER_SHIFT, jnp.int32)
    for i in range(len(_SHIFTS) - 1, -1, -1):
        shift = jnp.where(counts[:, i:i + 1] >= need, _SHIFTS[i], shift)

    # pass 2: scoring + running top-k over streamed node tiles
    scores, idx = pl.pallas_call(
        functools.partial(_geo_topk_tiled_kernel, k=k, bn=bn, nj=nj),
        grid=grid,
        in_specs=[user_spec, user_spec, user_spec, user_spec, user_spec,
                  node_spec, node_spec, node_spec,
                  pl.BlockSpec((mp, bn), lambda i, j: (0, j)),
                  node_spec, node_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((up, k), jnp.float32),
                   jax.ShapeDtypeStruct((up, k), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((bu, k), jnp.float32),
                        pltpu.VMEM((bu, k), jnp.int32)],
        interpret=interpret,
        **kwargs,
    )(ul, uo, un, uc, shift, nl, no, nf, na, nc, nv)
    return scores[:u], idx[:u]


def vmem_bytes_tiled(block_u: int, node_tile: int, k: int = 8,
                     m: int = 8) -> int:
    """Scoped VMEM one tiled grid step needs — independent of N."""
    user_tiles = 5 * block_u * 4
    node_tiles = (5 + m) * node_tile * 4
    carry = 2 * block_u * k * 4            # running top-k scratch
    out = 2 * block_u * k * 4
    return (2 * (user_tiles + node_tiles + out) + carry
            + _work_bytes(block_u, node_tile))
