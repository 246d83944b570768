"""Pure-jnp oracle for fused geo-selection top-k (paper Algorithm 1).

Scores every (user, replica) pair in one fused pass:

    score = W_RESOURCE * free + W_AFFINITY * aff + W_PROXIMITY * prox
    prox  = 1 / (1 + haversine_km / 10)

after the paper's adaptive-precision geohash proximity filter: for
p = 4..1, keep replicas whose first ``p`` geohash chars match the user's;
the first ``p`` with >= min(4, N) hits wins, else no filter.  Geohash
prefixes are compared on 20-bit Morton codes (the first 4 base32 chars of
``repro.core.geohash.encode_batch`` codes), which keeps every integer op
inside int32 — TPU-native.

Inputs are packed by ``repro.kernels.geo_topk.ops.pack_inputs``; scores
are fp32 (coordinates at city scale lose < 1 m to fp32, far below the
scoring resolution).  Masked-out pairs score ``NEG``.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

# single source of truth for the Algorithm-1 constants lives with the
# engine; the kernel must score exactly what the numpy path scores
from repro.core.selection import (MIN_PROXIMITY_HITS, W_AFFINITY,
                                  W_PROXIMITY, W_RESOURCE)
from repro.core.selection import PROXIMITY_PRECISION as PREFIX_CHARS

EARTH_KM = 6371.0
NEG = -1e30


# Cephes ``asinf`` minimax coefficients: asin(x) ~ x + x*z*P(z), z = x^2,
# on [0, 0.5]; larger arguments use asin(x) = pi/2 - 2*asin(sqrt((1-x)/2))
_ASIN_P = (4.2163199048e-2, 2.4181311049e-2, 4.5470025998e-2,
           7.4953002686e-2, 1.6666752422e-1)


def asin_unit(x):
    """fp32 ``arcsin`` on [0, 1] from ``sqrt`` and a polynomial — the
    operations Mosaic lowers (it has no ``asin``/``atan2``).  Error is
    within a few fp32 ulps of ``np.arcsin`` over the whole range."""
    big = x > 0.5
    z = jnp.where(big, 0.5 * (1.0 - x), x * x)
    r = jnp.where(big, jnp.sqrt(z), x)
    poly = _ASIN_P[0]
    for c in _ASIN_P[1:]:
        poly = poly * z + c
    p = r + r * z * poly
    return jnp.where(big, jnp.float32(jnp.pi / 2) - 2.0 * p, p)


def haversine_km(ulat, ulon, nlat, nlon):
    """Broadcasted fp32 haversine: (U, 1) x (1, N) -> (U, N)."""
    rad = jnp.float32(jnp.pi / 180.0)
    p1 = ulat * rad
    p2 = nlat * rad
    dp = (nlat - ulat) * rad
    dl = (nlon - ulon) * rad
    a = (jnp.sin(dp / 2) ** 2
         + jnp.cos(p1) * jnp.cos(p2) * jnp.sin(dl / 2) ** 2)
    return 2.0 * EARTH_KM * asin_unit(jnp.sqrt(jnp.clip(a, 0.0, 1.0)))


def affinity(user_net, node_aff):
    """(U, 1) int32 net index x (M, N) affinity rows -> (U, N): each
    user's own row, picked by selects.  Exact on every backend — a
    one-hot matmul would round the table through bf16 on the TPU's MXU
    at default precision."""
    aff = jnp.zeros((user_net.shape[0], node_aff.shape[1]), jnp.float32)
    for r in range(node_aff.shape[0]):
        aff = jnp.where(user_net == r, node_aff[r:r + 1, :], aff)
    return aff


def _raw_scores(user_lat, user_lon, user_net, node_lat, node_lon,
                node_free, node_aff):
    """Unfiltered (U, N) fp32 Algorithm-1 scores.  Single source for the
    scoring arithmetic — the sharded/unsharded decision-parity proof
    rests on both filters seeing bit-identical scores."""
    d = haversine_km(user_lat[:, None], user_lon[:, None],
                     node_lat[None, :], node_lon[None, :])
    prox = 1.0 / (1.0 + d / 10.0)
    aff = affinity(user_net[:, None], node_aff)        # (U, N)
    return (W_RESOURCE * node_free[None, :] + W_AFFINITY * aff
            + W_PROXIMITY * prox)


def proximity_mask(user_code20, node_code20, node_valid, need: int):
    """(U, N) bool: the adaptive-precision prefix filter over valid
    nodes — the restricted filter down to p=1, with unsatisfied rows
    falling back to no filter (every valid node)."""
    local, done = proximity_mask_restricted(user_code20, node_code20,
                                            node_valid, need, 1)
    valid = node_valid[None, :] > 0
    return jnp.where(done[:, None], local, valid)


def proximity_mask_restricted(user_code20, node_code20, node_valid,
                              need: int, p_min: int):
    """Shard-local adaptive filter: precisions restricted to
    ``p >= p_min`` (the shard's own prefix length), NO global fallback.
    Returns ``(mask, satisfied)`` — unsatisfied rows stay all-False and
    must escalate to the cross-shard border pass.  Because geohash cells
    nest, a satisfied row's level and mask equal the unrestricted
    ``proximity_mask`` computed over the full node set."""
    valid = node_valid[None, :] > 0
    u = user_code20.shape[0]
    local = jnp.zeros((u, node_code20.shape[0]), bool)
    done = jnp.zeros(u, bool)
    for p in range(PREFIX_CHARS, p_min - 1, -1):
        shift = 5 * (PREFIX_CHARS - p)
        eq = ((user_code20[:, None] >> shift)
              == (node_code20[None, :] >> shift)) & valid
        use = (eq.sum(axis=1) >= need) & ~done
        local = jnp.where(use[:, None], eq, local)
        done = done | use
    return local, done


def score_matrix_restricted(user_lat, user_lon, user_net, user_code20,
                            node_lat, node_lon, node_free, node_aff,
                            node_code20, node_valid, need: int, p_min: int):
    """(U, N) fp32 shard-local scores plus the (U,) satisfied mask.
    Scores are elementwise-identical to ``score_matrix`` over the same
    (user, node) pairs; unsatisfied rows are all ``NEG``."""
    scores = _raw_scores(user_lat, user_lon, user_net, node_lat, node_lon,
                         node_free, node_aff)
    local, sat = proximity_mask_restricted(user_code20, node_code20,
                                           node_valid, need, p_min)
    return jnp.where(local, scores, jnp.float32(NEG)), sat


def score_matrix(user_lat, user_lon, user_net, user_code20,
                 node_lat, node_lon, node_free, node_aff, node_code20,
                 node_valid, need: int):
    """(U, N) fp32 scores with filtered/invalid pairs at ``NEG``."""
    scores = _raw_scores(user_lat, user_lon, user_net, node_lat, node_lon,
                         node_free, node_aff)
    local = proximity_mask(user_code20, node_code20, node_valid, need)
    return jnp.where(local, scores, jnp.float32(NEG))


def geo_topk_reference(user_lat, user_lon, user_net, user_code20,
                       node_lat, node_lon, node_free, node_aff,
                       node_code20, node_valid, *, k: int, need: int):
    """-> (scores (U, k), indices (U, k)): per-user top-k replicas."""
    scores = score_matrix(user_lat, user_lon, user_net, user_code20,
                          node_lat, node_lon, node_free, node_aff,
                          node_code20, node_valid, need)
    return lax.top_k(scores, k)
