"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles,
swept over shapes and dtypes, plus VMEM-budget sanity for the TPU tiles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import geohash
from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_mha_reference
from repro.kernels.geo_topk import tune as geo_tune
from repro.kernels.geo_topk.kernel import (geo_topk_pallas,
                                           geo_topk_tiled_pallas,
                                           vmem_bytes_tiled)
from repro.kernels.geo_topk.kernel import vmem_bytes as geo_vmem
from repro.kernels.geo_topk.ops import geo_topk, pack_inputs
from repro.kernels.geo_topk.ref import affinity, asin_unit, geo_topk_reference
from repro.kernels.flash_attention import kernel as fa_kernel
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import mha_reference
from repro.kernels.moe_gmm.kernel import gmm_pallas
from repro.kernels.moe_gmm.ref import gmm_reference
from repro.kernels.ssm_scan.kernel import ssd_scan_pallas
from repro.kernels.ssm_scan.kernel import vmem_bytes as ssd_vmem
from repro.kernels.ssm_scan.ref import (ssd_chunked_reference,
                                        ssd_decode_step, ssd_sequential)

RNG = np.random.default_rng(42)

# interpret-mode kernel sweeps are priced in seconds per case on CPU:
# tier-1 keeps the float32 parity pin per kernel family and one layout
# case per geo_topk variant; the rest ride the slow marker
BF16_SLOW = pytest.param(jnp.bfloat16, marks=pytest.mark.slow)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-4


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FA_CASES = [
    # B, Hq, Hkv, Tq, Tk, D, causal, offset
    (2, 4, 2, 128, 128, 64, True, 0),
    pytest.param((1, 8, 8, 96, 96, 32, True, 0), marks=pytest.mark.slow),
    pytest.param((1, 4, 1, 64, 256, 64, True, 192),
                 marks=pytest.mark.slow),  # chunked prefill w/ offset
    (2, 2, 2, 50, 200, 128, False, 0),     # non-causal (encoder), ragged
    pytest.param((1, 6, 3, 33, 65, 16, True, 0),
                 marks=pytest.mark.slow),  # odd sizes -> padding path
]


@pytest.mark.parametrize("case", FA_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, BF16_SLOW])
def test_flash_attention_matches_reference(case, dtype):
    B, Hq, Hkv, Tq, Tk, D, causal, off = case
    q = jnp.asarray(RNG.normal(size=(B, Hq, Tq, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, Tk, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, Tk, D)), dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, q_offset=off,
                                 block_q=32, block_k=32, interpret=True)
    ref = mha_reference(q, k, v, causal=causal, q_offset=off)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype))


def test_flash_attention_sliding_window():
    q = jnp.asarray(RNG.normal(size=(1, 2, 128, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 2, 128, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 2, 128, 32)), jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=True, window=32,
                                 block_q=32, block_k=32, interpret=True)
    ref = mha_reference(q, k, v, causal=True, window=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_flash_attention_vmem_budget():
    # production tile sizes must fit v5e VMEM (~128 MB, use <= half)
    assert fa_kernel.vmem_bytes(128, 128, 128) < 64 * 2**20


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

DEC_CASES = [
    (2, 4, 2, 512, 64),
    pytest.param((1, 8, 1, 300, 128), marks=pytest.mark.slow),
    pytest.param((4, 2, 2, 64, 32), marks=pytest.mark.slow),
    pytest.param((3, 12, 4, 100, 16), marks=pytest.mark.slow),
]


@pytest.mark.parametrize("case", DEC_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, BF16_SLOW])
def test_decode_attention_matches_reference(case, dtype):
    B, Hq, Hkv, S, D = case
    q = jnp.asarray(RNG.normal(size=(B, Hq, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, S, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, S, D)), dtype)
    lens = jnp.asarray(RNG.integers(1, S + 1, size=(B,)), jnp.int32)
    out = decode_attention_pallas(q, k, v, lens, block_s=128, interpret=True)
    ref = decode_mha_reference(q, k, v, lens)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype))


def test_decode_attention_ignores_padding():
    """Entries past ``lengths`` must not affect the result."""
    B, Hq, Hkv, S, D = 2, 4, 2, 64, 32
    q = jnp.asarray(RNG.normal(size=(B, Hq, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, S, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, S, D)), jnp.float32)
    lens = jnp.asarray([10, 20], jnp.int32)
    out1 = decode_attention_pallas(q, k, v, lens, interpret=True)
    k2 = k.at[:, :, 30:].set(999.0)
    v2 = v.at[:, :, 30:].set(-999.0)
    out2 = decode_attention_pallas(q, k2, v2, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))


# ---------------------------------------------------------------------------
# grouped matmul
# ---------------------------------------------------------------------------

GMM_CASES = [
    (4, 64, 128, 256),
    pytest.param((2, 100, 96, 130), marks=pytest.mark.slow),
    pytest.param((8, 32, 64, 64), marks=pytest.mark.slow),
    (1, 17, 33, 65),               # ragged: the padding path stays pinned
]


@pytest.mark.parametrize("case", GMM_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, BF16_SLOW])
def test_gmm_matches_reference(case, dtype):
    E, C, D, F = case
    x = jnp.asarray(RNG.normal(size=(E, C, D)), dtype)
    w = jnp.asarray(RNG.normal(size=(E, D, F)), dtype)
    out = gmm_pallas(x, w, block_c=32, block_f=64, block_d=64,
                     interpret=True)
    ref = gmm_reference(x, w)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=_tol(dtype) * np.sqrt(D), rtol=5e-2 if dtype == jnp.bfloat16
        else 1e-4)


# ---------------------------------------------------------------------------
# generalized SSD scan (Mamba2 + mLSTM styles)
# ---------------------------------------------------------------------------

def _ssd_inputs(B, T, H, P, N, style, per_head):
    x = jnp.asarray(RNG.normal(size=(B, T, H, P)), jnp.float32)
    if style == "mamba2":
        dt = np.abs(RNG.normal(size=(B, T, H))) * 0.5 + 0.01
        A = -np.abs(RNG.normal(size=(H,))) - 0.1
        g = jnp.asarray(dt * A, jnp.float32)
        s = jnp.asarray(dt, jnp.float32)
    else:  # mlstm
        f = RNG.normal(size=(B, T, H)) + 2.0
        g = jnp.asarray(np.log(1 / (1 + np.exp(-f))), jnp.float32)
        s = jnp.asarray(np.exp(RNG.normal(size=(B, T, H)) * 0.4 - 1),
                        jnp.float32)
    bc_shape = (B, T, H, N) if per_head else (B, T, N)
    Bm = jnp.asarray(RNG.normal(size=bc_shape), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=bc_shape), jnp.float32)
    D = jnp.asarray(RNG.normal(size=(H,)), jnp.float32)
    return x, g, s, Bm, Cm, D


@pytest.mark.parametrize("style", ["mamba2", "mlstm"])
@pytest.mark.parametrize("per_head",
                         [False, pytest.param(True,
                                              marks=pytest.mark.slow)])
@pytest.mark.parametrize(
    "shape", [(2, 64, 3, 16, 8, 16),
              pytest.param((1, 100, 2, 32, 16, 32),
                           marks=pytest.mark.slow)])
def test_ssd_chunked_and_pallas_match_sequential(style, per_head, shape):
    B, T, H, P, N, chunk = shape
    x, g, s, Bm, Cm, D = _ssd_inputs(B, T, H, P, N, style, per_head)
    y_seq, h_seq = ssd_sequential(x, g, s, Bm, Cm, D)
    y_chk, _ = ssd_chunked_reference(x, g, s, Bm, Cm, D, chunk=chunk)
    y_pal, h_pal = ssd_scan_pallas(x, g, s, Bm, Cm, D, chunk=chunk,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(y_chk), np.asarray(y_seq),
                               atol=5e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_seq),
                               atol=5e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(h_pal), np.asarray(h_seq),
                               atol=5e-3, rtol=1e-3)


def test_ssd_decode_chain_equals_sequential():
    B, T, H, P, N = 2, 32, 2, 8, 8
    x, g, s, Bm, Cm, D = _ssd_inputs(B, T, H, P, N, "mamba2", False)
    y_seq, h_seq = ssd_sequential(x, g, s, Bm, Cm, D)
    h = jnp.zeros((B, H, P, N), jnp.float32)
    ys = []
    for t in range(T):
        y, h = ssd_decode_step(h, x[:, t], g[:, t], s[:, t], Bm[:, t],
                               Cm[:, t], D)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1)),
                               np.asarray(y_seq), atol=5e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_seq), atol=5e-3)


def test_ssd_vmem_budget():
    assert ssd_vmem(256, 64, 128) < 64 * 2**20


# ---------------------------------------------------------------------------
# fused geo-selection top-k
# ---------------------------------------------------------------------------

def _geo_inputs(u, n, spread=0.5, seed=0):
    rng = np.random.default_rng(seed)
    base = (44.97, -93.22)
    ulat = base[0] + rng.uniform(-spread, spread, u)
    ulon = base[1] + rng.uniform(-spread, spread, u)
    nlat = base[0] + rng.uniform(-spread, spread, n)
    nlon = base[1] + rng.uniform(-spread, spread, n)
    unet = rng.integers(0, 3, u)
    nnet = rng.integers(0, 3, n)
    nfree = rng.uniform(0, 1, n)
    uc = geohash.encode_batch(ulat, ulon, 9)
    nc = geohash.encode_batch(nlat, nlon, 9)
    return pack_inputs(ulat, ulon, unet, uc, nlat, nlon, nfree, nnet, nc)


GEO_CASES = [
    # U, N, k, block_u — exercise padding on every axis
    (64, 128, 3, 32),
    pytest.param((50, 37, 5, 16),
                 marks=pytest.mark.slow),      # ragged U and N
    pytest.param((8, 3, 3, 8),
                 marks=pytest.mark.slow),      # k == N: all selected
    pytest.param((130, 257, 8, 128), marks=pytest.mark.slow),
]


@pytest.mark.parametrize("case", GEO_CASES)
def test_geo_topk_pallas_matches_oracle(case):
    u, n, k, bu = case
    packed = _geo_inputs(u, n, seed=u + n)
    need = min(4, n)
    s_ref, i_ref = geo_topk_reference(
        *[jnp.asarray(a) for a in packed], k=k, need=need)
    s_pal, i_pal = geo_topk_pallas(*packed, k=k, need=need, block_u=bu,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(s_pal), np.asarray(s_ref),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(i_pal), np.asarray(i_ref))


@pytest.mark.parametrize(
    "spread", [pytest.param(0.02, marks=pytest.mark.slow), 5.0])
def test_geo_topk_proximity_filter_consistency(spread):
    """Tight clusters trigger the high-precision filter path; global
    spreads fall through to lower precisions — both must match."""
    packed = _geo_inputs(40, 64, spread=spread, seed=3)
    s_ref, i_ref = geo_topk_reference(
        *[jnp.asarray(a) for a in packed], k=4, need=4)
    s_pal, i_pal = geo_topk_pallas(*packed, k=4, need=4, block_u=16,
                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(i_pal), np.asarray(i_ref))
    assert np.isfinite(np.asarray(s_ref)).all()


def test_geo_topk_op_dispatches_to_oracle_on_cpu():
    packed = _geo_inputs(16, 24, seed=11)
    s_op, i_op = geo_topk(packed, k=3)
    s_ref, i_ref = geo_topk_reference(
        *[jnp.asarray(a) for a in packed], k=3, need=4)
    np.testing.assert_array_equal(np.asarray(i_op), np.asarray(i_ref))
    np.testing.assert_allclose(np.asarray(s_op), np.asarray(s_ref),
                               atol=1e-6, rtol=1e-6)


def test_geo_topk_force_pallas_off_tpu_needs_interpret():
    """Off the TPU the kernel runs only through the interpreter, and
    only when asked: no silent interpretation behind ``force_pallas``."""
    packed = _geo_inputs(16, 24, seed=11)
    with pytest.raises(RuntimeError, match="interpret=True"):
        geo_topk(packed, k=3, force_pallas=True)


def test_asin_unit_within_fp32_rounding_of_arcsin():
    """The Mosaic-lowerable arcsin the haversine uses stays within a few
    fp32 ulps of float64 ``np.arcsin`` over all of [0, 1], the branch
    point at 0.5 and the far end (antipodal pairs) included."""
    x = np.concatenate([
        np.linspace(0.0, 1.0, 400_001, dtype=np.float32),
        np.nextafter(np.float32(0.5), np.float32([0.0, 1.0])),
        np.float32([1e-30, 1e-8, 1.0 - 2**-24])])
    got = np.asarray(asin_unit(jnp.asarray(x)), np.float64)
    ref = np.arcsin(x.astype(np.float64))
    ulp = np.spacing(ref.astype(np.float32)).astype(np.float64)
    assert (np.abs(got - ref) <= 3 * ulp).all()


def test_affinity_rows_are_exact():
    """Each user's affinity row is copied bit for bit from the table
    (a default-precision matmul on the MXU would round it through
    bf16)."""
    from repro.core.selection import AFFINITY_TABLE
    rng = np.random.default_rng(7)
    net = rng.integers(0, AFFINITY_TABLE.shape[0], 64)
    node_net = rng.integers(0, AFFINITY_TABLE.shape[0], 40)
    table = AFFINITY_TABLE[node_net, :].T.astype(np.float32)   # (M, N)
    got = np.asarray(affinity(jnp.asarray(net[:, None], jnp.int32),
                              jnp.asarray(table)))
    np.testing.assert_array_equal(got, table[net])


def test_geo_topk_vmem_budget():
    # production tile: 128 users x 4096 nodes must fit half a v5e VMEM
    assert geo_vmem(128, 4096) < 64 * 2**20


# ---------------------------------------------------------------------------
# node-tiled geo top-k (past the all-nodes-in-VMEM wall)
# ---------------------------------------------------------------------------

def _geo_inputs_valid(u, n, spread=0.5, seed=0, valid=None):
    rng = np.random.default_rng(seed)
    base = (44.97, -93.22)
    ulat = base[0] + rng.uniform(-spread, spread, u)
    ulon = base[1] + rng.uniform(-spread, spread, u)
    nlat = base[0] + rng.uniform(-spread, spread, n)
    nlon = base[1] + rng.uniform(-spread, spread, n)
    return pack_inputs(ulat, ulon, rng.integers(0, 3, u),
                       geohash.encode_batch(ulat, ulon, 9),
                       nlat, nlon, rng.uniform(0, 1, n),
                       rng.integers(0, 3, n),
                       geohash.encode_batch(nlat, nlon, 9), valid)


TILED_CASES = [
    # U, N, k, block_u, node_tile — N spans multiple tiles, ragged too
    (48, 640, 3, 16, 256),
    pytest.param((20, 1000, 5, 8, 128), marks=pytest.mark.slow),
    pytest.param((8, 257, 4, 8, 128),
                 marks=pytest.mark.slow),  # ragged final tile
    pytest.param((16, 128, 3, 8, 128),
                 marks=pytest.mark.slow),  # single tile degenerates
]


@pytest.mark.parametrize("case", TILED_CASES)
def test_geo_topk_tiled_matches_oracle(case):
    u, n, k, bu, nt = case
    packed = _geo_inputs_valid(u, n, seed=u + n)
    need = min(4, n)
    s_ref, i_ref = geo_topk_reference(
        *[jnp.asarray(a) for a in packed], k=k, need=need)
    s_t, i_t = geo_topk_tiled_pallas(*packed, k=k, need=need, block_u=bu,
                                     node_tile=nt, interpret=True)
    np.testing.assert_allclose(np.asarray(s_t), np.asarray(s_ref),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(i_t), np.asarray(i_ref))


def test_geo_topk_tiled_ties_at_tile_boundary():
    """Equal-score nodes straddling a tile edge must resolve to the
    lowest global index, exactly like ``lax.top_k`` over the full row."""
    u, n, nt = 8, 384, 128
    packed = _geo_inputs_valid(u, n, seed=5)
    # clone node 126's full scoring identity across the 128-boundary
    for fld in ("node_lat", "node_lon", "node_free", "node_code20"):
        arr = getattr(packed, fld)
        arr[125:132] = arr[126]
    packed.node_aff[:, 125:132] = packed.node_aff[:, 126:127]
    s_ref, i_ref = geo_topk_reference(
        *[jnp.asarray(a) for a in packed], k=6, need=4)
    s_t, i_t = geo_topk_tiled_pallas(*packed, k=6, need=4, block_u=8,
                                     node_tile=nt, interpret=True)
    np.testing.assert_array_equal(np.asarray(i_t), np.asarray(i_ref))
    np.testing.assert_allclose(np.asarray(s_t), np.asarray(s_ref),
                               atol=1e-5)


@pytest.mark.slow
def test_geo_topk_tiled_all_invalid_tiles():
    """Whole-tile invalid spans (churned-out nodes / jit padding) and the
    fully-invalid query both match the reference."""
    u, n, nt = 12, 512, 128
    valid = np.ones(n, np.float32)
    valid[128:256] = 0.0                     # one entirely dead tile
    valid[500:] = 0.0
    packed = _geo_inputs_valid(u, n, seed=9, valid=valid)
    s_ref, i_ref = geo_topk_reference(
        *[jnp.asarray(a) for a in packed], k=4, need=4)
    s_t, i_t = geo_topk_tiled_pallas(*packed, k=4, need=4, block_u=8,
                                     node_tile=nt, interpret=True)
    np.testing.assert_array_equal(np.asarray(i_t), np.asarray(i_ref))

    packed = _geo_inputs_valid(u, n, seed=10,
                               valid=np.zeros(n, np.float32))
    s_ref, i_ref = geo_topk_reference(
        *[jnp.asarray(a) for a in packed], k=3, need=4)
    s_t, i_t = geo_topk_tiled_pallas(*packed, k=3, need=4, block_u=8,
                                     node_tile=nt, interpret=True)
    np.testing.assert_array_equal(np.asarray(i_t), np.asarray(i_ref))
    assert (np.asarray(s_t) < -1e29).all()


@pytest.mark.slow
def test_geo_topk_tiled_validates_at_64k_nodes():
    """The acceptance regime: N >= 64k — far past the untiled kernel's
    VMEM wall — still matches the reference exactly."""
    u, n = 8, 65536
    packed = _geo_inputs_valid(u, n, seed=3)
    s_ref, i_ref = geo_topk_reference(
        *[jnp.asarray(a) for a in packed], k=8, need=4)
    s_t, i_t = geo_topk_tiled_pallas(*packed, k=8, need=4, block_u=8,
                                     node_tile=8192, interpret=True)
    np.testing.assert_array_equal(np.asarray(i_t), np.asarray(i_ref))
    np.testing.assert_allclose(np.asarray(s_t), np.asarray(s_ref),
                               atol=1e-5, rtol=1e-5)


def test_geo_topk_tiled_vmem_independent_of_n():
    # the tiled budget is a function of the tile, not the fleet size —
    # this is what lifts the N ≲ 16k cap to 100k+ nodes
    assert vmem_bytes_tiled(128, 2048) < 64 * 2**20
    assert vmem_bytes_tiled(256, 8192) < 64 * 2**20
    assert geo_vmem(128, 131072) > 64 * 2**20      # untiled would not fit


@pytest.mark.slow       # registration smoke, not an identity pin
def test_geo_topk_autotune_smoke_end_to_end(monkeypatch, tmp_path):
    """The registered ``bench_autotune --smoke`` profile: a tiny
    interpret-mode sweep must run both layouts, cache a winner, and the
    dispatcher must serve it.  Cache and artifact are sandboxed so the
    smoke winner can't leak into other tests or the working tree."""
    import benchmarks.bench_autotune as ba
    monkeypatch.setattr(ba, "CACHE_PATH", tmp_path / "geo_topk.json")
    geo_tune.clear_cache()
    try:
        rows = ba.run(smoke=True)
        assert rows and any("winner=True" in r[2] for r in rows)
        assert (tmp_path / "geo_topk.json").exists()
        u, n, k = 32, 128, 4
        cfg = geo_tune.get_config(u, n, k)
        assert geo_tune.cache_key(u, n, k) in geo_tune._CACHE
        assert cfg in geo_tune.candidate_configs(u, n, k) + \
            [(32, None), (32, 64)]
        # winner actually dispatches through ops.geo_topk
        packed = _geo_inputs_valid(u, n, seed=1)
        s, i = geo_topk(packed, k=k, force_pallas=True, interpret=True)
        s_ref, i_ref = geo_topk_reference(
            *[jnp.asarray(a) for a in packed], k=k, need=4)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))
    finally:
        geo_tune.clear_cache()
