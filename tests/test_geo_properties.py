"""Property-based tests for the batched geo primitives the shard router
silently relies on (hypothesis).

The Beacon fault-domain router assumes three invariants of
``repro.core.geohash``:

* ``encode_batch`` produces exactly the bit stream the string ``encode``
  packs (so region prefix strings, Morton prefix codes and decoded cell
  centers all name the same cell), and decoding the code's cell contains
  the encoded point;
* Morton prefix **nesting** — the precision-p cell contains all its
  precision-(p+1) children (``code(p) == code(p+1) >> 5``), the property
  that makes in-shard proximity-hit counts equal global counts;
* ``distance_km_batch`` is a metric in the ways routing needs: symmetric,
  zero at identity, consistent with the scalar haversine, and triangle-
  sane (the nearest-live-Beacon pick is order-independent).
"""
import numpy as np

from hypothesis import example, given, settings
from hypothesis import strategies as st

from repro.core import geohash

lat_st = st.floats(min_value=-89.9, max_value=89.9, allow_nan=False)
lon_st = st.floats(min_value=-179.9, max_value=179.9, allow_nan=False)


@given(lat=lat_st, lon=lon_st, p=st.integers(min_value=1, max_value=9))
@example(lat=0.0, lon=-3.103005580771824e-57, p=1)   # lon + 180 rounds to 180
@settings(max_examples=100, deadline=None)
def test_encode_batch_matches_string_encode_and_roundtrips(lat, lon, p):
    """Batch Morton code == string-encoded code, and the decoded cell
    contains the point (within the cell half-sizes)."""
    code = int(geohash.encode_batch(np.asarray([lat]), np.asarray([lon]),
                                    p)[0])
    gh = geohash.encode(lat, lon, precision=p)
    assert code == geohash.str_to_code(gh)
    assert geohash.code_to_str(code, p) == gh
    dlat, dlon, elat, elon = geohash.decode(geohash.code_to_str(code, p))
    assert abs(dlat - lat) <= elat * 1.0001
    assert abs(dlon - lon) <= elon * 1.0001


@given(lat=lat_st, lon=lon_st, p=st.integers(min_value=1, max_value=8))
@settings(max_examples=100, deadline=None)
def test_morton_prefix_nesting(lat, lon, p):
    """The precision-p cell contains its precision-(p+1) child: dropping
    the child's last base32 char (5 bits) recovers the parent code.  This
    is the invariant behind in-shard-hits == global-hits."""
    child = int(geohash.encode_batch(np.asarray([lat]), np.asarray([lon]),
                                     p + 1)[0])
    parent = int(geohash.encode_batch(np.asarray([lat]), np.asarray([lon]),
                                      p)[0])
    assert parent == child >> 5


@given(lat=lat_st, lon=lon_st, p=st.integers(min_value=2, max_value=9))
@example(lat=0.0, lon=-2.220446049250313e-16, p=2)   # lon + 180 rounds to 180
@settings(max_examples=50, deadline=None)
def test_shared_prefix_chars_matches_string_common_prefix(lat, lon, p):
    """The vectorized prefix-length primitive agrees with the string one
    for a point and a perturbed neighbour."""
    lat2 = min(89.9, lat + 0.3)
    lon2 = min(179.9, lon + 0.3)
    a = geohash.encode_batch(np.asarray([lat]), np.asarray([lon]), p)
    b = geohash.encode_batch(np.asarray([lat2]), np.asarray([lon2]), p)
    want = geohash.common_prefix(geohash.encode(lat, lon, p),
                                 geohash.encode(lat2, lon2, p))
    assert int(geohash.shared_prefix_chars(a, b, p)[0]) == want


@given(lat1=lat_st, lon1=lon_st, lat2=lat_st, lon2=lon_st)
@settings(max_examples=100, deadline=None)
def test_distance_batch_symmetry_and_scalar_parity(lat1, lon1, lat2, lon2):
    d_ab = float(geohash.distance_km_batch(lat1, lon1, lat2, lon2))
    d_ba = float(geohash.distance_km_batch(lat2, lon2, lat1, lon1))
    np.testing.assert_allclose(d_ab, d_ba, rtol=1e-12)
    np.testing.assert_allclose(
        d_ab, geohash.distance_km(lat1, lon1, lat2, lon2),
        rtol=1e-9, atol=1e-9)
    assert d_ab >= 0.0
    assert float(geohash.distance_km_batch(lat1, lon1, lat1, lon1)) == 0.0


@given(lat1=lat_st, lon1=lon_st, lat2=lat_st, lon2=lon_st,
       lat3=lat_st, lon3=lon_st)
@settings(max_examples=100, deadline=None)
def test_distance_batch_triangle_inequality(lat1, lon1, lat2, lon2,
                                            lat3, lon3):
    """Great-circle distance is a metric: d(a,c) <= d(a,b) + d(b,c).
    The nearest-live-Beacon handoff relies on this staying sane."""
    d_ac = float(geohash.distance_km_batch(lat1, lon1, lat3, lon3))
    d_ab = float(geohash.distance_km_batch(lat1, lon1, lat2, lon2))
    d_bc = float(geohash.distance_km_batch(lat2, lon2, lat3, lon3))
    assert d_ac <= d_ab + d_bc + 1e-6
