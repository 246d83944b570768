"""Ahead-of-time compiles of the selection path for a described TPU v5e.

Interpret-mode tests cannot see what the chip's compiler refuses: ops
Mosaic has no lowering for, i1 masks it cannot select, kernels over the
scoped VMEM limit.  These tests hand the installed TPU compiler a
described ``v5e:2x2`` topology (no chip attached) and compile the
``geo_topk`` kernels and the jnp scoring at the main path's shapes:
100,000 users against a 1,024-slot metro fleet and a 10,240-slot border
pass, with the layout ``tune.default_config`` picks.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every xdist worker imports
this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.geo_topk import tune
from repro.kernels.geo_topk.kernel import (geo_topk_pallas,
                                           geo_topk_tiled_pallas)
from repro.kernels.geo_topk.ref import geo_topk_reference

USERS = 100_000
K = 8
NEED = 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _query(sharding, u, n, m=3):
    """Shapes of a packed ``GeoTopKInputs`` query on the described chip."""
    def f(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return (f((u,), jnp.float32), f((u,), jnp.float32), f((u,), jnp.int32),
            f((u,), jnp.int32), f((n,), jnp.float32), f((n,), jnp.float32),
            f((n,), jnp.float32), f((m, n), jnp.float32),
            f((n,), jnp.int32), f((n,), jnp.float32))


def _compile(fn, args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n,layout", [(1024, "untiled"), (10240, "tiled")])
def test_geo_topk_default_layout_compiles_for_v5e(one_chip, n, layout):
    """The layout the chooser picks at the main path's node counts
    lowers through Mosaic and fits the kernel's scoped VMEM."""
    bu, nt = tune.default_config(USERS, n, K)
    assert (nt is None) == (layout == "untiled"), (bu, nt)
    if nt is None:
        def fn(*a):
            return geo_topk_pallas(*a, k=K, need=NEED, block_u=bu)
    else:
        def fn(*a):
            return geo_topk_tiled_pallas(*a, k=K, need=NEED, block_u=bu,
                                         node_tile=nt)
    assert "tpu_custom_call" in _compile(fn, _query(one_chip, USERS, n))


def test_geo_topk_jnp_scoring_compiles_for_v5e(one_chip):
    """The jnp oracle the fused device tick scores with (haversine,
    affinity, prefix filter, ``lax.top_k``) compiles at (100k, 1,024)."""
    text = _compile(lambda *a: geo_topk_reference(*a, k=K, need=NEED),
                    _query(one_chip, USERS, 1024))
    assert "tpu_custom_call" not in text
