"""The main path's Pallas kernels compile for a TPU v5e.

Interpret mode runs the kernel bodies on the CPU but never hands them to
the TPU compiler, which refuses what the interpreter accepts: blocks not
aligned to the (8, 128) tiling, rank-1 blocks that are not whole lane
tiles, more fast memory than a kernel may use.  These tests compile each
kernel of the main path for a described, unattached v5e chip at the widths
``chip_smoke.py`` runs (nothing executes) and check that the compiled
program holds the kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and test workers import every file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fused_zmap, fused_zstats, ops
from repro.kernels.dirichlet_expectation import dirichlet_expectation
from repro.kernels.ref import ZChild


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _flat_zstats(tables):
    def fn(tp, rows, tab, vals, zm):
        return fused_zstats.zstats(tp, rows, (ZChild(tab, vals),), zm,
                                   tables=tables)
    return fn


# (K, V, prior rows, tokens): resident tables at a small vocabulary, and
# the LDA step of a 512-document NYTimes batch (V = 102,660, K = 256,
# ~332 tokens a document), whose topic-word table streams from HBM
@pytest.mark.parametrize("tables", ["elog", "alpha"])
@pytest.mark.parametrize("route,k,v,g,n", [
    ("fused", 128, 2000, 64, 4096),
    ("fused-streamed", 256, 102_660, 512, 169_984),
])
def test_fused_zstats_compiles(one_chip, route, k, v, g, n, tables):
    assert ops.routing(jax.ShapeDtypeStruct((g, k), jnp.float32), None,
                       (ZChild(jax.ShapeDtypeStruct((k, v), jnp.float32),
                               None),),
                       tables=tables, backend="pallas",
                       n_latent=n).path == route
    hlo = _compiled_text(_flat_zstats(tables), one_chip,
                         ((g, k), jnp.float32), ((n,), jnp.int32),
                         ((k, v), jnp.float32), ((n,), jnp.int32),
                         ((n,), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_fused_zmap_compiles(one_chip):
    """SLDA: 2,000 sentences of 8 tokens under 200 documents, K=32,
    V=5,000 — the two-phase segment-latent kernel."""
    k, v, g, nz, n = 32, 5000, 200, 2000, 16_000

    def fn(tp, rows, tab, vals, zmap, mask, zm):
        return fused_zmap.zstats_zmap(
            tp, rows, (ZChild(tab, vals, 1, zmap, None, mask),), zm,
            tables="alpha")

    hlo = _compiled_text(fn, one_chip, ((g, k), jnp.float32),
                         ((nz,), jnp.int32), ((k, v), jnp.float32),
                         ((n,), jnp.int32), ((n,), jnp.int32),
                         ((n,), jnp.float32), ((nz,), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_dirichlet_expectation_compiles(one_chip):
    """The topic-word table of the NYTimes LDA, K=256 x V=102,660."""
    hlo = _compiled_text(dirichlet_expectation, one_chip,
                         ((256, 102_660), jnp.float32))
    assert "tpu_custom_call" in hlo
