"""Compile-only rehearsals of the AirComp kernels for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler that ships with libtpu compiles
for a ``v5e:2x2`` topology that is described, not attached. It refuses what
interpret mode accepts — a block wider than VMEM, a slice not aligned to the
lane tiling — so these tests guard the kernels' blocking at real widths.

The topology is described inside a module-scoped fixture (never at import
time): only one process at a time may load libtpu, so only the worker that
runs these tests may touch it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.aircomp.kernel import LANE, TILE_M, _blocking
from repro.kernels.aircomp.ops import (aircomp_aggregate_flat,
                                       quant_aircomp_flat,
                                       sparse_aircomp_flat)

# the paper's logreg (C=K=40, M=7,850) and an unaligned model-sized width
SHAPES = [(40, 7_850), (40, 100_001)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compiles land in the persistent cache but cannot be
    # read back without the chip; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _aggregate(kind):
    """(fn(x, w, aux, z, ns, k), aux shape of [C, M]) for one kernel."""
    if kind == "analog":
        return (lambda x, w, _a, z, ns, k: aircomp_aggregate_flat(
            x, w, z, noise_std=ns, k=k)), None
    if kind == "quantized":
        # aux = the [C, M] rounding uniforms; the grid steps reuse w's shape
        return (lambda x, w, u, z, ns, k: quant_aircomp_flat(
            x, w, w, u, z, noise_std=ns, k=k)), "cm"
    return (lambda x, w, t, z, ns, k: sparse_aircomp_flat(
        x, w, t, z, noise_std=ns, k=k)), "c"


def _compile(one_chip, kind, c, m, batch=()):
    """Compile one kernel's default dispatch, vmapped over ``batch`` leading
    axes (the sweep's point and seed axes), for the described chip."""
    fn, aux = _aggregate(kind)
    for _ in batch:
        fn = jax.vmap(fn)

    def spec(*shape):
        return jax.ShapeDtypeStruct(batch + shape, jnp.float32,
                                    sharding=one_chip)

    aux_spec = {None: spec(c), "cm": spec(c, m), "c": spec(c)}[aux]
    args = (spec(c, m), spec(c), aux_spec, spec(m), spec(), spec())
    lowered = jax.jit(fn).lower(*args)
    assert lowered.out_info.shape == batch + (m,)
    compiled = lowered.compile()
    # default dispatch lowered for the TPU picks the compiled Pallas kernel
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("c,m", SHAPES)
@pytest.mark.parametrize("kind", ["analog", "quantized", "sparse"])
def test_aircomp_kernel_compiles_for_v5e(one_chip, kind, c, m):
    _compile(one_chip, kind, c, m)


@pytest.mark.parametrize("kind", ["analog", "quantized", "sparse"])
def test_aircomp_kernel_compiles_under_sweep_vmap_for_v5e(one_chip, kind):
    """``run_sweep`` vmaps the round over points × seeds: the kernel's
    per-row operands gain leading axes, and every block must still satisfy
    the TPU's (8, 128) tiling rule on its last two dimensions."""
    _compile(one_chip, kind, 40, 7_850, batch=(1, 2))


@pytest.mark.parametrize("m", [1, 127, 128, 1_000, 1_024, 7_850, 100_001,
                               1_000_001])
def test_blocking_is_lane_aligned_and_capped(m):
    tile, pad = _blocking(m)
    assert tile % LANE == 0 and 0 < tile <= TILE_M
    assert (m + pad) % tile == 0 and 0 <= pad < tile
