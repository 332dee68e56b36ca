"""The main path's Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler is installed, and it compiles for a
chip that is described and not attached. Interpret mode (every other
kernel test) cannot see what only Mosaic refuses — lane offsets it
cannot prove aligned, reductions it does not lower, VMEM overflow — so
these compiles guard the chip path on every run.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every pytest
worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import distance_argmin as da
from repro.kernels import flash_attention as fa

N = 4096   # one serving batch


@pytest.fixture(scope="module")
def chip():
    """A described v5e chip (SingleDeviceSharding), with JAX's persistent
    compilation cache off: a compile for a described chip is written to
    the cache but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("accumulate", [False, True])
def test_distance_argmin_l2_compiles(chip, accumulate):
    txt = _compiled_text(
        lambda x, c, v: da.distance_argmin_l2(x, c, v, accumulate=accumulate),
        chip, ((N, 128), jnp.float32), ((1024, 128), jnp.float32),
        ((1024,), jnp.bool_))
    assert "tpu_custom_call" in txt


def test_distance_argmin_hamming_compiles(chip):
    txt = _compiled_text(
        da.distance_argmin_hamming, chip, ((N, 64), jnp.int32),
        ((1024, 64), jnp.int32), ((1024,), jnp.bool_))
    assert "tpu_custom_call" in txt


def test_distance_argmin_hamming_packed_compiles(chip):
    txt = _compiled_text(
        lambda x, c, v: da.distance_argmin_hamming_packed(x, c, v, bits=16),
        chip, ((N, 32), jnp.uint32), ((1024, 32), jnp.uint32),
        ((1024,), jnp.bool_))
    assert "tpu_custom_call" in txt


def test_flash_centroid_attention_compiles(chip):
    """Decode step at smollm_360m's head layout: 15 query heads over 5
    KV heads of width 64, 256 centroids per head."""
    txt = _compiled_text(
        fa.flash_centroid_attention, chip, ((1, 15, 1, 64), jnp.float32),
        ((1, 5, 256, 64), jnp.float32), ((1, 5, 256, 64), jnp.float32),
        ((1, 5, 256), jnp.float32))
    assert "tpu_custom_call" in txt
