"""The main path's EC kernels compile for a TPU v5e at their real widths.

Nothing here runs on a chip: the TPU compiler builds each program for a
described (not attached) v5e, so a kernel the chip's compiler refuses —
a misaligned tile, too much VMEM, more HBM than a chip has — fails here
at no chip time.  The topology is described inside a module fixture:
only one process at a time may load the TPU library, and describing it
while modules are imported would make xdist workers collect different
tests.

The CRUSH fast rule's programs at 100k PGs compile too, but take 7-17 s
each (PR 21 rehearsal), so they stay out of the tier-1 suite.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

K, M, S, C = 8, 4, 64, 131072        # 64 objects of 1 MiB, k=8 m=4


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off (its entries for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"   # else libtpu logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("rows", [M, 2], ids=["encode_m4", "decode_e2"])
def test_gf_bit_matmul_compiles_for_v5e(one_chip, rows):
    """The XLA GF(2^8) bit-matmul: encode (k -> m rows) and the decode
    shape with two erasures (k survivors -> 2 rows)."""
    from ceph_tpu.ops.gf_matmul import gf_bit_matmul
    compiled = gf_bit_matmul.lower(
        _spec((S, K, C), jnp.uint8, one_chip),
        _spec((K * 8, rows * 8), jnp.int8, one_chip)).compile()
    out = compiled.out_info
    assert out.shape == (S, rows, C) and out.dtype == jnp.uint8
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < 16 * 10 ** 9


@pytest.mark.parametrize("k,m", [(4, 2), (3, 1)],
                         ids=["lrc_global", "lrc_local"])
def test_lrc_layer_matmuls_compile_for_v5e(one_chip, k, m):
    """The layers of an LRC k=4 m=2 l=3 write of 4 MiB (256 stripes of
    4 KiB chunks): the global layer, 4 chunks to 2, and each local
    layer, 3 chunks to 1."""
    from ceph_tpu.ops.gf_matmul import gf_bit_matmul
    compiled = gf_bit_matmul.lower(
        _spec((256, k, 4096), jnp.uint8, one_chip),
        _spec((k * 8, m * 8), jnp.int8, one_chip)).compile()
    assert compiled.out_info.shape == (256, m, 4096)


def test_pallas_gf_kernel_compiles_for_v5e(one_chip):
    """The fused Pallas kernel, not interpreted: a real Mosaic kernel."""
    from ceph_tpu.ops.gf_pallas import _run
    compiled = _run.lower(
        _spec((S, K, C), jnp.uint8, one_chip),
        _spec((M * 8, K * 8), jnp.int8, one_chip),
        interpret=False).compile()
    assert compiled.out_info.shape == (S, M, C)
    assert "tpu_custom_call" in compiled.as_text()

