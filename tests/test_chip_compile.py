"""Compile the main-path kernels for a described TPU v5e chip.

The TPU's compiler is installed beside JAX, and it compiles for a chip that
is described and not attached. These tests give it the serving pool gather
and the parity encode at qwen2.5-3b's published KV widths (uint16 lanes, 8
banks, page 64, 2 KV heads, head dim 128), so a kernel the chip would
refuse (block shapes off the (8, 128) tiling, more VMEM than a kernel may
use) fails here without a chip. Nothing runs: results and times come only
from a chip run.

The topology is described inside a module fixture, never while a module is
imported: only one process at a time may load the TPU's library, and each
test worker imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.coded_kv_decode.kernel import gather_pool_pallas
from repro.kernels.xor_encode.kernel import encode_parities_pallas

NB, PAGE, HKV, D = 8, 64, 2, 128
# (pool pages, slots, pages per slot): the smoke run's pool (4 slots x 256
# tokens, pool twice the working set) and a realistic one (8 slots x 4096
# tokens; one layer's K banks alone are 33.5 MB, more than VMEM holds)
POOLS = {"smoke": (32, 4, 4), "realistic": (1024, 8, 64)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here, or it is held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("pool", sorted(POOLS))
@pytest.mark.parametrize("coded", [True, False], ids=["coded", "uncoded"])
def test_pool_gather_compiles_for_v5e(one_chip, pool, coded):
    pages, slots, mp = POOLS[pool]
    ng = NB // 2 if coded else 0
    banks = _spec((NB, pages // NB, PAGE, HKV, D), jnp.uint16, one_chip)
    par = _spec((ng, pages // NB, PAGE, HKV, D), jnp.uint16, one_chip)
    table = _spec((slots, mp), jnp.int32, one_chip)
    plan = _spec((slots, mp), jnp.bool_, one_chip)
    compiled = jax.jit(
        lambda kb, vb, kp, vp, pt, up: gather_pool_pallas(
            kb, vb, kp, vp, pt, up, interpret=False)
    ).lower(banks, banks, par, par, table, plan).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # pages stream through VMEM: the program keeps no pool-sized temporary
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_xor_encode_compiles_for_v5e(one_chip):
    banks = _spec((8, 1024, 128), jnp.uint32, one_chip)
    members = _spec((4, 3), jnp.int32, one_chip)
    compiled = jax.jit(
        lambda b, m: encode_parities_pallas(b, m, interpret=False)
    ).lower(banks, members).compile()
    assert "tpu_custom_call" in compiled.as_text()
