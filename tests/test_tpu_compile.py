"""Ahead-of-time compiles of the decode path for a TPU v5e chip.

The TPU compiler compiles for a described `v5e:2x2` topology with no chip
attached, so these tests catch what the chip's compiler would refuse (a
lowering it does not implement, a program that does not fit the chip's
16 GB of HBM) without a chip. Nothing runs: they say nothing about
results or times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import encoder as enc
from repro.core.decoder import Decoder, _decode_sel_jit, _fnv_rows_jit
from repro.data.fastq import make_fastq

V5E_HBM_BYTES = 16 * 10**9
N_SEL = 64          # blocks per decode selection


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    # the TPU library otherwise writes its compiler logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
    return used


@pytest.mark.parametrize("block_size", [4096, 65536, 1 << 20])
def test_decode_selection_compiles_for_v5e(one_chip, no_persistent_cache,
                                           block_size):
    """The jitted mode-2 decode (rANS entropy stage + match resolution)
    of a 64-block selection, at the geometry of a real "ra" archive,
    compiles for one v5e chip and fits its HBM."""
    data = make_fastq("platinum", n_reads=block_size // 200 + 8, seed=0)
    dec = Decoder(enc.encode(data, block_size=block_size), backend="auto")
    n_blocks = 4 * N_SEL
    per_block = -(-dec.da.words.size // dec.da.n_blocks)

    def spec(x, n):
        return jax.ShapeDtypeStruct((n,) + tuple(x.shape[1:]), x.dtype,
                                    sharding=one_chip)

    arrays = {k: spec(v, n_blocks) for k, v in dec.arrays.items()}
    arrays["words"] = spec(dec.arrays["words"], n_blocks * per_block)
    meta = dec._meta(N_SEL)
    meta = (meta[0], n_blocks) + meta[2:]
    compiled = _decode_sel_jit.lower(
        arrays, jax.ShapeDtypeStruct((N_SEL,), jnp.int32, sharding=one_chip),
        da_meta=meta, backend=dec.backend).compile()
    used = _fits(compiled)
    assert used >= N_SEL * block_size          # at least the decoded rows


def test_fnv_verify_compiles_for_v5e(one_chip, no_persistent_cache):
    """The on-device FNV-1a-64 digest over 64 decoded 64 KiB rows."""
    compiled = _fnv_rows_jit.lower(
        jax.ShapeDtypeStruct((N_SEL, 65536), jnp.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((N_SEL,), jnp.int32, sharding=one_chip),
    ).compile()
    _fits(compiled)
    assert "u32" in compiled.as_text()
