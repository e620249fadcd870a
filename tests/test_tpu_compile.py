"""Compile the device path for a described TPU v5e, with no chip attached.

Interpret mode never applies the TPU compiler's rules (lane layouts, casts
it cannot lower, the 16 MiB scoped VMEM limit, device memory), so these
tests hand the kernels and the whole decode programs to that compiler at
real item widths: 92-byte records (L=23 words, paper §7) and 8-byte ones
(L=2).  Nothing runs; a compile that passes is not a chip run.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.hashing import DEFAULT_KEY
from repro.core.mapping import kmax
from repro.kernels.iblt_encode import iblt_apply
from repro.kernels.map_indices import map_indices
from repro.kernels.peel import peel_waves, peel_waves_batched, purity_scan

MP = 2048                  # symbols: the tile bucket of a d≈1,000 decode
K = kmax(16384)            # 65 chain slots
KEY = tuple(DEFAULT_KEY)
# temporaries of the batched ref-engine decode at U=8, mp=2048, L=23,
# max_diff=1024 (measured 3.46 GB): the bit unpack of every removed
# item's chain dominates, and it has to leave most of the chip's 16 GB to
# the residuals and the other buckets' programs
BATCHED_TEMP_BUDGET = 4e9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to ask
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _symbols(sharding, L, lead=()):
    return (_shape(sharding, lead + (MP, L), jnp.uint32),
            _shape(sharding, lead + (MP, 2), jnp.uint32),
            _shape(sharding, lead + (MP, 1), jnp.int32))


def test_purity_scan_compiles(one_chip):
    def scan(sums, checks, counts):
        return purity_scan(sums, checks, counts, key=KEY, nbytes=92,
                           interpret=False)
    jax.jit(scan).lower(*_symbols(one_chip, 23)).compile()


@pytest.mark.parametrize("L", [2, 23])
def test_iblt_apply_compiles(one_chip, L):
    def apply(items, idxs, chks, sides, m):
        return iblt_apply(items, idxs, chks, sides, m=m, m_out=MP,
                          interpret=False)
    jax.jit(apply).lower(
        _shape(one_chip, (MP, L), jnp.uint32),
        _shape(one_chip, (MP, K), jnp.int32),
        _shape(one_chip, (MP, 2), jnp.uint32),
        _shape(one_chip, (MP,), jnp.int32),
        _shape(one_chip, (), jnp.int32)).compile()


@pytest.mark.parametrize("L", [2, 23])
def test_map_indices_compiles(one_chip, L):
    def chains(items, m):
        return map_indices(items, K=K, m=m, nbytes=4 * L, key=KEY,
                           interpret=False)
    jax.jit(chains).lower(_shape(one_chip, (MP, L), jnp.uint32),
                          _shape(one_chip, (), jnp.int32)).compile()


def test_pallas_peel_program_compiles(one_chip):
    """The lone-session decode: the wave loop over the three kernels, as
    ``decode_device`` stages it off the interpreter."""
    def decode(sums, checks, counts, m):
        return peel_waves(sums, checks, counts, m=m, nbytes=92, key=KEY,
                          max_diff=1024, K=kmax(MP), kernel="pallas",
                          interpret=False, use_while_loop=True)
    jax.jit(decode).lower(*_symbols(one_chip, 23),
                          _shape(one_chip, (), jnp.int32)).compile()


def test_batched_peel_program_fits(one_chip):
    """The engine's batched decode of 8 units in one bucket."""
    def decode(sums, checks, counts, m):
        return peel_waves_batched(sums, checks, counts, m=m, nbytes=92,
                                  key=KEY, max_diff=1024, K=kmax(MP),
                                  use_while_loop=True)
    compiled = jax.jit(decode).lower(
        *_symbols(one_chip, 23, lead=(8,)),
        _shape(one_chip, (8,), jnp.int32)).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < BATCHED_TEMP_BUDGET, temp
