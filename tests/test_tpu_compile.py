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
from repro.kernels.iblt_dense import iblt_apply_dense
from repro.kernels.iblt_encode import iblt_apply
from repro.kernels.map_indices import map_indices
from repro.kernels import ops
from repro.kernels.peel import PeelState, peel_waves_batched

MP = 2048                  # symbols: the tile bucket of a d≈1,000 decode
K = kmax(16384)            # 65 chain slots
KEY = tuple(DEFAULT_KEY)
# temporaries of the batched decode at U=8, mp=2048, L=23, max_diff=1024
# (measured 25.6 MB with the dense chain removal; the bit unpack of every
# removed item's K chain slots it replaced took 3.46 GB, and would fail
# this budget)
BATCHED_TEMP_BUDGET = 256e6
# temporaries of the batched decode of 128-KiB rows (EIP-4844 blobs,
# L=32,768 words) at U=8, mp=256, max_diff=256: the state, the recovered
# rows and a wave's candidate rows are 256 MiB each, and the engine keeps
# two buckets in flight (measured 808.6 MB with the tiled chain removal;
# the untiled one needed more than the chip's 16 GB)
WIDE_TEMP_BUDGET = 2 * 2**30


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
def test_iblt_apply_dense_compiles(one_chip, L):
    """The batched peel's chain removal over one wave's candidate rows."""
    def apply(items, idxs, chks, sides, m):
        return iblt_apply_dense(items, idxs, chks, sides, m=m, m_out=MP)
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


def _batched_peel_temp(sharding, U):
    """Temporaries of the batched decode of U units of 92-byte rows in the
    bucket mp = 2048 at max_diff = 1024."""
    def decode(sums, checks, counts, m):
        return peel_waves_batched(sums, checks, counts, m=m, nbytes=92,
                                  key=KEY, max_diff=1024, K=kmax(MP),
                                  use_while_loop=True)
    compiled = jax.jit(decode).lower(
        *_symbols(sharding, 23, lead=(U,)),
        _shape(sharding, (U,), jnp.int32)).compile()
    return compiled.memory_analysis().temp_size_in_bytes


def test_batched_peel_program_fits(one_chip):
    """The engine's batched decode of 8 units in one bucket."""
    temp = _batched_peel_temp(one_chip, 8)
    assert temp < BATCHED_TEMP_BUDGET, temp


def test_one_unit_batched_peel_program_fits(one_chip):
    """A lone session's decode: the same program at U = 1, in the single
    cell's largest bucket."""
    temp = _batched_peel_temp(one_chip, 1)
    assert temp < BATCHED_TEMP_BUDGET, temp


def test_batched_peel_program_fits_wide_rows(one_chip):
    """The engine's batched decode of 8 units of 131,072-byte rows, with an
    O(L) hash and chain removal one word tile at a time."""
    mp, L = 256, 32768

    def decode(sums, checks, counts, m):
        return peel_waves_batched(sums, checks, counts, m=m, nbytes=4 * L,
                                  key=KEY, max_diff=256, K=kmax(mp),
                                  use_while_loop=True)
    compiled = jax.jit(decode).lower(
        _shape(one_chip, (8, mp, L), jnp.uint32),
        _shape(one_chip, (8, mp, 2), jnp.uint32),
        _shape(one_chip, (8, mp, 1), jnp.int32),
        _shape(one_chip, (8,), jnp.int32)).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < WIDE_TEMP_BUDGET, temp


@pytest.mark.parametrize("L,mp,D,budget", [
    (23, MP, 1024, BATCHED_TEMP_BUDGET), (32768, 256, 256, WIDE_TEMP_BUDGET)])
def test_staging_programs_fit(one_chip, L, mp, D, budget):
    """The programs around the batched decode of 8 units: the input laid
    out from the last decode's residual (at half the bucket, or the same
    one) and the new rows, and the read-back packed into chunks."""
    U, prev = 8, max(mp // 2, 256)

    def fields(lead, n):
        return (_shape(one_chip, lead + (n, L), jnp.uint32),
                _shape(one_chip, lead + (n, 2), jnp.uint32),
                _shape(one_chip, lead + (n, 1), jnp.int32))
    stage = ops._stage_program.lower(
        (fields((U,), prev),), fields((), U * (mp - prev) or U * mp),
        _shape(one_chip, (U, 5), jnp.int32), mp=mp).compile()
    lead = (U,)
    state = PeelState(
        *fields(lead, mp), _shape(one_chip, (U, D, L), jnp.uint32),
        _shape(one_chip, (U, D, 2), jnp.uint32),
        _shape(one_chip, (U, D, 2), jnp.uint32),
        _shape(one_chip, (U, D), jnp.int32), _shape(one_chip, lead, jnp.int32),
        _shape(one_chip, lead, bool), _shape(one_chip, lead, bool),
        _shape(one_chip, lead, jnp.int32))
    unstage = ops._unstage_program.lower(
        state, _shape(one_chip, lead, bool),
        chunk_bytes=ops.CHUNK_BYTES).compile()
    for compiled in (stage, unstage):
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < budget, temp
