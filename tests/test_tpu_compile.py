"""The metadata path's device kernels compile for a TPU v5e at real sizes.

No chip is attached: the TPU compiler compiles for a described ``v5e:2x2``
topology and the program is placed on one of its chips. This catches what
the CPU's interpret mode cannot — Mosaic lowering errors, VMEM limits,
unsupported gathers — before any chip time is spent. Nothing runs, so
nothing here says anything about results or speed.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and pytest workers import every file.

Sizes: a planner window of 1,024 probes over chains of depth 16, a hash
index of 2^22 slots (a namespace of about a million inodes at load <= 0.5
with growth headroom), a BFS wave of 1,024 directories over 2^20 column
slots.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

N_PROBES = 1024
DEPTH = 16
INDEX_SLOTS = 1 << 22
WAVE = 1024
COLUMN_SLOTS = 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _phash(shape):
    from repro.kernels.phash.kernel import phash
    fn = functools.partial(phash, n_partitions=64, interpret=False)
    return fn, [shape((N_PROBES,), jnp.uint32)]


def _phash_chain(shape):
    from repro.kernels.phash.kernel import phash_chain
    fn = functools.partial(phash_chain, n_partitions=64, interpret=False)
    return fn, [shape((DEPTH, N_PROBES), jnp.uint32),
                shape((DEPTH, N_PROBES), jnp.uint32),
                shape((1, N_PROBES), jnp.uint32),
                shape((1, N_PROBES), jnp.int32)]


def _index(shape):
    return [shape((INDEX_SLOTS,), jnp.int32),
            shape((INDEX_SLOTS,), jnp.uint32),
            shape((INDEX_SLOTS,), jnp.int32)]


def _pkval(shape):
    from repro.kernels.pkval.ops import pkval
    return pkval, _index(shape) + [shape((N_PROBES,), jnp.int32),
                                   shape((N_PROBES,), jnp.uint32)]


def _hintchain(shape):
    from repro.kernels.hintchain.ops import hintchain
    return hintchain, (_index(shape) + _index(shape)
                       + [shape((N_PROBES, DEPTH), jnp.uint32),
                          shape((N_PROBES,), jnp.int32)])


def _treeagg(shape):
    from repro.kernels.treeagg.ops import treeagg
    return treeagg, [shape((WAVE,), jnp.int32)] + [
        shape((COLUMN_SLOTS,), jnp.int32) for _ in range(3)]


@pytest.mark.parametrize("build,pallas", [
    (_phash, True), (_phash_chain, True), (_pkval, False),
    (_hintchain, False), (_treeagg, False)],
    ids=["phash", "phash_chain", "pkval", "hintchain", "treeagg"])
def test_kernel_compiles_for_v5e(build, pallas, one_chip, no_compile_cache):
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    fn, args = build(shape)
    compiled = jax.jit(fn).lower(*args).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == pallas
