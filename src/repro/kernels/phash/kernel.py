"""Batched partition hash (HopsFS ADP hot path) — Pallas TPU kernel.

The metadata plane hashes billions of (parent_id | inode_id) keys to
partition ids (paper §4.2: inodes partitioned by parent id, file-related
rows by inode id). At exabyte scale this runs over block-report streams and
bulk-import manifests — a pure integer-VPU workload:

    h  = key * 0x9E3779B1 (mod 2^32);  h ^= h >> 16;  partition = h % P

which matches ``repro.core.store._hash_key`` exactly, so the Python
metadata plane and the TPU data pipeline agree on placement.

Grid: 1-D over key blocks; BlockSpec moves [block_n] int32 tiles.
``interpret`` is chosen by the caller from :mod:`repro.kernels.mode`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

GOLDEN = 0x9E3779B1
# second mix constant for the fused chain signature (murmur3 fmix)
GOLDEN2 = 0x85EBCA6B


def _phash_kernel(keys_ref, out_ref, *, n_partitions: int):
    k = keys_ref[...].astype(jnp.uint32)
    h = (k * jnp.uint32(GOLDEN)).astype(jnp.uint32)
    h = h ^ (h >> jnp.uint32(16))
    out_ref[...] = (h % jnp.uint32(n_partitions)).astype(jnp.int32)


def phash(keys: jax.Array, *, n_partitions: int = 64, block_n: int = 1024,
          interpret: bool) -> jax.Array:
    """keys [N] int32/uint32 -> partition ids [N] int32."""
    (N,) = keys.shape
    bn = min(block_n, N)
    kernel = functools.partial(_phash_kernel, n_partitions=n_partitions)
    return pl.pallas_call(
        kernel,
        grid=(N // bn,),
        in_specs=[pl.BlockSpec((bn,), lambda i: (i,))],
        out_specs=pl.BlockSpec((bn,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((N,), jnp.int32),
        interpret=interpret,
    )(keys)


def _phash_chain_kernel(parents_ref, names_ref, hints_ref, depths_ref,
                        comp_ref, hint_ref, sig_ref, *,
                        n_partitions: int, depth: int):
    """Fused chain hash: per-component partition ids, per-path hint (leaf)
    partition ids, and a per-path chain signature, in one pass.

    ``parents[d, n]`` is the parent inode id of path n's d-th component and
    ``names[d, n]`` a 32-bit hash of its name — i.e. the composite PK
    (parent_id, name) the hint cache resolves (§5.1). Component partitions
    use the SAME mix as the scalar store hash (inodes are partitioned by
    parent_id, §4.2), so client-side routing agrees with ``MetadataStore``
    placement exactly; the signature folds every (parent, name) pair into
    a constant-time path-equality probe for chain-level consumers.

    Chains are laid out depth-major ([D, N], paths along lanes) so each
    step of the signature fold reads one lane-dense row; per-path vectors
    are [1, N] rows for the same reason."""
    par = parents_ref[...].astype(jnp.uint32)          # [depth, bn]
    nam = names_ref[...].astype(jnp.uint32)            # [depth, bn]
    h = (par * jnp.uint32(GOLDEN)).astype(jnp.uint32)
    h = h ^ (h >> jnp.uint32(16))
    comp_ref[...] = (h % jnp.uint32(n_partitions)).astype(jnp.int32)
    hv = (hints_ref[...].astype(jnp.uint32)
          * jnp.uint32(GOLDEN)).astype(jnp.uint32)     # [1, bn]
    hv = hv ^ (hv >> jnp.uint32(16))
    hint_ref[...] = (hv % jnp.uint32(n_partitions)).astype(jnp.int32)
    d = depths_ref[...]                                # [1, bn] int32
    sig = jnp.zeros(d.shape, jnp.uint32)
    for k in range(depth):       # static unroll over the (small) max depth
        step = ((sig ^ h[k:k + 1] ^ nam[k:k + 1])
                * jnp.uint32(GOLDEN2)).astype(jnp.uint32)
        step = step ^ (step >> jnp.uint32(15))
        sig = jnp.where(k < d, step, sig)
    sig_ref[...] = sig


def phash_chain(parents: jax.Array, names: jax.Array, hints: jax.Array,
                depths: jax.Array, *, n_partitions: int = 64,
                block_n: int = 1024, interpret: bool):
    """parents/names [D, N] uint32, hints [1, N] uint32, depths [1, N]
    int32 -> (comp_parts [D, N] int32, hint_parts [1, N] int32,
    sigs [1, N] uint32)."""
    D, N = parents.shape
    bn = min(block_n, N)
    kernel = functools.partial(_phash_chain_kernel,
                               n_partitions=n_partitions, depth=D)
    chain = pl.BlockSpec((D, bn), lambda i: (0, i))
    row = pl.BlockSpec((1, bn), lambda i: (0, i))
    return pl.pallas_call(
        kernel,
        grid=(N // bn,),
        in_specs=[chain, chain, row, row],
        out_specs=[chain, row, row],
        out_shape=[jax.ShapeDtypeStruct((D, N), jnp.int32),
                   jax.ShapeDtypeStruct((1, N), jnp.int32),
                   jax.ShapeDtypeStruct((1, N), jnp.uint32)],
        interpret=interpret,
    )(parents, names, hints, depths)
