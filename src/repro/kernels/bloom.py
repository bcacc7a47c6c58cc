"""Pallas TPU kernel: blocked Bloom filter build + probe.

Powers the bucket-diversity ratio rho (§III-A): "proportion of new
nodes in the bucket" = fraction of node keys NOT present in the filter
of previously-seen nodes.  The exact store lookup gives the same signal
at commit time; the Bloom probe gives it *before* commit, which is what
the controller needs to size the buffer ahead of the push.

Layout: the filter is a (W, 1024) uint32 bitmap (1024 VPU lanes per
row; W*1024 words = W*32768 bits).  Each key sets/tests HASHES bits from
independent splitmix rounds.  Scatter-OR is realised as 32 per-bit
scatter-max passes (no data races, static unroll — TPU friendly).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

HASHES = 4
LANES = 1024


def _hash_round(keys: jax.Array, r: int) -> jax.Array:
    c1 = jnp.uint32((0x9E3779B9 + 0x7F4A7C15 * r) & 0xFFFFFFFF)
    c2 = jnp.uint32(0x85EBCA6B)
    x = (keys + c1) * c2
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _bit_coords(keys: jax.Array, r: int, words: int):
    h = _hash_round(keys, r)
    word = ((h >> jnp.uint32(5)) % jnp.uint32(words)).astype(jnp.int32)
    bit = (h % jnp.uint32(32)).astype(jnp.int32)
    return word, bit


def _probe_kernel(keys_ref, bitmap_ref, hit_ref, *, words: int):
    keys = keys_ref[...]
    n = keys.shape[0]
    flat = bitmap_ref[...].reshape(-1)
    hit = jnp.ones((n,), jnp.int32)
    for r in range(HASHES):
        w, b = _bit_coords(keys, r, words)
        vals = flat[w]
        hit = hit & ((vals >> b.astype(jnp.uint32)) & jnp.uint32(1)).astype(jnp.int32)
    hit_ref[...] = hit


def _build_kernel(keys_ref, bitmap_in_ref, bitmap_ref, *, words: int):
    keys = keys_ref[...]
    flat = bitmap_in_ref[...].reshape(-1)
    for r in range(HASHES):
        w, b = _bit_coords(keys, r, words)
        # scatter-OR as 32 collision-free scatter-max passes
        for bit in range(32):
            sel = b == bit
            tgt = jnp.where(sel, w, words)  # out-of-range -> dropped
            upd = jnp.zeros_like(flat).at[tgt].max(
                jnp.uint32(1 << bit), mode="drop"
            )
            flat = flat | upd
    bitmap_ref[...] = flat.reshape(bitmap_ref.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bloom_probe(keys: jax.Array, bitmap: jax.Array, interpret: bool = False):
    """keys (n,) uint32; bitmap (W, LANES) uint32. Returns hit mask (n,)."""
    n = keys.shape[0]
    W = bitmap.shape[0]
    words = W * LANES
    kern = functools.partial(_probe_kernel, words=words)
    return pl.pallas_call(
        kern,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((W, LANES), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((n,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        interpret=interpret,
    )(keys, bitmap)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bloom_build(keys: jax.Array, bitmap: jax.Array, interpret: bool = False):
    """Insert keys; returns the updated bitmap."""
    n = keys.shape[0]
    W = bitmap.shape[0]
    words = W * LANES
    kern = functools.partial(_build_kernel, words=words)
    return pl.pallas_call(
        kern,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((W, LANES), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((W, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((W, LANES), jnp.uint32),
        interpret=interpret,
    )(keys, bitmap)


def bloom_diversity(keys: jax.Array, bitmap: jax.Array, interpret: bool = False):
    """(rho, new_bitmap): fraction of unseen keys + updated filter —
    the pre-commit diversity signal for the buffer controller."""
    hit = bloom_probe(keys, bitmap, interpret=interpret)
    rho = 1.0 - hit.mean(dtype=jnp.float32)
    return rho, bloom_build(keys, bitmap, interpret=interpret)


def init_bitmap(rows: int = 64) -> jax.Array:
    return jnp.zeros((rows, LANES), jnp.uint32)
