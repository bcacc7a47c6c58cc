"""Pallas TPU kernel: bitonic sort + run-head marking for edge dedup.

The ingestion hot spot (Algorithm 1's INSERTEDGE dedup) adapted to the
TPU: instead of the paper's serial hash map, keys are sorted in VMEM by
a bitonic network (log^2 n compare-exchange stages, pure VPU min/max on
(n/2j, 2, j)-reshaped vectors — no data-dependent control flow), then
run heads are marked by a shifted comparison.  Segment counting runs in
XLA afterwards (`dedup_sorted_counts` below) where
segment-sum is already optimal.

VMEM budget: one uint32 key vector + one index vector; n <= 65536 keys
per block (512 KiB) — far below the ~16 MiB VMEM of a v5e core.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _bitonic_stage(x: jax.Array, idx: jax.Array, k: int, j: int):
    """One compare-exchange stage on (x, idx) (keys + payload indices)."""
    n = x.shape[0]
    xr = x.reshape(n // (2 * j), 2, j)
    ir = idx.reshape(n // (2 * j), 2, j)
    a, b = xr[:, 0, :], xr[:, 1, :]
    ia, ib = ir[:, 0, :], ir[:, 1, :]
    # ascending iff bit k of the element's position is 0
    pos = jax.lax.broadcasted_iota(jnp.int32, (n // (2 * j), j), 0) * (2 * j) + \
        jax.lax.broadcasted_iota(jnp.int32, (n // (2 * j), j), 1)
    asc = (pos & k) == 0
    swap = jnp.where(asc, a > b, a < b)
    na = jnp.where(swap, b, a)
    nb = jnp.where(swap, a, b)
    nia = jnp.where(swap, ib, ia)
    nib = jnp.where(swap, ia, ib)
    x = jnp.stack([na, nb], axis=1).reshape(n)
    idx = jnp.stack([nia, nib], axis=1).reshape(n)
    return x, idx


def _dedup_kernel(keys_ref, sorted_ref, order_ref, head_ref, *, n: int):
    x = keys_ref[...]
    idx = jax.lax.broadcasted_iota(jnp.int32, (n,), 0)
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            x, idx = _bitonic_stage(x, idx, k, j)
            j //= 2
        k *= 2
    sorted_ref[...] = x
    order_ref[...] = idx
    # run heads: first occurrence of each key value
    prev = jnp.concatenate([x[:1] ^ jnp.uint32(0xFFFFFFFF), x[:-1]])
    head_ref[...] = (x != prev).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sort_dedup(keys: jax.Array, interpret: bool = False):
    """keys: (n,) uint32, n a power of two.
    Returns (sorted_keys, order, head_flags)."""
    n = keys.shape[0]
    assert n & (n - 1) == 0, f"n must be a power of two, got {n}"
    kern = functools.partial(_dedup_kernel, n=n)
    return pl.pallas_call(
        kern,
        grid=(1,),
        in_specs=[pl.BlockSpec((n,), lambda i: (0,))],
        out_specs=[
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.uint32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
        ],
        interpret=interpret,
    )(keys)


def dedup_sorted_counts(sorted_keys: jax.Array, head: jax.Array):
    """Per-run counts from the kernel's (sorted, head) output."""
    n = sorted_keys.shape[0]
    run = jnp.cumsum(head) - 1
    counts = jax.ops.segment_sum(jnp.ones((n,), jnp.int32), jnp.clip(run, 0, n - 1), num_segments=n)
    n_unique = head.sum()
    return counts, n_unique
