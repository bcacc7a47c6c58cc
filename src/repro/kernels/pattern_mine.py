"""Pallas TPU kernel: per-batch frequent-substructure mining (GraphZip).

GraphZip (Packer & Holder, arXiv:1703.08614) grows a dictionary of
frequent substructures and emits compact references instead of raw
edges.  The serial algorithm extends candidate subgraphs one edge at a
time; here mining is recast as three sorted-vector problems over the
dedup'd batch, so it vectorises on the VPU exactly like the dedup and
upsert kernels:

  star bursts    fan_out[e] = |{f : (src, etype) equal}|  (hub fan-out)
                 fan_in[e]  = |{f : (dst, etype) equal}|  (hub fan-in)
  cascade chains dst[e] appears as a source elsewhere in the batch
                 (retweet-of-retweet relay nodes)
  hot edges      within-batch multiplicity >= hot_min

Each admitted edge carries a *pattern signature* (the hub or relay
identity mixed with a pattern tag) that the dictionary keeps for
lineage.  The classification itself — binary searches over the three
sorted vectors plus flag logic — is the pure body `mine_body`, shared
verbatim by the Pallas kernel and the jnp oracle.  Only the sort
primitive differs (bitonic network in-kernel, `jnp.sort` in the
oracle); both produce the identical sorted *values*, so the outputs
are bit-exact either way and tests assert it.

VMEM budget: six n-vectors resident; n <= 65536 per block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.compression import mix_keys, sentinel_for

# pattern-signature tags (the "pattern class" half of a dictionary key)
TAG_STAR_OUT = 0xA1
TAG_STAR_IN = 0xA2
TAG_CHAIN = 0xA3
TAG_HOT = 0xA4

# admit-flag bits returned per edge
FLAG_STAR_OUT = 1
FLAG_STAR_IN = 2
FLAG_CHAIN = 4
FLAG_HOT = 8


def _bisect(sorted_keys: jax.Array, q: jax.Array, right: bool) -> jax.Array:
    """Vectorised binary search (lower/upper bound) — log2(n) gathers."""
    n = sorted_keys.shape[0]
    steps = max(n.bit_length(), 1)

    def body(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        v = sorted_keys[jnp.clip(mid, 0, n - 1)]
        go = (v <= q) if right else (v < q)
        return jnp.where(go, mid + 1, lo), jnp.where(go, hi, mid)

    lo = jnp.zeros(q.shape, jnp.int32)
    hi = jnp.full(q.shape, n, jnp.int32)
    lo, _ = jax.lax.fori_loop(0, steps, body, (lo, hi))
    return lo


def _tag(ids: jax.Array, etype: jax.Array, tag: int) -> jax.Array:
    """Pattern signature: hub/relay id x etype x pattern-class tag."""
    kd = ids.dtype
    return mix_keys(ids, etype.astype(kd), jnp.full(ids.shape, tag, jnp.int32))


def mine_body(src, dst, etype, count, valid, star_min, hot_min, sort_fn):
    """Classify every edge of a dedup'd batch (pure body, shared by the
    kernel and the oracle; `sort_fn` must sort ascending).

    Returns (fan_out, fan_in, flags, psig): int32 fan counts, an int32
    FLAG_* bitmask (0 = not a pattern member), and the key-dtype
    pattern signature of the strongest matching pattern.
    """
    kd = src.dtype
    sentinel = sentinel_for(kd)
    gs = _tag(src, etype, TAG_STAR_OUT)   # (src, etype) group key
    gd = _tag(dst, etype, TAG_STAR_IN)    # (dst, etype) group key
    sorted_gs = sort_fn(jnp.where(valid, gs, sentinel))
    sorted_gd = sort_fn(jnp.where(valid, gd, sentinel))
    sorted_src = sort_fn(jnp.where(valid, src, sentinel))

    fan_out = _bisect(sorted_gs, gs, True) - _bisect(sorted_gs, gs, False)
    fan_in = _bisect(sorted_gd, gd, True) - _bisect(sorted_gd, gd, False)
    fan_out = jnp.where(valid, fan_out, 0)
    fan_in = jnp.where(valid, fan_in, 0)

    # cascade chain: this edge's head is some other edge's tail
    pos = _bisect(sorted_src, dst, False)
    member = sorted_src[jnp.clip(pos, 0, src.shape[0] - 1)] == dst
    chain = valid & member & (dst != src)

    staro = valid & (fan_out >= star_min)
    stari = valid & (fan_in >= star_min)
    hot = valid & (count >= hot_min)
    flags = (staro * FLAG_STAR_OUT + stari * FLAG_STAR_IN
             + chain * FLAG_CHAIN + hot * FLAG_HOT).astype(jnp.int32)

    # strongest pattern wins the signature: hub fan-out > fan-in >
    # chain relay > hot edge (the edge's own key)
    psig = _tag(src, etype, TAG_HOT)
    psig = jnp.where(chain, _tag(dst, etype, TAG_CHAIN), psig)
    psig = jnp.where(stari, gd, psig)
    psig = jnp.where(staro, gs, psig)
    return fan_out, fan_in, flags, jnp.where(flags != 0, psig, 0)


# ---------------------------------------------------------------- oracle
@jax.jit
def pattern_mine_ref(src, dst, etype, count, valid, star_min, hot_min):
    """jnp oracle, and what the main path runs on every backend
    (see repro.kernels.ops)."""
    return mine_body(src, dst, etype, count, valid,
                     jnp.asarray(star_min, jnp.int32),
                     jnp.asarray(hot_min, jnp.int32), jnp.sort)


# ---------------------------------------------------------------- kernel
def _bitonic_sort(x: jax.Array) -> jax.Array:
    """Key-only bitonic network (edge_dedup's stages minus the payload)."""
    n = x.shape[0]
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            xr = x.reshape(n // (2 * j), 2, j)
            a, b = xr[:, 0, :], xr[:, 1, :]
            pos = jax.lax.broadcasted_iota(
                jnp.int32, (n // (2 * j), j), 0) * (2 * j) + \
                jax.lax.broadcasted_iota(jnp.int32, (n // (2 * j), j), 1)
            asc = (pos & k) == 0
            swap = jnp.where(asc, a > b, a < b)
            na = jnp.where(swap, b, a)
            nb = jnp.where(swap, a, b)
            x = jnp.stack([na, nb], axis=1).reshape(n)
            j //= 2
        k *= 2
    return x


def _mine_kernel(params_ref, src_ref, dst_ref, etype_ref, count_ref,
                 valid_ref, fan_out_ref, fan_in_ref, flags_ref, psig_ref):
    fan_out, fan_in, flags, psig = mine_body(
        src_ref[...], dst_ref[...], etype_ref[...], count_ref[...],
        valid_ref[...] != 0, params_ref[0], params_ref[1], _bitonic_sort)
    fan_out_ref[...] = fan_out
    fan_in_ref[...] = fan_in
    flags_ref[...] = flags
    psig_ref[...] = psig


@functools.partial(jax.jit, static_argnames=("interpret",))
def pattern_mine(src, dst, etype, count, valid, star_min, hot_min,
                 interpret: bool = False):
    """Pattern mining through the Pallas kernel.

    src/dst (n,) key dtype; etype/count (n,) int32; valid (n,) bool;
    star_min/hot_min scalar int32 thresholds.  n must be a power of
    two (batch caps already are).  Returns (fan_out, fan_in, flags,
    psig) as `mine_body`.
    """
    n = src.shape[0]
    assert n & (n - 1) == 0, f"n must be a power of two, got {n}"
    params = jnp.stack([jnp.asarray(star_min, jnp.int32),
                        jnp.asarray(hot_min, jnp.int32)])
    return pl.pallas_call(
        _mine_kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), src.dtype),
        ],
        interpret=interpret,
    )(params, src, dst, etype, count, valid.astype(jnp.int32))
