"""Pallas TPU kernel: blocked (flash) causal attention, fwd.

Grid (batch*heads, nq, nk) with the kv axis innermost and sequential
("arbitrary"); online-softmax running stats (acc, m, l) live in VMEM
scratch that persists across the nk iterations.  Q/K/V blocks are
MXU-aligned (block_q x head_dim, block_k x head_dim tiles in VMEM).
Supports causal and sliding-window (SWA) masking.

The pure-jnp oracle is the online-softmax recurrence in
`repro.models.layers._sdpa_chunked`, wired up via repro.kernels.ref.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, block_q: int, block_k: int, nk: int, causal: bool,
    window: Optional[int], scale: float,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]  # (block_q, d)
    k = k_ref[0]  # (block_k, d)
    v = v_ref[0]
    s = jnp.dot(
        q.astype(jnp.float32), k.astype(jnp.float32).T,
        preferred_element_type=jnp.float32,
    ) * scale  # (block_q, block_k)

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= qpos - kpos < window
    s = jnp.where(mask, s, -1e30)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v.astype(jnp.float32), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _done():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(
            o_ref.dtype
        )


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (BH, S, d)  flattened batch*heads
    k: jax.Array,  # (BH, S, d)
    v: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
):
    BH, S, d = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0
    nq, nk = S // block_q, S // block_k
    scale = 1.0 / math.sqrt(d)
    kern = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, nk=nk,
        causal=causal, window=window, scale=scale,
    )
    return pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
