"""Pallas TPU kernel: Mamba2 SSD chunked scan (fwd).

Grid (batch*heads, nchunks) with the chunk axis sequential; the
inter-chunk recurrent state (N x p) lives in VMEM scratch across chunk
iterations.  Within a chunk everything is matmuls (MXU):

  seg   = LT1 @ dA          (cumsum as lower-triangular ones matmul)
  G     = C @ B^T           (Q x Q)
  y_in  = (G * L) @ (dt*x)  intra-chunk
  y_out = C @ (exp(seg) * state)  inter-chunk carry-in
  state = exp(total) * state + B^T @ (w * x)

Oracle: repro.models.mamba2.ssd_chunked (pure jnp).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref, state_ref,
                *, Q: int, N: int, p: int, nc: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)  # (Q, p)
    dt = dt_ref[0].astype(jnp.float32)  # (Q,)
    A = a_ref[0].astype(jnp.float32)  # scalar (1,)
    B = b_ref[0].astype(jnp.float32)  # (Q, N)
    C = c_ref[0].astype(jnp.float32)  # (Q, N)

    dA = dt * A  # (Q,) negative
    lt1 = jnp.tril(jnp.ones((Q, Q), jnp.float32))
    seg = jnp.dot(lt1, dA, preferred_element_type=jnp.float32)  # cumsum
    total = seg[Q - 1]

    # intra-chunk
    li = seg[:, None] - seg[None, :]
    mask = jnp.tril(jnp.ones((Q, Q), jnp.bool_))
    L = jnp.exp(jnp.where(mask, li, -1e30)) * dt[None, :]
    G = jnp.dot(C, B.T, preferred_element_type=jnp.float32)
    y_intra = jnp.dot(G * L, x, preferred_element_type=jnp.float32)

    # inter-chunk carry-in
    h = state_ref[...]  # (N, p)
    y_inter = jnp.exp(seg)[:, None] * jnp.dot(C, h, preferred_element_type=jnp.float32)
    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update
    w = jnp.exp(total - seg) * dt  # (Q,)
    upd = jnp.dot(B.T, w[:, None] * x, preferred_element_type=jnp.float32)  # (N,p)
    state_ref[...] = jnp.exp(total) * h + upd

    @pl.when(ci == nc - 1)
    def _done():
        st_ref[0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,   # (BH, S, p)   per-head inputs, batch*heads flattened
    dt: jax.Array,  # (BH, S)      positive step sizes
    A: jax.Array,   # (BH,)        negative decay rate per (batch,head)
    B: jax.Array,   # (BH, S, N)
    C: jax.Array,   # (BH, S, N)
    chunk: int = 128,
    interpret: bool = False,
):
    BH, S, p = x.shape
    N = B.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q
    kern = functools.partial(_ssd_kernel, Q=Q, N=N, p=p, nc=nc)
    return pl.pallas_call(
        kern,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, Q, p), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Q), lambda b, c: (b, c)),
            pl.BlockSpec((1,), lambda b, c: (b,)),
            pl.BlockSpec((1, Q, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, p), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, N, p), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, p), x.dtype),
            jax.ShapeDtypeStruct((BH, N, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt, A, B, C)
