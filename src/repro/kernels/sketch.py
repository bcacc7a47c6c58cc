"""Pallas TPU kernel: batched scatter-update of the graph sketch.

The ingestion-time sketch (repro.query.sketch, GSS/TCM-style) absorbs
one compressed edge table per update: every unique edge adds its
`count` into D hashed cells of the (D, W, W) edge-weight matrix sketch
and into the per-depth out/in degree counter rows.  That triple
scatter-add is the sketch's hot path — one kernel launch per commit,
all operands resident in VMEM (D*W*W ints: 1 MB at the default
D=4, W=256).

Row/col hash coordinates are precomputed outside (cheap VPU work, and
the host-side oracle shares them); the kernel owns the memory-bound
scatter.  Integer scatter-add is order-independent, so the kernel is
bit-exact against its jnp oracle (`scatter_add` run outside pallas_call,
which is `repro.kernels.ops.sketch_scatter`) by construction — tests
assert it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def scatter_add(edge_w, out_deg, in_deg, r, c, cnt):
    """The pure scatter-add body, shared verbatim by the Pallas kernel
    and the jnp oracle (`repro.kernels.ops.sketch_scatter`) so the two
    can never drift."""
    W = edge_w.shape[1]
    depth = jax.lax.broadcasted_iota(jnp.int32, r.shape, 0)
    cnt_b = jnp.broadcast_to(cnt[None, :], r.shape)
    flat_e = (depth * (W * W) + r * W + c).reshape(-1)
    ew = edge_w.reshape(-1).at[flat_e].add(cnt_b.reshape(-1)).reshape(edge_w.shape)
    flat_o = (depth * W + r).reshape(-1)
    od = out_deg.reshape(-1).at[flat_o].add(cnt_b.reshape(-1)).reshape(out_deg.shape)
    flat_i = (depth * W + c).reshape(-1)
    idg = in_deg.reshape(-1).at[flat_i].add(cnt_b.reshape(-1)).reshape(in_deg.shape)
    return ew, od, idg


def _scatter_kernel(ew_ref, od_ref, id_ref, r_ref, c_ref, cnt_ref,
                    ew_out, od_out, id_out):
    # r/c: (D, n) int32 row/col hashes; cnt: (n,) int32, 0 for invalid
    ew, od, idg = scatter_add(ew_ref[...], od_ref[...], id_ref[...],
                              r_ref[...], c_ref[...], cnt_ref[...])
    ew_out[...] = ew
    od_out[...] = od
    id_out[...] = idg


@functools.partial(jax.jit, static_argnames=("interpret",))
def sketch_scatter(edge_w: jax.Array, out_deg: jax.Array, in_deg: jax.Array,
                   r: jax.Array, c: jax.Array, cnt: jax.Array,
                   interpret: bool = False):
    """One sketch update: (edge_w', out_deg', in_deg').

    edge_w (D, W, W) int32; out_deg/in_deg (D, W) int32;
    r/c (D, n) int32 hash coordinates; cnt (n,) int32 edge counts
    (invalid slots must carry 0)."""
    D, W, _ = edge_w.shape
    n = cnt.shape[0]
    return pl.pallas_call(
        _scatter_kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((D, W, W), lambda i: (0, 0, 0)),
            pl.BlockSpec((D, W), lambda i: (0, 0)),
            pl.BlockSpec((D, W), lambda i: (0, 0)),
            pl.BlockSpec((D, n), lambda i: (0, 0)),
            pl.BlockSpec((D, n), lambda i: (0, 0)),
            pl.BlockSpec((n,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((D, W, W), lambda i: (0, 0, 0)),
            pl.BlockSpec((D, W), lambda i: (0, 0)),
            pl.BlockSpec((D, W), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((D, W, W), jnp.int32),
            jax.ShapeDtypeStruct((D, W), jnp.int32),
            jax.ShapeDtypeStruct((D, W), jnp.int32),
        ],
        interpret=interpret,
    )(edge_w, out_deg, in_deg, r, c, cnt)
