"""Ingestion-time graph sketch (GSS/TCM-style, fixed shapes).

Summarises the edge stream *as it is ingested* so edge-weight, degree
and top-k queries can be answered live, without touching the store:

  * `edge_w` — a (D, W, W) count-min matrix sketch of the weighted
    adjacency matrix (TCM; GSS is the collision-aware refinement):
    depth d hashes src to a row and dst to a column and accumulates
    the edge's `count` there.  A point query reads the D cells and
    takes the min — an upper bound on the true weight that is exact
    when no collision hit all D cells.
  * `out_deg` / `in_deg` — (D, W) count-min rows of the weighted out-
    and in-degree per node.
  * `hh_keys` / `hh_counts` — a K-slot heavy-hitter table (SpaceSaving
    flavour): every batch's nodes compete by their current sketch
    degree estimate; the K largest survive.  `sketch_heavy_hitters`
    reads top-k from it in O(K).

All shapes are static, the whole state is a pytree, and one update
absorbs one compressed `EdgeTable` — the same batches the store
commits, so sketch totals are directly comparable to store contents.
Updates run the scatter-add through `repro.kernels.ops.sketch_scatter`
(XLA on every backend; the Pallas kernel in `repro.kernels.sketch` is
bit-exact with it but does not compile for TPU, see ROADMAP S7).

Guarantees (tested in tests/test_query.py):
  sketch_degree(u)         >= weighted degree of u in the store
  sketch_edge_weight(s, d) >= sum over etype of store edge counts
with expected overestimate <= e * N / W per depth (classic CMS bound,
N = total absorbed count), i.e. vanishing for W >> distinct keys.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import compression as C
from repro.kernels import ops


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GraphSketch:
    edge_w: jax.Array  # (D, W, W) int32 count-min of edge weights
    out_deg: jax.Array  # (D, W) int32 count-min of weighted out-degree
    in_deg: jax.Array  # (D, W) int32 count-min of weighted in-degree
    hh_keys: jax.Array  # (K,) key-dtype heavy-hitter candidates; 0 = empty
    hh_counts: jax.Array  # (K,) int32 their degree estimates
    n_updates: jax.Array  # scalar int32: total edge count absorbed

    def tree_flatten(self):
        # shallow, like GraphStore: astuple() deep-copies every leaf
        return tuple(getattr(self, f.name)
                     for f in dataclasses.fields(self)), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def depth(self) -> int:
        return self.edge_w.shape[0]

    @property
    def width(self) -> int:
        return self.edge_w.shape[1]


def init_sketch(depth: int = 4, width: int = 256, hh_slots: int = 64,
                key_dtype=None) -> GraphSketch:
    """Fresh sketch.  `width` should be a multiple of 128 (TPU lanes);
    memory is depth * width^2 * 4 bytes (1 MB at the defaults)."""
    kd = key_dtype or C.key_dtype()
    return GraphSketch(
        edge_w=jnp.zeros((depth, width, width), jnp.int32),
        out_deg=jnp.zeros((depth, width), jnp.int32),
        in_deg=jnp.zeros((depth, width), jnp.int32),
        hh_keys=jnp.zeros((hh_slots,), kd),
        hh_counts=jnp.zeros((hh_slots,), jnp.int32),
        n_updates=jnp.zeros((), jnp.int32),
    )


# ---------------------------------------------------------------------------
# hashing: D independent splitmix rounds -> [0, W)
# ---------------------------------------------------------------------------


def _fold32(keys: jax.Array) -> jax.Array:
    if keys.dtype == jnp.uint64:
        return (keys ^ (keys >> jnp.uint64(32))).astype(jnp.uint32)
    return keys.astype(jnp.uint32)


def node_hash(keys: jax.Array, depth: int, width: int) -> jax.Array:
    """(D, n) int32 hash coordinates, one independent row per depth."""
    k32 = _fold32(keys)
    rows = []
    for d in range(depth):
        c1 = jnp.uint32((0x9E3779B9 + 0x7F4A7C15 * d) & 0xFFFFFFFF)
        x = (k32 + c1) * jnp.uint32(0x85EBCA6B)
        x = x ^ (x >> 13)
        x = x * jnp.uint32(0xC2B2AE35)
        x = x ^ (x >> 16)
        rows.append((x % jnp.uint32(width)).astype(jnp.int32))
    return jnp.stack(rows)


# ---------------------------------------------------------------------------
# update
# ---------------------------------------------------------------------------


def _merge_top_k(hh_keys, hh_counts, cand_keys, cand_counts):
    """Merge candidates into the K-slot heavy-hitter table.

    Fixed shapes: dedup by key keeping the max count (CMS estimates
    only grow, so max = freshest), then the top K by count, ties to the
    smaller key.  Key 0 marks empty slots on both sides.

    Only the candidates are sorted, at their own length: the TPU
    compiler takes minutes over one sort of the K-longer concatenation
    at 16k candidates.  The table folds into the sorted candidates by
    binary search, and the top K of the union is the top K of the best
    K of each side."""
    K = hh_keys.shape[0]
    kd = hh_keys.dtype
    sent = C.sentinel_for(kd)
    m = cand_keys.shape[0]
    # candidates: sorted unique keys (sentinel tail), best count each
    masked = jnp.where(cand_keys != 0, cand_keys, sent)
    order = jnp.argsort(masked)
    sk, sc = masked[order], cand_counts.astype(jnp.int32)[order]
    is_valid = sk != sent
    head = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]]) & is_valid
    run = jnp.clip(jnp.cumsum(head.astype(jnp.int32)) - 1, 0, m - 1)
    best = jax.ops.segment_max(jnp.where(is_valid, sc, -1), run, num_segments=m)
    first = jax.ops.segment_min(jnp.where(head, jnp.arange(m), m), run,
                                num_segments=m)
    live = jnp.arange(m) < jnp.sum(head.astype(jnp.int32))
    run_keys = jnp.where(live, sk[jnp.clip(first, 0, m - 1)], sent)
    run_best = jnp.where(live, best, -1)
    # table entries already among the candidates keep the larger count
    hk = jnp.where(hh_keys != 0, hh_keys, sent)
    pos = jnp.clip(jnp.searchsorted(run_keys, hk).astype(jnp.int32), 0, m - 1)
    found = (run_keys[pos] == hk) & (hk != sent)
    run_best = run_best.at[jnp.where(found, pos, m)].max(
        hh_counts.astype(jnp.int32), mode="drop")
    rest = jnp.where(found | (hk == sent), -1, hh_counts.astype(jnp.int32))
    # top_k breaks ties to the lower index, i.e. the smaller run key
    cand_c, cand_i = jax.lax.top_k(run_best, min(K, m))
    neg_c, keys = jax.lax.sort(
        (-jnp.concatenate([cand_c, rest]),
         jnp.concatenate([run_keys[cand_i], hk])), num_keys=2)
    top_c = -neg_c[:K]
    keep = top_c > 0
    return (jnp.where(keep, keys[:K], 0),
            jnp.where(keep, top_c, 0).astype(jnp.int32))


@jax.jit
def sketch_update(sketch: GraphSketch, et) -> GraphSketch:
    """Absorb one compressed `EdgeTable` (the same batch the store
    commits)."""
    D, W = sketch.depth, sketch.width
    cnt = jnp.where(et.edge_valid, et.count, 0).astype(jnp.int32)
    r = node_hash(et.src, D, W)
    c = node_hash(et.dst, D, W)
    ew, od, idg = ops.sketch_scatter(
        sketch.edge_w, sketch.out_deg, sketch.in_deg, r, c, cnt)

    # heavy hitters: this batch's (deduplicated) nodes compete by
    # their post-update CMS degree estimate
    nh = node_hash(et.node_ids, D, W)
    drow = jnp.arange(D)[:, None]
    est = jnp.min(od[drow, nh] + idg[drow, nh], axis=0)
    cand_keys = jnp.where(et.node_valid, et.node_ids, 0)
    cand_cnt = jnp.where(et.node_valid, est, -1)
    hh_keys, hh_counts = _merge_top_k(sketch.hh_keys, sketch.hh_counts,
                                      cand_keys, cand_cnt)
    return GraphSketch(
        edge_w=ew, out_deg=od, in_deg=idg,
        hh_keys=hh_keys, hh_counts=hh_counts,
        n_updates=sketch.n_updates + jnp.sum(cnt),
    )


# ---------------------------------------------------------------------------
# queries
# ---------------------------------------------------------------------------


@jax.jit
def sketch_edge_weight(sketch: GraphSketch, src: jax.Array,
                       dst: jax.Array) -> jax.Array:
    """Upper bound on total edge weight src->dst (summed over etype)."""
    D, W = sketch.depth, sketch.width
    r = node_hash(src, D, W)
    c = node_hash(dst, D, W)
    return jnp.min(sketch.edge_w[jnp.arange(D)[:, None], r, c], axis=0)


@partial(jax.jit, static_argnames=("mode",))
def sketch_degree(sketch: GraphSketch, keys: jax.Array,
                  mode: str = "total") -> jax.Array:
    """Upper bound on weighted degree ("out", "in" or "total")."""
    D, W = sketch.depth, sketch.width
    h = node_hash(keys, D, W)
    drow = jnp.arange(D)[:, None]
    if mode == "out":
        v = sketch.out_deg[drow, h]
    elif mode == "in":
        v = sketch.in_deg[drow, h]
    else:
        v = sketch.out_deg[drow, h] + sketch.in_deg[drow, h]
    return jnp.min(v, axis=0)


@partial(jax.jit, static_argnames=("k",))
def sketch_heavy_hitters(sketch: GraphSketch, k: int = 10
                         ) -> Tuple[jax.Array, jax.Array]:
    """Top-k node keys by estimated degree from the HH table."""
    score = jnp.where(sketch.hh_keys != 0, sketch.hh_counts, -1)
    v, i = jax.lax.top_k(score, k)
    return (jnp.where(v > 0, sketch.hh_keys[i], 0),
            jnp.maximum(v, 0))


def sketch_error_bound(sketch: GraphSketch) -> float:
    """Classic CMS additive-error bound: with probability >= 1 - e^-D,
    a point query overestimates by at most e * N / W (N = total edge
    count absorbed so far)."""
    return math.e * float(sketch.n_updates) / float(sketch.width)
