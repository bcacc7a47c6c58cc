"""The in-memory edge-centric structure of Algorithm 1 / Figs. 8-9.

`EdgeTable` is the device-resident, fixed-capacity analogue of the
paper's multithreaded edge table: a deduplicated edge list with a
`count` property per edge (duplicate handling of Alg. 1 line 20), the
indexed node list, and the table-level metadata the controller reads —
diversity ratio, density, velocity (§III-A parameters).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compression as C
from repro.core.transform import RawEdgeBatch


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EdgeTable:
    """Fixed-capacity deduplicated edge table + node index (device)."""

    # edges
    src: jax.Array  # (cap,) key-dtype
    dst: jax.Array
    etype: jax.Array  # (cap,) int32
    count: jax.Array  # (cap,) int32   duplicate-edge multiplicity
    edge_valid: jax.Array  # (cap,) bool
    # node index — (2*cap,) so every endpoint of a valid edge is
    # present (cap edges have up to 2*cap distinct endpoints; the seed
    # truncated to cap, silently dropping node instructions)
    node_ids: jax.Array  # (2*cap,) sorted unique keys, sentinel tail
    node_valid: jax.Array  # (2*cap,) bool
    # per-edge endpoint positions in `node_ids` (the dedup index): the
    # store reuses the node-upsert slots through these instead of
    # re-probing the hash table for degree updates
    src_node_idx: jax.Array  # (cap,) int32
    dst_node_idx: jax.Array  # (cap,) int32
    # metadata
    n_edges: jax.Array  # scalar int32 (unique)
    n_nodes: jax.Array  # scalar int32 (unique)
    n_raw: jax.Array  # scalar int32 (pre-compression edge instructions)

    def tree_flatten(self):
        # NOT dataclasses.astuple: it deep-copies every leaf and
        # rebuilds tuple-subclass leaves (PartitionSpec) as plain
        # tuples — return the fields themselves
        return tuple(getattr(self, f.name)
                     for f in dataclasses.fields(self)), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    # ---- table-level metadata (PerfMon inputs, Alg. 2 lines 17-19) ----
    def density(self) -> jax.Array:
        v = jnp.maximum(self.n_nodes.astype(jnp.float32), 2.0)
        return 2.0 * self.n_edges.astype(jnp.float32) / (v * (v - 1.0))

    def size(self) -> jax.Array:
        """PerfMon `e = edgeTable.size() + nodeIndex.size()`."""
        return self.n_edges + self.n_nodes

    def compression_ratio(self) -> jax.Array:
        return C.compression_ratio(self.n_nodes, self.n_edges, self.n_raw)


@jax.jit
def build_edge_table(src, dst, etype, valid) -> EdgeTable:
    """Model transformation output -> compressed edge table (Alg. 1)."""
    cap = src.shape[0]
    ecomp, _ = C.compress_edges(src, dst, etype, valid)
    ncomp = C.unique_nodes(src, dst, valid)
    # gather representative (src,dst,etype) of each unique edge
    idx = ecomp.index
    esrc = jnp.where(ecomp.valid, src[idx], 0)
    edst = jnp.where(ecomp.valid, dst[idx], 0)
    # endpoint -> node-index position: `node_ids` is sorted unique with
    # a sentinel tail, so the position is one binary search; every
    # valid endpoint is guaranteed present (index is 2*cap wide)
    nidx = lambda k: jnp.clip(
        jnp.searchsorted(ncomp.keys, k).astype(jnp.int32), 0, 2 * cap - 1)
    return EdgeTable(
        src=esrc,
        dst=edst,
        etype=jnp.where(ecomp.valid, etype[idx], 0),
        count=ecomp.counts,
        edge_valid=ecomp.valid,
        node_ids=ncomp.keys,
        node_valid=ncomp.valid,
        src_node_idx=nidx(esrc),
        dst_node_idx=nidx(edst),
        n_edges=ecomp.n_unique,
        n_nodes=ncomp.n_unique,
        n_raw=ecomp.n_input,
    )


def from_raw_batch(raw: RawEdgeBatch, capacity: int) -> EdgeTable:
    """Host RawEdgeBatch -> padded device arrays -> EdgeTable.

    Keeps the first `capacity` raw edges and pads on the host, so the
    device sees one shape per capacity whatever the raw edge count."""
    kd = C.key_dtype()
    n = min(raw.n_edges, capacity)

    def padded(a, dtype):
        out = np.zeros(capacity, a.dtype)
        out[:n] = a[:n]
        return jnp.asarray(out, dtype)

    return build_edge_table(padded(raw.src, kd), padded(raw.dst, kd),
                            padded(raw.etype, jnp.int32),
                            jnp.asarray(np.arange(capacity) < n))
