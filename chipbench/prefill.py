"""The store as the deployment holds it when the window opens, and the
store read back after it.

`fill` commits the stream's past through the program's own commit,
`ingest_step`, in tables of `chunk_edges` edges built on the device from
the seed.  Chunk `c` holds `n` nodes of its own, each id a bijective mix
of a distinct counter, so no two past nodes share an id, and edge `j`
joins node `j mod n` to node `(j mod n) + 1 + (j div n)`, modulo `n`:
every edge is distinct and joins two of the chunk's nodes.  So what the
past adds is known in total without a reference over it: `edges` edges
with count 1, `nodes` nodes with count 1, and degrees summing to twice
the edges.

`select` reads back what the window wrote.  A past node is told by its
id, whose mix it undoes to the counter, so every other node in the
store, and every edge whose source is such a node, is the window's.
They are found on the device, and only they leave it."""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

MASK = (1 << 64) - 1
# splitmix64's finaliser and murmur3's fmix32, with the inverses of
# their multipliers modulo 2^64 and 2^32
MIX64 = (0x9E3779B97F4A7C15, 0xBF58476D1CE4E5B9, 0x94D049BB133111EB)
UNMUL64 = (0x96DE1B173F119089, 0x319642B2D24D8EC3)
MIX32 = (0x85EBCA6B, 0xC2B2AE35)
UNMUL32 = (0xA5CB9243, 0x7ED1B41D)


@dataclasses.dataclass
class Past:
    """What `fill` committed."""

    base: int          # counter of the first past node
    nodes: int
    edges: int
    dropped: int       # inserts the program's commits reported dropped

    def totals(self) -> Dict[str, int]:
        return {"nodes": self.nodes, "edges": self.edges,
                "edge_count_sum": self.edges, "node_count_sum": self.nodes,
                "degree_sum": 2 * self.edges}


def plan(cfg: Dict) -> Tuple[int, int, int]:
    """(chunks, edges per chunk, nodes per chunk) for the configuration's
    `prefill`; the totals are rounded to whole chunks."""
    p = cfg.get("prefill")
    if not p:
        return 0, 0, 0
    pe = int(p["chunk_edges"])
    chunks = max(1, round(int(p["edges"]) / pe))
    pn = round(pe * int(p["nodes"]) / int(p["edges"]))
    if not 4 <= pn <= pe:
        raise ValueError("prefill: need 4 <= nodes per chunk <= edges per chunk")
    return chunks, pe, pn


def _mix(x):
    kd = x.dtype
    if kd == jnp.uint64:
        x = x + kd.type(MIX64[0])
        x = (x ^ (x >> 30)) * kd.type(MIX64[1])
        x = (x ^ (x >> 27)) * kd.type(MIX64[2])
        return x ^ (x >> 31)
    x = (x ^ (x >> 16)) * kd.type(MIX32[0])
    x = (x ^ (x >> 13)) * kd.type(MIX32[1])
    return x ^ (x >> 16)


def _unmix(x):
    kd = x.dtype
    if kd == jnp.uint64:
        x = x ^ (x >> 31) ^ (x >> 62)
        x = x * kd.type(UNMUL64[1])
        x = x ^ (x >> 27) ^ (x >> 54)
        x = x * kd.type(UNMUL64[0])
        x = x ^ (x >> 30) ^ (x >> 60)
        return x - kd.type(MIX64[0])
    x = x ^ (x >> 16)
    x = x * kd.type(UNMUL32[1])
    x = x ^ (x >> 13) ^ (x >> 26)
    x = x * kd.type(UNMUL32[0])
    return x ^ (x >> 16)


@functools.partial(jax.jit, static_argnames=("pe", "pn"), donate_argnums=(0,))
def _commit_chunk(store, base, c, *, pe: int, pn: int):
    """One chunk of the past through `ingest_step`; returns the store and
    the commit's dropped inserts.  The node index holds the chunk's `pn`
    nodes and no empty lanes: `ingest_step` sweeps every lane of it, and
    the program's own tables, twice as wide as their edges, would make
    the fill sweep several times the lanes it needs."""
    from repro.core.edge_table import EdgeTable
    from repro.graphstore.store import ingest_step

    kd = store.node_keys.dtype
    node_ids = _mix(base + (c * pn + jnp.arange(pn, dtype=jnp.int32)).astype(kd))
    j = jnp.arange(pe, dtype=jnp.int32)
    s = j % pn
    d = (s + 1 + j // pn) % pn
    et = EdgeTable(
        src=node_ids[s], dst=node_ids[d],
        etype=1 + (j // pn) % 4, count=jnp.ones(pe, jnp.int32),
        edge_valid=jnp.ones(pe, bool), node_ids=node_ids,
        node_valid=jnp.ones(pn, bool), src_node_idx=s, dst_node_idx=d,
        n_edges=jnp.int32(pe), n_nodes=jnp.int32(pn), n_raw=jnp.int32(pe))
    store, stats = ingest_step(store, et)
    return store, stats["dropped_inserts"]


def _key_mask(kd) -> int:
    return MASK if kd == jnp.uint64 else 0xFFFFFFFF


def fill(store, cfg: Dict, seed: int):
    """The store with the configuration's past committed, and the `Past`.

    An id that the mix sends to 0, the empty-slot mark, would be lost,
    so the base is moved past the one counter that mixes to 0."""
    chunks, pe, pn = plan(cfg)
    kd = store.node_keys.dtype
    mask = _key_mask(kd)
    base = _base(seed) & mask
    zero = (-MIX64[0]) & mask if kd == jnp.uint64 else 0
    if (zero - base) & mask < chunks * pn:
        base = (zero + 1) & mask
    dropped = []
    for c in range(chunks):
        store, dr = _commit_chunk(store, kd.type(base), jnp.int32(c),
                                  pe=pe, pn=pn)
        dropped.append(dr)
    jax.block_until_ready(store.n_edges)
    return store, Past(base=base, nodes=chunks * pn, edges=chunks * pe,
                       dropped=int(sum(int(d) for d in jax.device_get(dropped))))


def _base(seed: int) -> int:
    """Counter of the first past node, from the seed (its own stream, so
    that it shares nothing with the traffic's generator)."""
    x = (seed * 0xD1B54A32D192ED03 + 0x8CB92BA72F3D8DD7) & MASK
    x ^= x >> 33
    return (x * 0xFF51AFD7ED558CCD) & MASK


@jax.jit
def _masks(store, base, n_past):
    """Masks of the window's nodes and edges, and the store's totals."""
    def past(k):
        return (_unmix(k) - base) < n_past

    e_used = store.edge_keys != 0
    n_used = store.node_keys != 0
    nmask = n_used & ~past(store.node_keys)
    emask = e_used & ~past(store.edge_src)
    wide = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    totals = {
        "nodes": jnp.sum(n_used, dtype=wide),
        "edges": jnp.sum(e_used, dtype=wide),
        "edge_count_sum": jnp.sum(store.edge_count, dtype=wide),
        "node_count_sum": jnp.sum(store.node_count, dtype=wide),
        "degree_sum": jnp.sum(store.node_degree, dtype=wide),
    }
    return nmask, emask, totals


@jax.jit
def _rows(store, ni, ei):
    return (store.node_keys[ni], store.node_degree[ni], store.node_count[ni],
            store.edge_src[ei], store.edge_dst[ei], store.edge_type[ei],
            store.edge_count[ei])


def _padded(idx: np.ndarray) -> np.ndarray:
    """Indices padded to a power of two, so that few shapes compile."""
    out = np.zeros(1 << max(10, (len(idx) - 1).bit_length()), np.int32)
    out[:len(idx)] = idx
    return out


def select(store, past: Past):
    """(edges, degree, count) dicts of everything in the store that is not
    the past, and the store's totals."""
    kd = store.node_keys.dtype
    nmask, emask, totals = jax.device_get(_masks(
        store, kd.type(past.base), kd.type(past.nodes)))
    ni, ei = np.flatnonzero(nmask), np.flatnonzero(emask)
    del nmask, emask
    rows = jax.device_get(_rows(store, _padded(ni), _padded(ei)))
    nk, deg, ncnt, src, dst, ety, cnt = (np.asarray(r) for r in rows)
    kn, ke = len(ni), len(ei)
    edges = dict(zip(zip(src[:ke].tolist(), dst[:ke].tolist(),
                         ety[:ke].tolist()), cnt[:ke].tolist()))
    degree = dict(zip(nk[:kn].tolist(), deg[:kn].tolist()))
    count = dict(zip(nk[:kn].tolist(), ncnt[:kn].tolist()))
    return (edges, degree, count), {k: int(v) for k, v in totals.items()}
