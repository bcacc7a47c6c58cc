"""Store -> device-resident CSR snapshot (compaction + incremental maintenance).

The open-addressing hash tables of `repro.graphstore` are ideal for
O(1) ingest but hostile to traversal: edges of one node are scattered
across the table.  `build_snapshot` compacts them — entirely on
device, one jit — into a CSR form the query engine can traverse with
gathers and segment ops:

  * nodes sorted by key (invalid slots carry the all-ones sentinel and
    sort last), so key -> compact index is a binary search;
  * edges relabelled to compact indices and sorted lexicographically
    by (src, dst, etype), with `indptr` row offsets (forward CSR) and
    the reverse orientation (`rindptr`, sorted by (dst, src, etype))
    for in-edge traversal;
  * a prefix sum over sorted edge counts, so any contiguous edge range
    (e.g. all etypes of one (src, dst) pair) sums in O(1).

Shapes stay static at the store capacities; validity is carried by
masks, so one compiled snapshot program serves any fill level.

**Incremental maintenance** (ROADMAP item, closed): a full
`build_snapshot` pays O(cap log cap) sorts per call.  `apply_delta`
instead merges ONE commit's `CommitDelta` (repro.graphstore.store)
into an existing snapshot with sort-free rank merges: both the base
CSR and the (small, freshly sorted) delta are lexicographically
sorted, so every element's new position is its old position plus its
rank in the other list — two vectorised binary searches and O(cap)
scatters, no O(cap log cap) recompaction.  The tie order is fully
deterministic (3-key sort), so the incremental snapshot is BIT-EXACT
against a fresh `build_snapshot` — tests assert array equality.
`SnapshotMaintainer` drives it: it buffers pending commit deltas and
falls back to a full rebuild only when the buffer overflows or the
store holds dangling edges (saturated node table) the merge cannot
place.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import compression as C
from repro.graphstore.store import CommitDelta, GraphStore


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GraphSnapshot:
    # nodes, sorted by key; slots >= n_nodes hold the sentinel
    node_key: jax.Array  # (Ncap,) key dtype
    node_count: jax.Array  # (Ncap,) int32
    node_degree: jax.Array  # (Ncap,) int32 (unique-edge endpoints, from store)
    # forward CSR: edges sorted by (src_idx, dst_idx, etype); invalid rows = Ncap
    indptr: jax.Array  # (Ncap+1,) int32
    edge_row: jax.Array  # (Ecap,) int32 compact src index
    edge_col: jax.Array  # (Ecap,) int32 compact dst index
    edge_type: jax.Array  # (Ecap,) int32
    edge_count: jax.Array  # (Ecap,) int32
    edge_prefix: jax.Array  # (Ecap+1,) int32 cumsum of edge_count
    # reverse CSR: same edges sorted by (dst_idx, src_idx, etype)
    rindptr: jax.Array  # (Ncap+1,) int32
    redge_row: jax.Array  # (Ecap,) int32 compact dst index (the row)
    redge_col: jax.Array  # (Ecap,) int32 compact src index
    redge_type: jax.Array  # (Ecap,) int32 (delta merges rank by it)
    # sizes
    n_nodes: jax.Array  # scalar int32
    n_edges: jax.Array  # scalar int32 (unique (src,dst,etype) triples)

    def tree_flatten(self):
        # shallow, like GraphStore: astuple() deep-copies every leaf
        return tuple(getattr(self, f.name)
                     for f in dataclasses.fields(self)), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def node_cap(self) -> int:
        return self.node_key.shape[0]

    @property
    def edge_valid(self) -> jax.Array:
        return self.edge_row < self.node_cap


def _lex_sort3(primary: jax.Array, secondary: jax.Array,
               tertiary: jax.Array) -> jax.Array:
    """Permutation sorting by (primary, secondary, tertiary), stable."""
    o = jnp.argsort(tertiary, stable=True)
    o = o[jnp.argsort(secondary[o], stable=True)]
    return o[jnp.argsort(primary[o], stable=True)]


@jax.jit
def build_snapshot(store: GraphStore) -> GraphSnapshot:
    """Compact the hash-table store into a CSR snapshot (one jit)."""
    kd = store.node_keys.dtype
    sent = C.sentinel_for(kd)
    ncap = store.node_keys.shape[0]

    # ---- nodes: sort by key, invalid last ----
    nvalid = store.node_keys != 0
    masked = jnp.where(nvalid, store.node_keys, sent)
    order = jnp.argsort(masked)
    node_key = masked[order]
    svalid = node_key != sent
    node_count = jnp.where(svalid, store.node_count[order], 0)
    node_degree = jnp.where(svalid, store.node_degree[order], 0)
    n_nodes = jnp.sum(svalid.astype(jnp.int32))

    # ---- edges: relabel endpoints to compact indices ----
    evalid = store.edge_keys != 0

    def to_idx(keys):
        idx = jnp.searchsorted(node_key, keys).astype(jnp.int32)
        ci = jnp.clip(idx, 0, ncap - 1)
        found = node_key[ci] == keys
        return jnp.where(evalid & found, ci, ncap)

    src_idx = to_idx(store.edge_src)
    dst_idx = to_idx(store.edge_dst)
    # an edge is in the snapshot only if BOTH endpoints resolved (a
    # saturated node table can leave dangling endpoints; see ROADMAP)
    dangling = (src_idx == ncap) | (dst_idx == ncap)
    src_idx = jnp.where(dangling, ncap, src_idx)
    dst_idx = jnp.where(dangling, ncap, dst_idx)

    # forward: lexicographic (src, dst, etype); invalid (row = Ncap)
    # sort last.  The etype tiebreak makes the order fully
    # deterministic, which `apply_delta` relies on for exact merges.
    perm = _lex_sort3(src_idx, dst_idx, store.edge_type)
    edge_row = src_idx[perm]
    edge_col = dst_idx[perm]
    live = edge_row < ncap
    edge_type = jnp.where(live, store.edge_type[perm], 0)
    edge_count = jnp.where(live, store.edge_count[perm], 0)
    rows = jnp.arange(ncap + 1, dtype=jnp.int32)
    indptr = jnp.searchsorted(edge_row, rows, side="left").astype(jnp.int32)
    edge_prefix = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(edge_count, dtype=jnp.int32)]
    )

    # reverse: lexicographic (dst, src, etype)
    rperm = _lex_sort3(dst_idx, src_idx, store.edge_type)
    redge_row = dst_idx[rperm]
    rlive = redge_row < ncap
    redge_col = jnp.where(rlive, src_idx[rperm], ncap)
    redge_type = jnp.where(rlive, store.edge_type[rperm], 0)
    rindptr = jnp.searchsorted(redge_row, rows, side="left").astype(jnp.int32)

    return GraphSnapshot(
        node_key=node_key,
        node_count=node_count,
        node_degree=node_degree,
        indptr=indptr,
        edge_row=edge_row,
        edge_col=edge_col,
        edge_type=edge_type,
        edge_count=edge_count,
        edge_prefix=edge_prefix,
        rindptr=rindptr,
        redge_row=redge_row,
        redge_col=redge_col,
        redge_type=redge_type,
        n_nodes=n_nodes,
        n_edges=indptr[-1],
    )


@jax.jit
def node_index(snap: GraphSnapshot, keys: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """Key -> compact index lookup: (found (bool), idx (int32))."""
    ncap = snap.node_cap
    idx = jnp.searchsorted(snap.node_key, keys).astype(jnp.int32)
    ci = jnp.clip(idx, 0, ncap - 1)
    found = (snap.node_key[ci] == keys) & (keys != 0)
    return found, jnp.where(found, ci, -1)


# ---------------------------------------------------------------------------
# Incremental maintenance: merge one CommitDelta without recompacting
# ---------------------------------------------------------------------------


def _searchsorted3(ar, ac, at_, qr, qc, qt):
    """Vectorised 'left' binary search over a lexicographically sorted
    triple (ar, ac, at_) — the rank of each query triple.  Avoids a
    composite key (which overflows int32 at large node capacities)."""
    n = ar.shape[0]
    steps = int(math.ceil(math.log2(max(n, 2)))) + 1
    lo = jnp.zeros(qr.shape, jnp.int32)
    hi = jnp.full(qr.shape, n, jnp.int32)

    def body(_, c):
        lo, hi = c
        mid = (lo + hi) // 2
        m = jnp.clip(mid, 0, n - 1)
        vr, vc, vt = ar[m], ac[m], at_[m]
        lt = (vr < qr) | ((vr == qr) & ((vc < qc) | ((vc == qc) & (vt < qt))))
        open_ = lo < hi
        return (jnp.where(open_ & lt, mid + 1, lo),
                jnp.where(open_ & ~lt, mid, hi))

    lo, _ = jax.lax.fori_loop(0, steps, body, (lo, hi))
    return lo


@jax.jit
def apply_delta(snap: GraphSnapshot, delta: CommitDelta
                ) -> Tuple[GraphSnapshot, jax.Array]:
    """Merge one commit's delta into the CSR without recompaction.

    Returns (snapshot', unplaced) where `unplaced` counts committed
    edges the merge could not place (dangling endpoints or count
    increments to edges absent from the base CSR) — callers must fall
    back to `build_snapshot` when it is nonzero.

    Everything is a rank merge: base and delta are both sorted, so new
    position = own index + rank in the other list.  Cost: O(cap)
    gathers/scatters plus one small sort of the delta — no O(cap log
    cap) recompaction of the full edge set.  Output is bit-exact
    against `build_snapshot` of the post-commit store."""
    kd = snap.node_key.dtype
    sent = C.sentinel_for(kd)
    ncap = snap.node_cap
    ecap = snap.edge_row.shape[0]
    big = jnp.int32(ncap + 1)  # sorts after every live row AND the ncap tail

    # ---- nodes: sorted-insert the new keys ----
    new_keys = jnp.sort(jnp.where(delta.node_new, delta.node_ids, sent))
    live_new = new_keys != sent
    k_new = jnp.sum(live_new.astype(jnp.int32))
    # base entry i shifts right by the number of new keys below it
    shift = jnp.searchsorted(new_keys, snap.node_key, side="left").astype(jnp.int32)
    base_valid = snap.node_key != sent
    nb = snap.node_key.shape[0]
    pos_base = jnp.where(base_valid,
                         jnp.arange(nb, dtype=jnp.int32) + shift, ncap)
    # new key j lands at (rank among base) + j
    rank_new = jnp.searchsorted(snap.node_key, new_keys, side="left").astype(jnp.int32)
    pos_new = jnp.where(live_new,
                        rank_new + jnp.arange(new_keys.shape[0], dtype=jnp.int32),
                        ncap)

    node_key = jnp.full((ncap,), sent, kd)
    node_key = node_key.at[pos_base].set(snap.node_key, mode="drop")
    node_key = node_key.at[pos_new].set(new_keys, mode="drop")
    node_count = jnp.zeros((ncap,), jnp.int32).at[pos_base].set(
        snap.node_count, mode="drop")
    node_degree = jnp.zeros((ncap,), jnp.int32).at[pos_base].set(
        snap.node_degree, mode="drop")

    def find_node(keys):
        p = jnp.clip(jnp.searchsorted(node_key, keys).astype(jnp.int32),
                     0, ncap - 1)
        return p, node_key[p] == keys

    # per-commit property updates: +1 count per committed node, +1
    # degree per endpoint of a new edge (masks prepared by ingest_step)
    pc, _ = find_node(delta.node_ids)
    node_count = node_count.at[jnp.where(delta.node_placed, pc, ncap)].add(
        1, mode="drop")
    ps, sok = find_node(delta.src)
    pd, dok = find_node(delta.dst)
    node_degree = node_degree.at[jnp.where(delta.src_deg, ps, ncap)].add(
        1, mode="drop")
    node_degree = node_degree.at[jnp.where(delta.dst_deg, pd, ncap)].add(
        1, mode="drop")

    # old compact index -> new compact index (monotone, so relabelled
    # base edges KEEP their lexicographic order — pure gather)
    o2n = jnp.concatenate([
        jnp.where(jnp.arange(nb, dtype=jnp.int32) < snap.n_nodes,
                  jnp.arange(nb, dtype=jnp.int32) + shift, ncap),
        jnp.full((1,), ncap, jnp.int32),
    ])

    # ---- delta edges: endpoints -> new compact indices ----
    live_d = delta.edge_new & sok & dok
    drow = jnp.where(live_d, ps, big)
    dcol = jnp.where(live_d, pd, big)
    det = jnp.where(live_d, delta.etype, 0)
    dcnt = jnp.where(live_d, delta.count, 0)

    def merge(base_row, base_col, base_et, base_cnt, delta_a, delta_b):
        """Rank-merge delta edges (sorted by (delta_a, delta_b, etype),
        where `a` is this orientation's row key) into the relabelled
        base orientation.  New position = own index + rank in the
        other (sorted) list — no recompaction."""
        brow = o2n[base_row]
        bcol = o2n[base_col]
        sa, sb, set_, scnt, slive = jax.lax.sort(
            (delta_a, delta_b, det, dcnt, live_d.astype(jnp.int32)),
            num_keys=3)
        rank_d = _searchsorted3(brow, bcol, base_et, sa, sb, set_)
        pos_d = jnp.where(slive != 0,
                          rank_d + jnp.arange(sa.shape[0], dtype=jnp.int32),
                          ecap)
        rank_b = _searchsorted3(sa, sb, set_, brow, bcol, base_et)
        pos_b = jnp.arange(ecap, dtype=jnp.int32) + rank_b
        row = jnp.full((ecap,), ncap, jnp.int32).at[pos_b].set(
            brow, mode="drop").at[pos_d].set(sa, mode="drop")
        col = jnp.full((ecap,), ncap, jnp.int32).at[pos_b].set(
            bcol, mode="drop").at[pos_d].set(sb, mode="drop")
        et = jnp.zeros((ecap,), jnp.int32).at[pos_b].set(
            base_et, mode="drop").at[pos_d].set(set_, mode="drop")
        cnt = None
        if base_cnt is not None:
            cnt = jnp.zeros((ecap,), jnp.int32).at[pos_b].set(
                base_cnt, mode="drop").at[pos_d].set(scnt, mode="drop")
        return row, col, et, cnt

    # forward orientation: sort/merge by (row, col, etype)
    edge_row, edge_col, edge_type, edge_count = merge(
        snap.edge_row, snap.edge_col, snap.edge_type, snap.edge_count,
        drow, dcol)

    # count increments for pre-existing edges: locate their triple
    inc = delta.edge_placed & ~delta.edge_new & sok & dok
    q = _searchsorted3(edge_row, edge_col, edge_type,
                       jnp.where(inc, ps, big), jnp.where(inc, pd, big),
                       jnp.where(inc, delta.etype, 0))
    qc = jnp.clip(q, 0, ecap - 1)
    match = inc & (edge_row[qc] == ps) & (edge_col[qc] == pd) & \
        (edge_type[qc] == delta.etype)
    edge_count = edge_count.at[jnp.where(match, qc, ecap)].add(
        delta.count, mode="drop")

    rows = jnp.arange(ncap + 1, dtype=jnp.int32)
    indptr = jnp.searchsorted(edge_row, rows, side="left").astype(jnp.int32)
    edge_prefix = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(edge_count, dtype=jnp.int32)]
    )

    # reverse orientation: sort/merge by (col, row, etype)
    redge_row, redge_col, redge_type, _ = merge(
        snap.redge_row, snap.redge_col, snap.redge_type, None, dcol, drow)
    rindptr = jnp.searchsorted(redge_row, rows, side="left").astype(jnp.int32)

    # anything the merge could not place? (dangling new edge, or a
    # count increment whose edge is not in the base CSR)
    unplaced = jnp.sum((delta.edge_new & ~live_d).astype(jnp.int32)) + \
        jnp.sum((inc & ~match).astype(jnp.int32)) + \
        jnp.sum((delta.edge_placed & ~delta.edge_new & ~(sok & dok))
                .astype(jnp.int32))

    out = GraphSnapshot(
        node_key=node_key,
        node_count=node_count,
        node_degree=node_degree,
        indptr=indptr,
        edge_row=edge_row,
        edge_col=edge_col,
        edge_type=edge_type,
        edge_count=edge_count,
        edge_prefix=edge_prefix,
        rindptr=rindptr,
        redge_row=redge_row,
        redge_col=redge_col,
        redge_type=redge_type,
        n_nodes=snap.n_nodes + k_new,
        n_edges=indptr[-1],
    )
    return out, unplaced


class SnapshotMaintainer:
    """Keeps a CSR snapshot current across commits without paying a
    full `build_snapshot` per query (ROADMAP "incremental snapshot
    maintenance").

    `absorb(et, stats)` (the `GraphIngestor.commit_hooks` shape)
    buffers each commit's `CommitDelta`; `snapshot(store)` applies the
    pending deltas to the cached snapshot and falls back to a full
    rebuild only when (a) there is no snapshot yet, (b) the pending
    buffer overflowed `max_pending`, or (c) the store holds edges the
    merge cannot place (dangling endpoints under node-table
    saturation).  `full_builds` / `delta_applies` count both paths."""

    def __init__(self, max_pending: int = 32):
        from repro.telemetry.spans import NULL_REGISTRY

        self.max_pending = max_pending
        self._snap: Optional[GraphSnapshot] = None
        self._pending: List[CommitDelta] = []
        self._force_rebuild = True
        self.full_builds = 0
        self.delta_applies = 0
        self.telemetry = NULL_REGISTRY

    def absorb(self, et, stats) -> None:
        delta = None if stats is None else stats.get("delta")
        if delta is None:
            self._force_rebuild = True  # opaque commit: cannot merge
        else:
            self._pending.append(delta)

    def reset(self) -> None:
        """Drop cached/pending state so the next `snapshot()` is a full
        rebuild — how checkpoint restore (repro.resilience) re-anchors
        the view on the restored store without serialising the CSR."""
        self._snap = None
        self._pending = []
        self._force_rebuild = True

    def snapshot(self, store: GraphStore) -> GraphSnapshot:
        tel = self.telemetry
        pending, self._pending = self._pending, []
        snap = self._snap
        if (snap is None or self._force_rebuild
                or len(pending) > self.max_pending):
            with tel.span("snapshot.rebuild"):
                snap = build_snapshot(store)
            self.full_builds += 1
        else:
            for d in pending:
                with tel.span("snapshot.apply_delta"):
                    snap, unplaced = apply_delta(snap, d)
                self.delta_applies += 1
                if int(unplaced):
                    with tel.span("snapshot.rebuild"):
                        snap = build_snapshot(store)
                    self.full_builds += 1
                    break
        self._snap = snap
        # dangling edges (store committed, CSR excluded) can be
        # resurrected by later node inserts — only a rebuild sees that
        self._force_rebuild = int(store.n_edges) != int(snap.n_edges)
        return snap
