"""Device-resident property-graph store — the framework's "Neo4j".

Open-addressing hash tables in JAX arrays (linear probing, vectorised
over the batch; all shapes static).  The store ingests *compressed*
edge-table batches (Algorithm 3 GRAPHPUSH): MERGE semantics for nodes
(insert-if-absent, so ingesting the same node twice never duplicates),
CREATE-or-count for edges (duplicate edges accumulate `count`, the
paper's Alg. 1 line 20 semantics at store level).

The commit hot path is a *fused upsert* (repro.kernels.upsert):
lookup-or-insert in ONE probe sweep per table, and degree updates
reuse the node-upsert slots through the edge table's dedup index — the
whole commit runs exactly TWO probe loops (nodes + edges), down from
six in the seed (see `count_probe_loops`).  The probe budget is
adaptive: it doubles past 0.6 load factor and doubles again past 0.8
(ROADMAP "store probing robustness"); `dropped_inserts` in the commit
stats is the table-pressure signal the Algorithm-2 controller consumes
via the MetricsHub.

`ingest_step` also returns the number of *new* nodes — exactly the
bucket-diversity signal rho the buffer controller needs (§III-A), so
diversity costs nothing extra to compute — and a `CommitDelta` the
incremental snapshot maintainer (repro.query.snapshot.apply_delta)
merges into the CSR without a full recompaction.

The distributed variant shards both tables over the `data` mesh axis by
key ownership and exchanges entries with a single all_to_all — the
paper's "DBMS ingestion pool" mapped onto a TPU pod (DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

MAX_PROBES = 32


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GraphStore:
    node_keys: jax.Array  # (Ncap,) key dtype; 0 = empty
    node_count: jax.Array  # (Ncap,) int32  (times seen, a node property)
    node_degree: jax.Array  # (Ncap,) int32
    edge_keys: jax.Array  # (Ecap,)
    edge_src: jax.Array  # (Ecap,)
    edge_dst: jax.Array  # (Ecap,)
    edge_type: jax.Array  # (Ecap,) int32
    edge_count: jax.Array  # (Ecap,) int32
    n_nodes: jax.Array  # scalar int32
    n_edges: jax.Array  # scalar int32

    def tree_flatten(self):
        # shallow on purpose: astuple() recurses into tuple-subclass
        # leaves (e.g. the PartitionSpec pytree make_distributed_ingest
        # builds), silently downgrading them to plain tuples
        return tuple(getattr(self, f.name)
                     for f in dataclasses.fields(self)), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CommitDelta:
    """What one commit changed — the incremental-snapshot input.

    Node arrays are (2*cap,), edge arrays (cap,) at the edge-table
    capacity.  `*_placed` marks entries that reached the store (valid
    and not dropped by probing); `*_new` marks first insertions.
    `src_deg`/`dst_deg` mark the endpoints that received a +1 degree
    (endpoint present in the table and the edge newly created)."""

    node_ids: jax.Array
    node_placed: jax.Array
    node_new: jax.Array
    src: jax.Array
    dst: jax.Array
    etype: jax.Array
    count: jax.Array
    edge_placed: jax.Array
    edge_new: jax.Array
    src_deg: jax.Array
    dst_deg: jax.Array

    def tree_flatten(self):
        # shallow, like GraphStore: astuple() deep-copies and rebuilds
        # tuple-subclass leaves (PartitionSpec) as plain tuples
        return tuple(getattr(self, f.name)
                     for f in dataclasses.fields(self)), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def init_store(node_cap: int, edge_cap: int, key_dtype=None) -> GraphStore:
    from repro.core.compression import key_dtype as kd_fn

    kd = key_dtype or kd_fn()
    z32 = lambda c: jnp.zeros((c,), jnp.int32)
    zk = lambda c: jnp.zeros((c,), kd)
    return GraphStore(
        node_keys=zk(node_cap),
        node_count=z32(node_cap),
        node_degree=z32(node_cap),
        edge_keys=zk(edge_cap),
        edge_src=zk(edge_cap),
        edge_dst=zk(edge_cap),
        edge_type=z32(edge_cap),
        edge_count=z32(edge_cap),
        n_nodes=jnp.zeros((), jnp.int32),
        n_edges=jnp.zeros((), jnp.int32),
    )


def probe_budget(n_used: jax.Array, cap: int) -> jax.Array:
    """Adaptive probe rounds from the table load factor: MAX_PROBES
    below 0.6 load, x2 past 0.6, x4 past 0.8.  Monotone in load, so a
    key placed under an earlier (smaller) budget is always found again
    under the current one."""
    load = n_used.astype(jnp.float32) / jnp.float32(cap)
    mult = 1 + (load >= 0.6).astype(jnp.int32) + 2 * (load >= 0.8).astype(jnp.int32)
    return jnp.int32(MAX_PROBES) * mult


@jax.jit
def ingest_step(store: GraphStore, et) -> Tuple[GraphStore, dict]:
    """GRAPHPUSH (Algorithm 3): commit one compressed edge table.

    Two fused probe sweeps (nodes, edges); degree updates reuse the
    node slots via the edge table's dedup index.  Returns (store',
    stats) where stats carries the controller signals: new-node count
    (diversity rho numerator), sizes, the effective instruction count,
    the table-pressure signals (dropped_inserts, loads, and
    `probe_rounds`, the budget the sweeps ran with: the controller's
    pressure input), and the `CommitDelta` for incremental snapshot
    maintenance.

    Probe-work counters, one pair per sweep: `node_rounds_run` /
    `edge_rounds_run`, the rounds the sweep's loop executed (it ends
    once its last lane is placed, so this is capped by the budget, and
    is the whole budget when a lane is dropped), and
    `node_rounds_needed` / `edge_rounds_needed`, the rounds that placed
    its last lane, read from the slots (`repro.kernels.upsert.
    rounds_needed`; a dropped lane counts as the budget)."""
    from repro.core.compression import mix_keys
    from repro.kernels import ops
    from repro.kernels.upsert import rounds_needed

    # NB masked lanes scatter to the out-of-range capacity index, which
    # mode="drop" discards; -1 would WRAP to the last slot and corrupt it.
    ncap = store.node_keys.shape[0]
    ecap = store.edge_keys.shape[0]
    n_probes_n = probe_budget(store.n_nodes, ncap)
    n_probes_e = probe_budget(store.n_edges, ecap)

    # Each phase runs under a `jax.named_scope` (node_upsert, edge_upsert,
    # store_scatter, degree_update): the names reach every HLO
    # instruction's `op_name` metadata, so a trace's device time can be
    # split by phase.  Scopes are metadata only; the compiled program is
    # the same without them.

    # ---- nodes: MERGE (one fused probe sweep) ----
    with jax.named_scope("node_upsert"):
        nk, nslot, n_isnew, n_rounds = ops.fused_upsert(
            store.node_keys, et.node_ids, et.node_valid, n_probes_n)
    node_placed = et.node_valid & (nslot >= 0)
    is_new = n_isnew & et.node_valid
    with jax.named_scope("store_scatter"):
        node_count = store.node_count.at[
            jnp.where(node_placed, nslot, ncap)].add(1, mode="drop")
    n_new_nodes = jnp.sum(is_new.astype(jnp.int32))
    dropped_nodes = jnp.sum((et.node_valid & ~node_placed).astype(jnp.int32))

    # ---- edges: CREATE-or-count (one fused probe sweep) ----
    with jax.named_scope("store_scatter"):
        ekey = mix_keys(et.src, et.dst, et.etype)
    with jax.named_scope("edge_upsert"):
        ek, eslot, e_isnew, e_rounds = ops.fused_upsert(
            store.edge_keys, ekey, et.edge_valid, n_probes_e)
    edge_placed = et.edge_valid & (eslot >= 0)
    e_new = e_isnew & et.edge_valid
    with jax.named_scope("store_scatter"):
        edge_src = store.edge_src.at[jnp.where(e_new, eslot, ecap)].set(
            et.src, mode="drop")
        edge_dst = store.edge_dst.at[jnp.where(e_new, eslot, ecap)].set(
            et.dst, mode="drop")
        edge_type = store.edge_type.at[jnp.where(e_new, eslot, ecap)].set(
            et.etype, mode="drop")
        edge_count = store.edge_count.at[
            jnp.where(edge_placed, eslot, ecap)].add(et.count, mode="drop")
    n_new_edges = jnp.sum(e_new.astype(jnp.int32))
    dropped_edges = jnp.sum((et.edge_valid & ~edge_placed).astype(jnp.int32))

    # ---- degree update (both endpoints of new edges) — NO re-probing:
    # the dedup index maps each endpoint to its already-upserted slot
    with jax.named_scope("degree_update"):
        sslot = nslot[et.src_node_idx]
        dslot = nslot[et.dst_node_idx]
        src_deg = e_new & (sslot >= 0)
        dst_deg = e_new & (dslot >= 0)
        node_degree = store.node_degree.at[
            jnp.where(src_deg, sslot, ncap)].add(1, mode="drop")
        node_degree = node_degree.at[
            jnp.where(dst_deg, dslot, ncap)].add(1, mode="drop")

    new_store = GraphStore(
        node_keys=nk,
        node_count=node_count,
        node_degree=node_degree,
        edge_keys=ek,
        edge_src=edge_src,
        edge_dst=edge_dst,
        edge_type=edge_type,
        edge_count=edge_count,
        n_nodes=store.n_nodes + n_new_nodes,
        n_edges=store.n_edges + n_new_edges,
    )
    stats = {
        "new_nodes": n_new_nodes,
        "new_edges": n_new_edges,
        "batch_nodes": jnp.sum(et.node_valid.astype(jnp.int32)),
        "batch_edges": jnp.sum(et.edge_valid.astype(jnp.int32)),
        "instructions": n_new_nodes + jnp.sum(et.edge_valid.astype(jnp.int32)),
        "store_nodes": new_store.n_nodes,
        "store_edges": new_store.n_edges,
        # table-pressure signals (MetricsHub -> Algorithm-2 controller)
        "dropped_nodes": dropped_nodes,
        "dropped_edges": dropped_edges,
        "dropped_inserts": dropped_nodes + dropped_edges,
        # the budget the sweeps ran with, the controller's pressure input
        "probe_rounds": jnp.maximum(n_probes_n, n_probes_e),
        # probe work per sweep: the rounds each loop executed, and the
        # rounds that placed its last lane (upsert.rounds_needed)
        "node_rounds_run": n_rounds,
        "edge_rounds_run": e_rounds,
        "node_rounds_needed": rounds_needed(
            et.node_ids, nslot, et.node_valid, ncap, n_probes_n),
        "edge_rounds_needed": rounds_needed(
            ekey, eslot, et.edge_valid, ecap, n_probes_e),
        "node_load": new_store.n_nodes.astype(jnp.float32) / jnp.float32(ncap),
        "edge_load": new_store.n_edges.astype(jnp.float32) / jnp.float32(ecap),
        # per-entry store slots (-1 = dropped): the dictionary-
        # compression stage caches these as reference bindings
        # (repro.compress); popped before cross-shard reduction like
        # the delta below
        "nslot": jnp.where(node_placed, nslot, -1),
        "eslot": jnp.where(edge_placed, eslot, -1),
        # incremental snapshot maintenance input
        "delta": CommitDelta(
            node_ids=et.node_ids,
            node_placed=node_placed,
            node_new=is_new,
            src=et.src,
            dst=et.dst,
            etype=et.etype,
            count=et.count,
            edge_placed=edge_placed,
            edge_new=e_new,
            src_deg=src_deg,
            dst_deg=dst_deg,
        ),
    }
    return new_store, stats


@jax.jit
def commit_compressed(store: GraphStore, cc) -> Tuple[GraphStore, dict]:
    """Pattern-aware GRAPHPUSH for a `repro.compress.CompressedCommit`.

    The residual edge table takes the normal two-sweep `ingest_step`;
    dictionary references then land by DIRECT scatter to their cached
    store slots — zero probe rounds per reference.  Referenced edges
    are by construction already present (their slots were cached at a
    previous successful commit and slots are never freed), so the
    result is bit-identical to committing the full raw batch: counts
    accumulate on the same slots and no degrees change (refs are never
    new edges).  The residual keeps every node of the batch table, so
    its node sweep gives each unique batch node, reference-only
    endpoints included, exactly one `node_count` increment.  The
    residual and the references are masks over the table's lanes, so
    this compiles once per table capacity.

    Stats keep the raw-path keys with FULL-batch semantics (so rho,
    instruction accounting and pressure signals stay comparable) plus
    `dict_refs` / `dict_hit_rate`, and the `CommitDelta` marks the
    reference lanes placed-not-new, as the raw path's delta has them,
    so incremental snapshots (repro.query.snapshot.apply_delta) stay
    exact.
    """
    store1, s = ingest_step(store, cc.residual)
    ecap = store1.edge_keys.shape[0]

    # the residual's phases are named inside `ingest_step`
    with jax.named_scope("ref_apply"):
        # ---- reference edges: count accumulation on cached slots ----
        rv = cc.ref_valid & (cc.ref_eslot >= 0)
        edge_count = store1.edge_count.at[jnp.where(rv, cc.ref_eslot, ecap)].add(
            cc.residual.count, mode="drop")
        n_refs = jnp.sum(rv.astype(jnp.int32))

    d = s["delta"]
    batch_edges = s["batch_edges"] + n_refs
    stats = dict(s)
    stats.update(
        batch_edges=batch_edges,
        instructions=s["new_nodes"] + batch_edges,
        dict_refs=n_refs,
        dict_hit_rate=(n_refs.astype(jnp.float32)
                       / jnp.maximum(batch_edges.astype(jnp.float32), 1.0)),
        delta=dataclasses.replace(d, edge_placed=d.edge_placed | rv),
    )
    new_store = dataclasses.replace(store1, edge_count=edge_count)
    return new_store, stats


def count_probe_loops(et) -> int:
    """Structural perf contract: number of sequential probe loops
    (while/scan eqns) in one compiled commit — 2 since the fused
    upsert (6 in the seed's lookup-then-insert commit).  Benchmarks
    and tests report/assert this."""
    kd = et.node_ids.dtype
    store = init_store(et.node_ids.shape[0], et.src.shape[0], key_dtype=kd)
    jaxpr = jax.make_jaxpr(ingest_step)(store, et)

    def count(jp) -> int:
        total = 0
        for eqn in jp.eqns:
            if eqn.primitive.name in ("while", "scan"):
                total += 1
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    inner = getattr(sub, "jaxpr", None)
                    if inner is not None:
                        total += count(inner)
        return total

    return count(jaxpr.jaxpr)


# ---------------------------------------------------------------------------
# Distributed ingest: shard by key ownership over the `data` axis
# ---------------------------------------------------------------------------

# stats keys reduced by max instead of sum across shards (budgets and
# load factors are per-table properties, not additive counts)
_STATS_MAX_KEYS = ("probe_rounds", "node_load", "edge_load",
                   "node_rounds_run", "edge_rounds_run",
                   "node_rounds_needed", "edge_rounds_needed")


def make_distributed_ingest(mesh):
    """shard_map ingest over the `data` axis: each shard owns the keys
    with hash % D == rank; one all_to_all routes every edge to its
    owner shard, then the local path (dedup + fused-upsert commit)
    runs unchanged — sharded and local commits share the one
    `ingest_step` implementation.

    This is the paper's ingestion-pool architecture mapped onto a pod
    (DESIGN.md §2): the Bolt connector pool becomes the data-axis
    shards, the commit becomes a compiled collective exchange.  The
    `model` (and `pod`) axes replicate the ingest — on a real fleet
    they run the training/serving consumers fed by this store."""
    from jax.sharding import PartitionSpec as P

    D = mesh.shape["data"]
    other_axes = tuple(a for a in mesh.axis_names if a != "data")

    def local_ingest(store, src, dst, etype, valid):
        # src/dst/etype/valid: (n_local,) this shard's raw slice
        own = (src % jnp.asarray(D, src.dtype)).astype(jnp.int32)
        order = jnp.argsort(own)
        srcs, dsts, ets, vals, owns = (
            src[order], dst[order], etype[order], valid[order], own[order]
        )
        n = src.shape[0]
        per = n // D
        # capacity-partitioned exchange: slot i of shard r goes to shard
        # i//per; entries landing in a foreign slice are dropped (rare:
        # hashing balances owners), mirroring the paper's bounded pool
        slot_owner = jnp.arange(n) // per
        keep = vals & (owns == slot_owner)

        def ex(x):
            return jax.lax.all_to_all(x.reshape(D, per), "data", 0, 0, tiled=True).reshape(-1)

        from repro.core.edge_table import build_edge_table

        et = build_edge_table(ex(srcs), ex(dsts), ex(ets), ex(keep))
        # n_nodes/n_edges are GLOBAL (replicated) but the tables here
        # are the per-shard slices: scale the counters down so the
        # adaptive probe budget and load stats see the local fill
        local_store = dataclasses.replace(
            store,
            n_nodes=store.n_nodes // jnp.int32(D),
            n_edges=store.n_edges // jnp.int32(D),
        )
        new_store, stats = ingest_step(local_store, et)
        # the CommitDelta and slot arrays stay shard-local (they index
        # shard tables)
        stats.pop("delta", None)
        stats.pop("nslot", None)
        stats.pop("eslot", None)
        stats = {
            k: (jax.lax.pmax(v, "data") if k in _STATS_MAX_KEYS
                else jax.lax.psum(v, "data"))
            for k, v in stats.items()
        }
        # store-level counters are global (replicated) across shards
        new_store = dataclasses.replace(
            new_store,
            n_nodes=store.n_nodes + stats["new_nodes"],
            n_edges=store.n_edges + stats["new_edges"],
        )
        return new_store, stats

    store_specs = GraphStore(
        node_keys=P("data"), node_count=P("data"), node_degree=P("data"),
        edge_keys=P("data"), edge_src=P("data"), edge_dst=P("data"),
        edge_type=P("data"), edge_count=P("data"),
        n_nodes=P(), n_edges=P(),
    )
    in_specs = (store_specs, P("data"), P("data"), P("data"), P("data"))
    out_specs = (store_specs, P())
    return jax.shard_map(local_ingest, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
