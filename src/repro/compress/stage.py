"""Dictionary-compression pipeline stages (GraphZip rewrite path).

`DictionaryStage.rewrite` turns one dedup'd `EdgeTable` into a
`CompressedCommit` in one jitted program per table capacity: the
batch's dictionary hits become *references* — the dictionary entry
(the pattern id) and its cached store edge slot (the binding) — and
the misses the *residual*.  Both are masks over the table's own lanes,
not compacted copies: the residual is the table with `edge_valid &
~hit`, keeping the table's node arrays, and the references are the
same lanes with `hit`.  So every program of the path has the table's
shapes, whatever share of the table hits, and nothing is pulled to the
host to choose a shape.  Mining (`repro.kernels.pattern_mine`) marks
which residual edges belong to frequent patterns; after the store
confirms their slots, `observe_commit` admits them to the dictionary
so the NEXT occurrence is a reference.

Bit-exactness: an edge's first-ever appearance is always a dictionary
miss (the dictionary only holds previously committed edges), so it is
inserted by the residual sweep exactly as the raw path would; present
keys never claim empty slots in `upsert_sweep`, so the scatter races
involve the same new-key set in both paths and every placement/count
lands identically.  The residual keeps every node of the table, so the
node sweep counts each unique batch node once, reference-only
endpoints included, as the raw path does — `tests/test_compress.py`
asserts full store equality against the uncompressed path.

The price of the fixed shape: the residual's sweep runs over all of
the table's lanes, and the counter `rewrite.lanes_idle` counts the
lanes of the residual and of the references that hold no edge.

`CompressedCommit` duck-types the `EdgeTable` surface the rest of the
system reads (`push_batch` metadata, `sketch_update` fields) at
the table's own shapes, so sinks, sketches and the snapshot maintainer
observe compressed commits unchanged.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.compression import mix_keys
from repro.core.edge_table import EdgeTable
from repro.compress.dictionary import (
    PatternDictionary,
    dict_admit,
    dict_lookup,
    init_dictionary,
)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CompressedCommit:
    """One batch rewritten as residual + pattern references, both masks
    over the batch table's lanes.

    `residual` is the table with its hit lanes made invalid; the
    reference arrays are (cap,) over the same lanes: `ref_valid` marks
    the hits, `ref_eslot` is the dictionary's cached store edge slot
    (the binding) and `ref_pattern` the dictionary entry (the pattern
    id), -1 off the hits.  A reference's edge and count are the
    residual's arrays at its lane.  Scalar metadata keeps the FULL
    batch's unique edge count so controller signals (density, size,
    rho denominator) match the uncompressed path.
    """

    residual: EdgeTable
    res_admit: jax.Array    # (cap,) bool — mined pattern members to admit
    res_psig: jax.Array     # (cap,) key dtype — their pattern signatures
    ref_valid: jax.Array    # (cap,) bool dictionary hits
    ref_eslot: jax.Array    # (cap,) int32 store edge slot (binding)
    ref_pattern: jax.Array  # (cap,) int32 dictionary entry (pattern id)
    n_refs: jax.Array       # scalar int32
    n_instr: jax.Array      # scalar int32 instructions: residual nodes
    #                         and edges, one per reference
    n_raw: jax.Array        # scalar int32 full-batch raw instructions
    n_edges_full: jax.Array  # scalar int32 full-batch unique edges

    def tree_flatten(self):
        return (self.residual, self.res_admit, self.res_psig, self.ref_valid,
                self.ref_eslot, self.ref_pattern, self.n_refs, self.n_instr,
                self.n_raw, self.n_edges_full), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def lanes(self) -> int:
        """Lanes of the residual and of the references."""
        return self.residual.src.shape[0] + self.ref_valid.shape[0]

    # ---- EdgeTable duck-type surface (sketch_update reads these) ----
    @property
    def src(self):
        return self.residual.src

    @property
    def dst(self):
        return self.residual.dst

    @property
    def etype(self):
        return self.residual.etype

    @property
    def count(self):
        return self.residual.count

    @property
    def edge_valid(self):
        return self.residual.edge_valid | self.ref_valid

    @property
    def node_ids(self):
        return self.residual.node_ids

    @property
    def node_valid(self):
        return self.residual.node_valid

    # ---- table-level metadata (push_batch reads these) ----
    def density(self) -> jax.Array:
        v = jnp.maximum(self.residual.n_nodes.astype(jnp.float32), 2.0)
        return 2.0 * self.n_edges_full.astype(jnp.float32) / (v * (v - 1.0))

    def size(self) -> jax.Array:
        return self.n_edges_full + self.residual.n_nodes

    def compression_ratio(self) -> jax.Array:
        """Fig. 13 accounting with references: a reference costs ONE
        instruction (vs 1 edge + up to 2 node instructions raw)."""
        raw = jnp.maximum((3 * self.n_raw).astype(jnp.float32), 1.0)
        return self.n_instr.astype(jnp.float32) / raw


@partial(jax.jit, static_argnames=("star_min", "hot_min"))
def rewrite_table(d: PatternDictionary, et: EdgeTable, star_min: int,
                  hot_min: int) -> Tuple[PatternDictionary, CompressedCommit]:
    """Mine, look up and mask one dedup'd table: the dictionary after
    the lookup, and the table as a `CompressedCommit`.  Every output has
    the table's shapes."""
    from repro.kernels import ops

    with jax.named_scope("rewrite_mine"):
        _, _, flags, psig = ops.pattern_mine(
            et.src, et.dst, et.etype, et.count, et.edge_valid,
            star_min, hot_min)
    with jax.named_scope("rewrite_lookup"):
        keys = mix_keys(et.src, et.dst, et.etype)
        d, hit, eslot, _, _, entry = dict_lookup(d, keys, et.edge_valid)
    with jax.named_scope("rewrite_mask"):
        keep = et.edge_valid & ~hit
        n_edges = jnp.sum(keep.astype(jnp.int32))
        n_refs = jnp.sum(hit.astype(jnp.int32))
        # the residual's own endpoints, for the instruction count: the
        # residual keeps every node of the table for the node sweep
        nn = et.node_ids.shape[0]
        used = jnp.zeros((nn,), bool)
        used = used.at[jnp.where(keep, et.src_node_idx, nn)].set(
            True, mode="drop")
        used = used.at[jnp.where(keep, et.dst_node_idx, nn)].set(
            True, mode="drop")
        n_res_nodes = jnp.sum((used & et.node_valid).astype(jnp.int32))
        residual = dataclasses.replace(
            et, edge_valid=keep, n_edges=n_edges,
            n_raw=jnp.sum(jnp.where(keep, et.count, 0)))
        cc = CompressedCommit(
            residual=residual,
            res_admit=(flags != 0) & keep,
            res_psig=jnp.where(keep, psig, 0),
            ref_valid=hit,
            ref_eslot=jnp.where(hit, eslot, -1),
            ref_pattern=jnp.where(hit, entry, -1),
            n_refs=n_refs,
            n_instr=n_res_nodes + n_edges + n_refs,
            n_raw=et.n_raw,
            n_edges_full=et.n_edges,
        )
    return d, cc


@partial(jax.jit, static_argnames=("ttl",))
def admit_committed(d: PatternDictionary, cc: CompressedCommit,
                    eslot: jax.Array, nslot: jax.Array,
                    ttl: int) -> PatternDictionary:
    """Admit a committed batch's mined pattern members with the slots
    the commit confirmed (`eslot` per edge lane, `nslot` per node lane;
    -1 where not placed)."""
    res = cc.residual
    sslot = nslot[res.src_node_idx]
    dslot = nslot[res.dst_node_idx]
    admit = cc.res_admit & (eslot >= 0) & (sslot >= 0) & (dslot >= 0)
    keys = mix_keys(res.src, res.dst, res.etype)
    return dict_admit(d, keys, admit, eslot, sslot, dslot, cc.res_psig,
                      ttl=ttl)


class DictionaryStage:
    """Stage-protocol owner of the pattern dictionary.

    As a record stage it is a pass-through observer (the heavy lifting
    happens at transform time via `rewrite`); `PipelineBuilder
    .with_compression()` wires it in and registers `observe_commit` on
    the sink's ingestor so admissions see confirmed store slots.
    """

    name = "dictionary"

    def __init__(self, capacity: int = 4096, star_min: int = 4,
                 hot_min: int = 2, ttl: int = 64):
        from repro.telemetry.spans import NULL_REGISTRY

        self.capacity = int(capacity)
        self.star_min = int(star_min)
        self.hot_min = int(hot_min)
        self.ttl = int(ttl)
        self.dct: Optional[PatternDictionary] = None
        self.ticks_seen = 0
        self.rewrites = 0
        self.refs_total = 0
        self.telemetry = NULL_REGISTRY

    # ---- Stage protocol ----
    def __call__(self, records: List[dict], ctx=None) -> List[dict]:
        self.ticks_seen += 1
        return records

    # ---- checkpoint surface (repro.resilience); the dictionary itself
    # snapshots as array leaves (lazily re-templated on restore) ----
    def state(self) -> dict:
        return {"ticks_seen": self.ticks_seen, "rewrites": self.rewrites,
                "refs_total": self.refs_total}

    def restore_state(self, s: dict) -> None:
        self.ticks_seen = int(s["ticks_seen"])
        self.rewrites = int(s["rewrites"])
        self.refs_total = int(s["refs_total"])

    # ---- rewrite path ----
    def _ensure(self, kd):
        if self.dct is None or self.dct.sig.dtype != kd:
            self.dct = init_dictionary(self.capacity, kd)

    def rewrite(self, et: EdgeTable) -> CompressedCommit:
        """Mine + dictionary lookup + mask one dedup'd batch: one
        program per table capacity, no device-to-host pull."""
        self._ensure(et.src.dtype)
        with self.telemetry.span("rewrite.table"):
            self.dct, cc = rewrite_table(self.dct, et, self.star_min,
                                         self.hot_min)
        self.rewrites += 1
        return cc

    # ---- commit feedback (ingestor.commit_hooks) ----
    def observe_commit(self, committed, stats) -> None:
        """Count the commit's references (`dict_refs`, which the
        ingestor has already pulled) and admit the just-committed
        batch's mined pattern members using the slots the commit
        confirmed (`nslot`/`eslot` commit stats)."""
        if self.dct is None or stats is None:
            return
        if getattr(committed, "res_admit", None) is None:
            return
        eslot = stats.get("eslot")
        nslot = stats.get("nslot")
        if eslot is None or nslot is None:
            return
        self.refs_total += int(stats["dict_refs"])
        with self.telemetry.span("dict.admit"):
            self.dct = admit_committed(self.dct, committed, eslot, nslot,
                                       self.ttl)

    # ---- observability ----
    def stats(self) -> dict:
        if self.dct is None:
            return {"entries": 0, "load": 0.0, "hit_rate": 0.0,
                    "evictions": 0, "rewrites": self.rewrites,
                    "refs_total": self.refs_total}
        return {
            "entries": int(self.dct.n_entries),
            "load": self.dct.load(),
            "hit_rate": self.dct.hit_rate(),
            "evictions": int(self.dct.evictions),
            "rewrites": self.rewrites,
            "refs_total": self.refs_total,
        }


class CompressingTransform:
    """Transform-protocol wrapper: inner encode, then dictionary
    rewrite.  The instruction count refs actually cost (one per
    reference) replaces the plain compressed count, which is how
    compressibility reaches the consumer model and the controller."""

    def __init__(self, inner, stage: DictionaryStage):
        self.inner = inner
        self.stage = stage
        self.name = f"{inner.name}+dict"

    # one registry drives both halves (builder sets .telemetry once)
    @property
    def telemetry(self):
        return self.stage.telemetry

    @telemetry.setter
    def telemetry(self, reg):
        self.stage.telemetry = reg
        if hasattr(self.inner, "telemetry"):
            self.inner.telemetry = reg

    def encode(self, records: List[dict]) -> Tuple[CompressedCommit, int, int]:
        et, _, raw_instr = self.inner.encode(records)
        cc = self.stage.rewrite(et)
        tel = self.telemetry
        with tel.span("transform.fetch"):
            n_instr, edges = jax.device_get((cc.n_instr, cc.n_edges_full))
        # every valid edge holds one lane, of the residual or of the
        # references; the rest of their lanes is the fixed shape's price
        tel.count("rewrite.edges", int(edges))
        tel.count("rewrite.lanes_idle", cc.lanes - int(edges))
        return cc, int(n_instr), raw_instr
