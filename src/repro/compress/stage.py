"""Dictionary-compression pipeline stages (GraphZip rewrite path).

`DictionaryStage.rewrite` turns one dedup'd `EdgeTable` into a
`CompressedCommit`: the batch's dictionary hits become `(pattern_id,
bindings)` *references* — the binding is the cached (edge, src, dst)
store-slot triple — and the misses become a smaller residual
`EdgeTable` that takes the normal two-sweep commit.  Mining
(`repro.kernels.pattern_mine`) marks which residual edges belong to
frequent patterns; after the store confirms their slots,
`observe_commit` admits them to the dictionary so the NEXT occurrence
is a reference.

Bit-exactness: an edge's first-ever appearance is always a dictionary
miss (the dictionary only holds previously committed edges), so it is
inserted by the residual sweep exactly as the raw path would; present
keys never claim empty slots in `upsert_sweep`, so the scatter races
involve the same new-key set in both paths and every placement/count
lands identically — `tests/test_compress.py` asserts full store
equality against the uncompressed path.

`CompressedCommit` duck-types the `EdgeTable` surface the rest of the
system reads (`controlled_tick` metadata, `sketch_update` fields), so
sinks, sketches and the snapshot maintainer observe compressed commits
unchanged.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import dedup_with_counts, mix_keys
from repro.core.edge_table import EdgeTable
from repro.compress.dictionary import (
    PatternDictionary,
    dict_admit,
    dict_lookup,
    init_dictionary,
)

REF_MIN_CAP = 8  # smallest static reference-array capacity


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CompressedCommit:
    """One batch rewritten as residual EdgeTable + pattern references.

    Reference arrays are (R,) at a static power-of-two capacity;
    `ref_eslot`/`ref_sslot`/`ref_dslot` are the dictionary's cached
    store slots (the reference bindings), `ref_pattern` the dictionary
    entry index (the pattern id).  Scalar metadata keeps the FULL
    batch's unique node/edge counts so controller signals (density,
    size, rho denominator) match the uncompressed path.
    """

    residual: EdgeTable
    res_admit: jax.Array    # (rcap,) bool — mined pattern members to admit
    res_psig: jax.Array     # (rcap,) key dtype — their pattern signatures
    ref_src: jax.Array      # (R,) key dtype
    ref_dst: jax.Array      # (R,) key dtype
    ref_etype: jax.Array    # (R,) int32
    ref_count: jax.Array    # (R,) int32 batch multiplicity
    ref_eslot: jax.Array    # (R,) int32 store edge slot (binding)
    ref_sslot: jax.Array    # (R,) int32 store src-node slot
    ref_dslot: jax.Array    # (R,) int32 store dst-node slot
    ref_pattern: jax.Array  # (R,) int32 dictionary entry (pattern id)
    ref_valid: jax.Array    # (R,) bool
    n_refs: jax.Array       # scalar int32
    n_raw: jax.Array        # scalar int32 full-batch raw instructions
    n_nodes_full: jax.Array  # scalar int32 full-batch unique nodes
    n_edges_full: jax.Array  # scalar int32 full-batch unique edges

    def tree_flatten(self):
        return (self.residual, self.res_admit, self.res_psig, self.ref_src,
                self.ref_dst, self.ref_etype, self.ref_count, self.ref_eslot,
                self.ref_sslot, self.ref_dslot, self.ref_pattern,
                self.ref_valid, self.n_refs, self.n_raw, self.n_nodes_full,
                self.n_edges_full), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    # ---- EdgeTable duck-type surface (sketch_update reads these) ----
    @property
    def src(self):
        return jnp.concatenate([self.residual.src, self.ref_src])

    @property
    def dst(self):
        return jnp.concatenate([self.residual.dst, self.ref_dst])

    @property
    def etype(self):
        return jnp.concatenate([self.residual.etype, self.ref_etype])

    @property
    def count(self):
        return jnp.concatenate([self.residual.count, self.ref_count])

    @property
    def edge_valid(self):
        return jnp.concatenate([self.residual.edge_valid, self.ref_valid])

    @property
    def node_ids(self):
        return jnp.concatenate([
            self.residual.node_ids,
            jnp.where(self.ref_valid, self.ref_src, 0),
            jnp.where(self.ref_valid, self.ref_dst, 0)])

    @property
    def node_valid(self):
        return jnp.concatenate([self.residual.node_valid,
                                self.ref_valid, self.ref_valid])

    # ---- table-level metadata (controlled_tick reads these) ----
    def density(self) -> jax.Array:
        v = jnp.maximum(self.n_nodes_full.astype(jnp.float32), 2.0)
        return 2.0 * self.n_edges_full.astype(jnp.float32) / (v * (v - 1.0))

    def size(self) -> jax.Array:
        return self.n_edges_full + self.n_nodes_full

    def compression_ratio(self) -> jax.Array:
        """Fig. 13 accounting with references: a reference costs ONE
        instruction (vs 1 edge + up to 2 node instructions raw)."""
        eff = (self.residual.n_nodes + self.residual.n_edges
               + self.n_refs).astype(jnp.float32)
        raw = jnp.maximum((3 * self.n_raw).astype(jnp.float32), 1.0)
        return eff / raw


def _empty_refs(kd, cap: int = REF_MIN_CAP):
    return dict(
        ref_src=jnp.zeros((cap,), kd), ref_dst=jnp.zeros((cap,), kd),
        ref_etype=jnp.zeros((cap,), jnp.int32),
        ref_count=jnp.zeros((cap,), jnp.int32),
        ref_eslot=jnp.full((cap,), -1, jnp.int32),
        ref_sslot=jnp.full((cap,), -1, jnp.int32),
        ref_dslot=jnp.full((cap,), -1, jnp.int32),
        ref_pattern=jnp.full((cap,), -1, jnp.int32),
        ref_valid=jnp.zeros((cap,), bool),
        n_refs=jnp.zeros((), jnp.int32),
    )


@partial(jax.jit, static_argnames=("rcap", "refcap"))
def _split(et: EdgeTable, hit, admit, psig, eslot, sslot, dslot, entry,
           rcap: int, refcap: int) -> CompressedCommit:
    """Compact dictionary hits into reference arrays and misses into a
    residual EdgeTable (static power-of-two capacities)."""
    keep = et.edge_valid & ~hit
    order = jnp.argsort(~keep)  # stable: kept edges first, in order
    sidx = order[:rcap]
    rvalid = keep[sidx]
    zed = lambda a: jnp.where(rvalid, a[sidx], 0)
    rsrc, rdst = zed(et.src), zed(et.dst)
    rety, rcnt = zed(et.etype), zed(et.count)
    ncomp = dedup_with_counts(jnp.concatenate([rsrc, rdst]),
                              jnp.concatenate([rvalid, rvalid]))
    nidx = lambda k: jnp.clip(
        jnp.searchsorted(ncomp.keys, k).astype(jnp.int32), 0, 2 * rcap - 1)
    residual = EdgeTable(
        src=rsrc, dst=rdst, etype=rety, count=rcnt, edge_valid=rvalid,
        node_ids=ncomp.keys, node_valid=ncomp.valid,
        src_node_idx=nidx(rsrc), dst_node_idx=nidx(rdst),
        n_edges=jnp.sum(rvalid.astype(jnp.int32)),
        n_nodes=ncomp.n_unique,
        n_raw=jnp.sum(jnp.where(rvalid, rcnt, 0)),
    )
    rorder = jnp.argsort(~hit)
    ridx = rorder[:refcap]
    refv = hit[ridx]
    gk = lambda a: jnp.where(refv, a[ridx], 0)
    gi = lambda a: jnp.where(refv, a[ridx], -1)
    return CompressedCommit(
        residual=residual,
        res_admit=admit[sidx] & rvalid,
        res_psig=jnp.where(rvalid, psig[sidx], 0),
        ref_src=gk(et.src), ref_dst=gk(et.dst),
        ref_etype=jnp.where(refv, et.etype[ridx], 0),
        ref_count=jnp.where(refv, et.count[ridx], 0),
        ref_eslot=gi(eslot), ref_sslot=gi(sslot), ref_dslot=gi(dslot),
        ref_pattern=gi(entry),
        ref_valid=refv,
        n_refs=jnp.sum(refv.astype(jnp.int32)),
        n_raw=et.n_raw,
        n_nodes_full=et.n_nodes,
        n_edges_full=et.n_edges,
    )


def _pow2(n: int, lo: int) -> int:
    return max(lo, 1 << int(np.ceil(np.log2(max(n, 1)))))


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
        """Mine + dictionary lookup + split one dedup'd batch."""
        from repro.kernels import ops

        kd = et.src.dtype
        tel = self.telemetry
        self._ensure(kd)
        with tel.span("rewrite.mine"):
            fan_out, fan_in, flags, psig = ops.pattern_mine(
                et.src, et.dst, et.etype, et.count, et.edge_valid,
                self.star_min, self.hot_min)
        with tel.span("rewrite.lookup"):
            keys = mix_keys(et.src, et.dst, et.etype)
            self.dct, hit, eslot, sslot, dslot, entry = dict_lookup(
                self.dct, keys, et.edge_valid)
            n_ref = int(jnp.sum(hit.astype(jnp.int32)))
        admit = (flags != 0) & et.edge_valid & ~hit
        self.rewrites += 1
        self.refs_total += n_ref
        if n_ref == 0:
            # nothing referenced: the batch IS the residual
            return CompressedCommit(
                residual=et, res_admit=admit,
                res_psig=jnp.where(et.edge_valid, psig, 0),
                n_raw=et.n_raw, n_nodes_full=et.n_nodes,
                n_edges_full=et.n_edges, **_empty_refs(kd))
        cap = et.src.shape[0]
        n_valid = int(jnp.sum(et.edge_valid.astype(jnp.int32)))
        rcap = min(_pow2(max(n_valid - n_ref, 1), 64), cap)
        refcap = min(_pow2(n_ref, REF_MIN_CAP), cap)
        with tel.span("rewrite.split"):
            return _split(et, hit, admit, psig, eslot, sslot, dslot, entry,
                          rcap, refcap)

    # ---- commit feedback (ingestor.commit_hooks) ----
    def observe_commit(self, committed, stats) -> None:
        """Admit the just-committed batch's mined pattern members using
        the slots the commit confirmed (`nslot`/`eslot` commit stats)."""
        if self.dct is None or stats is None:
            return
        res = getattr(committed, "residual", None)
        admit_mask = getattr(committed, "res_admit", None)
        if res is None or admit_mask is None:
            return
        eslot = stats.get("eslot")
        nslot = stats.get("nslot")
        if eslot is None or nslot is None:
            return
        with self.telemetry.span("dict.admit"):
            sslot = nslot[res.src_node_idx]
            dslot = nslot[res.dst_node_idx]
            admit = admit_mask & (eslot >= 0) & (sslot >= 0) & (dslot >= 0)
            keys = mix_keys(res.src, res.dst, res.etype)
            self.dct = dict_admit(self.dct, keys, admit, eslot, sslot, dslot,
                                  committed.res_psig, ttl=self.ttl)

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
        with self.telemetry.span("transform.fetch"):
            n_instr = (int(cc.residual.n_nodes) + int(cc.residual.n_edges)
                       + int(cc.n_refs))
        return cc, n_instr, raw_instr
