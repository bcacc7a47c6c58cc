"""Device-resident pattern dictionary (GraphZip's frequent-pattern set).

A fixed-capacity open-addressing table over *edge signatures*: an
entry is one member edge of a mined pattern, keyed by its
`mix_keys(src, dst, etype)` signature, with the pattern signature that
admitted it (`psig`, lineage) and — the payload that makes references
cheap — the store slots the edge and its endpoints were committed to.
A later batch containing the same edge resolves it to a
`(pattern_id, bindings)` reference: the binding IS the cached slot
triple, so the commit path applies it by direct scatter instead of
re-probing three hash tables.

Lifecycle (all counter-deterministic — no wall clock, no RNG):
  * `dict_lookup`  per batch: probe every dedup'd edge key; hits bump
    `refcount` and stamp `clock` with the dictionary tick (LRU), the
    tick advances once per batch.
  * `dict_admit`   after a successful commit: insert the batch's
    pattern-member residual edges (slots now known) via the same
    fused `upsert_sweep` the store uses.
  * eviction       aging sweep inside `dict_admit`: once occupancy
    passes the high-water mark, entries idle for more than `ttl`
    ticks are cleared.  Clearing can break probe chains for entries
    inserted behind an evicted slot; those entries simply stop being
    found (a miss, never a wrong hit) and are re-admitted on their
    next commit — correctness never depends on a dictionary hit.

The dictionary survives across batches by construction and across
shards because `ShardedPipeline` shares ONE transform/sink per run —
a single dictionary observes every commit.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.upsert import probe_hash, upsert_sweep

DICT_PROBES = 16  # fixed probe budget (table never exceeds high water)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PatternDictionary:
    """Fixed-capacity signature table + payload + LRU bookkeeping."""

    sig: jax.Array       # (C,) key dtype; 0 = empty slot
    psig: jax.Array      # (C,) key dtype; mined pattern signature (lineage)
    eslot: jax.Array     # (C,) int32 cached store edge slot
    sslot: jax.Array     # (C,) int32 cached store slot of src node
    dslot: jax.Array     # (C,) int32 cached store slot of dst node
    refcount: jax.Array  # (C,) int32 lifetime reference hits
    clock: jax.Array     # (C,) int32 dictionary tick of last touch (LRU)
    tick: jax.Array      # scalar int32, advances once per lookup batch
    n_entries: jax.Array  # scalar int32 live entries
    hits: jax.Array      # scalar int32 cumulative reference hits
    misses: jax.Array    # scalar int32 cumulative lookup misses
    evictions: jax.Array  # scalar int32 cumulative aged-out entries

    def tree_flatten(self):
        # explicit field tuple, NOT dataclasses.astuple (see
        # CompressedBatch.tree_flatten for the recursion bug class)
        return (self.sig, self.psig, self.eslot, self.sslot, self.dslot,
                self.refcount, self.clock, self.tick, self.n_entries,
                self.hits, self.misses, self.evictions), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def capacity(self) -> int:
        return self.sig.shape[0]

    def load(self) -> float:
        return int(self.n_entries) / max(self.capacity, 1)

    def hit_rate(self) -> float:
        total = int(self.hits) + int(self.misses)
        return int(self.hits) / max(total, 1)


def init_dictionary(capacity: int, key_dtype=None) -> PatternDictionary:
    from repro.core.compression import key_dtype as kd_fn

    kd = key_dtype or kd_fn()
    zk = lambda: jnp.zeros((capacity,), kd)
    z32 = lambda: jnp.zeros((capacity,), jnp.int32)
    zs = lambda: jnp.zeros((), jnp.int32)
    return PatternDictionary(
        sig=zk(), psig=zk(), eslot=z32(), sslot=z32(), dslot=z32(),
        refcount=z32(), clock=z32(), tick=zs(), n_entries=zs(),
        hits=zs(), misses=zs(), evictions=zs(),
    )


@jax.jit
def dict_lookup(d: PatternDictionary, keys: jax.Array, valid: jax.Array):
    """Read-mostly probe of unique batch keys (one tick of the clock).

    Returns (d', hit, eslot, sslot, dslot, entry) — per-key bool hit
    mask, the cached slot payload (-1 where missed) and the dictionary
    entry index (the reference's pattern id).  Probing stops at the
    first empty slot of a key's sequence, mirroring insert order —
    entries orphaned behind an evicted slot read as misses.
    """
    cap = d.sig.shape[0]
    n = keys.shape[0]

    def body(i, carry):
        slot, done = carry
        cand = probe_hash(keys, cap, jnp.full((n,), i, jnp.int32))
        cur = d.sig[cand]
        hit = (cur == keys) & ~done
        slot = jnp.where(hit, cand, slot)
        done = done | hit | (cur == 0)
        return slot, done

    slot, _ = jax.lax.fori_loop(
        0, DICT_PROBES, body, (jnp.full((n,), -1, jnp.int32), ~valid))
    hit = valid & (slot >= 0)
    tgt = jnp.where(hit, slot, cap)
    refcount = d.refcount.at[tgt].add(1, mode="drop")
    clock = d.clock.at[tgt].set(
        jnp.full((n,), 1, jnp.int32) * d.tick, mode="drop")
    d2 = dataclasses.replace(
        d, refcount=refcount, clock=clock, tick=d.tick + 1,
        hits=d.hits + jnp.sum(hit.astype(jnp.int32)),
        misses=d.misses + jnp.sum((valid & ~hit).astype(jnp.int32)))
    safe = jnp.clip(slot, 0, cap - 1)
    g = lambda a: jnp.where(hit, a[safe], -1)
    return d2, hit, g(d.eslot), g(d.sslot), g(d.dslot), slot


@partial(jax.jit, static_argnames=("ttl", "high_water"))
def dict_admit(d: PatternDictionary, keys: jax.Array, admit: jax.Array,
               eslot: jax.Array, sslot: jax.Array, dslot: jax.Array,
               psig: jax.Array, ttl: int = 64,
               high_water: float = 0.85) -> PatternDictionary:
    """Insert committed pattern-member edges (unique keys + payload).

    Runs the aging eviction first when occupancy is past the
    high-water mark: entries idle (no lookup hit, no re-admit) for
    more than `ttl` dictionary ticks are cleared.  Deterministic in
    the tick counter alone.  Then the store's own `upsert_sweep`
    places the admitted keys; already-present keys are refreshed, new
    keys take their payload.
    """
    cap = d.sig.shape[0]
    n = keys.shape[0]
    over = d.n_entries > jnp.int32(int(high_water * cap))
    stale = (d.sig != 0) & (d.clock + jnp.int32(ttl) < d.tick)
    evict = stale & over
    sig = jnp.where(evict, 0, d.sig)
    n_evicted = jnp.sum(evict.astype(jnp.int32))
    refcount = jnp.where(evict, 0, d.refcount)

    sig, slot, is_new, _ = upsert_sweep(sig, keys, admit,
                                        jnp.asarray(DICT_PROBES, jnp.int32))
    placed = admit & (slot >= 0)
    new = is_new & admit
    tgt_new = jnp.where(new, slot, cap)
    tgt_placed = jnp.where(placed, slot, cap)
    tick_col = jnp.full((n,), 1, jnp.int32) * d.tick
    return dataclasses.replace(
        d,
        sig=sig,
        psig=d.psig.at[tgt_new].set(psig, mode="drop"),
        eslot=d.eslot.at[tgt_new].set(eslot, mode="drop"),
        sslot=d.sslot.at[tgt_new].set(sslot, mode="drop"),
        dslot=d.dslot.at[tgt_new].set(dslot, mode="drop"),
        refcount=refcount.at[tgt_new].set(jnp.ones((n,), jnp.int32),
                                          mode="drop"),
        clock=jnp.where(evict, 0, d.clock).at[tgt_placed].set(
            tick_col, mode="drop"),
        n_entries=d.n_entries - n_evicted + jnp.sum(new.astype(jnp.int32)),
        evictions=d.evictions + n_evicted,
    )
