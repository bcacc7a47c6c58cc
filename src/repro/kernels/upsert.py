"""Pallas TPU kernel: fused lookup-or-insert for the open-addressing
graph-store tables (Algorithm 3 GRAPHPUSH commit hot path).

The seed committed a batch with a *lookup* sweep followed by an
*insert* sweep per table (plus two more lookups for degree updates) —
six MAX_PROBES-round gather/scatter loops per commit.  This kernel
fuses lookup-or-insert into ONE probe sweep per table: at each probe
round a lane either hits its key (slot found, not new), claims an
empty slot (scatter-max race, winners check back — slot found, new),
or keeps probing.  Because slots are never freed, a present key is
always hit before the first empty slot of its probe sequence, so the
fused sweep is bit-identical to lookup-then-insert.

The probe budget is *dynamic* (a traced scalar): the caller doubles it
as the table load factor grows (adaptive probing, ROADMAP "store
probing robustness").  The loop is a `while` that also ends once every
valid lane is resolved, so its trip count is the round that placed the
last lane, capped by the budget; a lane that is never placed keeps it
running to the whole budget.  A round after the last lane resolved
would change nothing (every lane is masked off and scatters to the
dropped index), so the early exit is bit-identical to running the
budget out.  The sweep returns its trip count beside its results.

`upsert_sweep` is the pure body shared verbatim by the Pallas kernel
and the jnp oracle `fused_upsert_ref` (repro.kernels.ref style), so
the two can never drift; tests assert bit-exactness anyway.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def probe_hash(keys: jax.Array, cap: int, i: jax.Array) -> jax.Array:
    """Linear-probing slot for `keys` at probe round `i` (splitmix mix)."""
    kd = keys.dtype
    c = jnp.asarray(0x9E3779B97F4A7C15 if kd == jnp.uint64 else 0x9E3779B9, kd)
    h = keys * c
    h = h ^ (h >> 16)
    return ((h.astype(jnp.uint32) + i.astype(jnp.uint32)) % jnp.uint32(cap)).astype(jnp.int32)


def rounds_needed(keys: jax.Array, slot: jax.Array, valid: jax.Array,
                  cap: int, n_probes: jax.Array) -> jax.Array:
    """Probe rounds a finished sweep needed: the largest over its placed
    lanes of the round that placed each, as ``((slot - probe_hash(keys,
    cap, 0)) mod cap) + 1``.  A valid lane that was dropped (``slot`` -1)
    counts as the whole budget ``n_probes``; 0 when no lane is valid.

    Read from the slots a sweep returned, independently of the sweep's
    loop: `upsert_sweep` resolves a lane at the first round that hits
    its key or wins an empty slot and stops after the last such round,
    so this equals the trip count it returns."""
    home = probe_hash(keys, cap, jnp.zeros(keys.shape, jnp.int32))
    placed = valid & (slot >= 0)
    rounds = jnp.where(placed, jnp.mod(slot - home, cap) + 1, 0)
    dropped = jnp.any(valid & (slot < 0))
    return jnp.maximum(jnp.max(rounds, initial=0),
                       jnp.where(dropped, jnp.asarray(n_probes, jnp.int32), 0))


def upsert_sweep(table_keys: jax.Array, keys: jax.Array, valid: jax.Array,
                 n_probes: jax.Array):
    """Single-pass fused upsert of UNIQUE keys (pre-deduplicated batch).

    Returns (table_keys', slot (int32, -1 = dropped), is_new (bool),
    rounds (int32)).  `n_probes` may be a traced scalar (adaptive probe
    budget).  Races for empty slots resolve by scatter-max; losers keep
    probing.  `rounds` is the loop's trip count: the round that placed
    the last valid lane, capped by `n_probes` (the whole budget when a
    lane is dropped, 0 when no lane is valid).
    """
    cap = table_keys.shape[0]
    n = keys.shape[0]

    def pending(carry):
        i, _, _, _, done = carry
        return (i < n_probes) & ~jnp.all(done)

    def body(carry):
        i, tk, slot, is_new, done = carry
        cand = probe_hash(keys, cap, jnp.full((n,), i, jnp.int32))
        cur = tk[cand]
        hit = (cur == keys) & valid & ~done
        empty = (cur == 0) & valid & ~done
        tk = tk.at[jnp.where(empty, cand, cap)].max(keys, mode="drop")
        won = empty & (tk[cand] == keys)
        placed = hit | won
        slot = jnp.where(placed, cand, slot)
        is_new = is_new | won
        done = done | placed
        return i + 1, tk, slot, is_new, done

    rounds, tk, slot, is_new, _ = jax.lax.while_loop(
        pending, body,
        (jnp.int32(0), table_keys, jnp.full((n,), -1, jnp.int32),
         jnp.zeros((n,), bool), ~valid))
    return tk, slot, is_new, rounds


@jax.jit
def fused_upsert_ref(table_keys: jax.Array, keys: jax.Array, valid: jax.Array,
                     n_probes: jax.Array):
    """jnp oracle, and what the main path runs on every backend
    (see repro.kernels.ops)."""
    return upsert_sweep(table_keys, keys, valid,
                        jnp.asarray(n_probes, jnp.int32))


def _upsert_kernel(probes_ref, table_ref, keys_ref, valid_ref,
                   table_out, slot_out, new_out, rounds_out):
    tk, slot, is_new, rounds = upsert_sweep(
        table_ref[...], keys_ref[...], valid_ref[...] != 0, probes_ref[0])
    table_out[...] = tk
    slot_out[...] = slot
    new_out[...] = is_new.astype(jnp.int32)
    rounds_out[0] = rounds


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_upsert(table_keys: jax.Array, keys: jax.Array, valid: jax.Array,
                 n_probes: jax.Array, interpret: bool = False):
    """Fused upsert through the Pallas kernel.

    table_keys (cap,) key dtype (0 = empty); keys (n,) unique batch;
    valid (n,) bool; n_probes scalar int32 (dynamic probe budget).
    Returns (table_keys', slot (int32, -1 = dropped), is_new (bool),
    rounds (int32, the sweep's trip count)).
    VMEM budget: table + batch keys resident (4 MB at cap = 1M uint32).
    """
    cap = table_keys.shape[0]
    n = keys.shape[0]
    probes = jnp.asarray(n_probes, jnp.int32).reshape(1)
    tk, slot, new_i, rounds = pl.pallas_call(
        _upsert_kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((cap,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((cap,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((cap,), table_keys.dtype),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        interpret=interpret,
    )(probes, table_keys, keys, valid.astype(jnp.int32))
    return tk, slot, new_i != 0, rounds[0]
