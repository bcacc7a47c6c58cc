"""Fused GRAPHPUSH commit kernel + incremental CSR snapshots.

Covers the PR-3 hot-path rewrite: Pallas-vs-jnp-oracle parity of the
fused upsert, the 6 -> 2 probe-loop contract of `ingest_step`, the
sweep's early exit (bit-identical to running its whole budget, in the
store, the dictionary and the sharded commit), the adaptive probe
budget under table pressure (hypothesis property: a key is only
dropped when its escalated probe window is genuinely exhausted), the
table-pressure -> controller back-pressure, and
bit-exact equivalence of `apply_delta` / `SnapshotMaintainer` against
full `build_snapshot` recompaction after N random commits.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.edge_table import from_raw_batch
from repro.core.transform import RawEdgeBatch
from repro.graphstore.store import (
    MAX_PROBES,
    count_probe_loops,
    ingest_step,
    init_store,
    probe_budget,
)
from repro.kernels import ops
from repro.kernels.upsert import (
    fused_upsert,
    fused_upsert_ref,
    probe_hash,
    rounds_needed,
)
from repro.query.snapshot import (
    SnapshotMaintainer,
    apply_delta,
    build_snapshot,
)


def _raw(src, dst, etype):
    n = len(src)
    return RawEdgeBatch(
        src=np.asarray(src, np.uint64), dst=np.asarray(dst, np.uint64),
        etype=np.asarray(etype, np.int32),
        src_type=np.zeros(n, np.int32), dst_type=np.zeros(n, np.int32),
        n_records=n,
    )


def _table(rng, n=256, n_keys=60, cap=512, n_types=3):
    src = rng.integers(1, n_keys, size=n)
    dst = rng.integers(1, n_keys, size=n)
    et = rng.integers(0, n_types, size=n)
    return from_raw_batch(_raw(src, dst, et), cap)


def _assert_snapshots_equal(got, want, msg=""):
    for f in dataclasses.fields(want):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, f.name)), np.asarray(getattr(want, f.name)),
            err_msg=f"{msg}{f.name}")


# ---------------------------------------------------------------------------
# fused upsert: kernel parity + invariants
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cap,n,probes", [(128, 64, 32), (512, 256, 64),
                                          (1024, 128, 128)])
def test_fused_upsert_kernel_matches_oracle(cap, n, probes, rng):
    keys = jnp.asarray(
        rng.choice(np.arange(1, 1 << 30, dtype=np.uint32), size=n,
                   replace=False))
    valid = jnp.asarray(rng.random(n) < 0.9)
    # pre-populate some slots so hits, claims and races all occur
    table = jnp.zeros((cap,), jnp.uint32)
    table = fused_upsert_ref(table, keys[: n // 2], valid[: n // 2],
                             jnp.int32(probes))[0]
    got = fused_upsert(table, keys, valid, jnp.int32(probes), interpret=True)
    want = fused_upsert_ref(table, keys, valid, jnp.int32(probes))
    assert len(got) == len(want) == 4
    for g, w, name in zip(got, want, ("table", "slot", "is_new", "rounds")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)


def test_fused_upsert_idempotent_and_consistent(rng):
    cap, n = 512, 256
    keys = jnp.asarray(
        rng.choice(np.arange(1, 1 << 30, dtype=np.uint32), size=n,
                   replace=False))
    valid = jnp.ones((n,), bool)
    table0 = jnp.zeros((cap,), jnp.uint32)
    table1, slot1, new1, _ = ops.fused_upsert(table0, keys, valid, MAX_PROBES)
    s1 = np.asarray(slot1)
    placed = s1 >= 0
    assert np.asarray(new1)[placed].all()  # empty table: every placed is new
    # placed keys occupy distinct slots holding exactly their key
    assert len(set(s1[placed])) == placed.sum()
    assert (np.asarray(table1)[s1[placed]] == np.asarray(keys)[placed]).all()
    # re-upsert: pure lookup — same slots, nothing new, table unchanged
    table2, slot2, new2, _ = ops.fused_upsert(table1, keys, valid, MAX_PROBES)
    np.testing.assert_array_equal(np.asarray(table2), np.asarray(table1))
    np.testing.assert_array_equal(np.asarray(slot2)[placed], s1[placed])
    assert not np.asarray(new2).any()


def test_probe_budget_escalates_with_load():
    cap = 1000
    assert int(probe_budget(jnp.int32(100), cap)) == MAX_PROBES
    assert int(probe_budget(jnp.int32(599), cap)) == MAX_PROBES
    assert int(probe_budget(jnp.int32(600), cap)) == 2 * MAX_PROBES
    assert int(probe_budget(jnp.int32(799), cap)) == 2 * MAX_PROBES
    assert int(probe_budget(jnp.int32(800), cap)) == 4 * MAX_PROBES


def test_high_load_drops_only_when_probing_exhausted():
    """Hypothesis property: fill a table to >= 0.8 load; the fused
    upsert must not drop a key while an empty slot remains inside its
    (adaptively escalated) probe window, placed keys stay retrievable,
    and escalation never drops more than the fixed seed budget."""
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    cap, chunk = 256, 64

    def fill(keys, adaptive: bool):
        table = jnp.zeros((cap,), jnp.uint32)
        placed_mask = np.zeros(len(keys), bool)
        placed, dropped = 0, []
        for lo in range(0, len(keys), chunk):
            part = keys[lo: lo + chunk]
            batch = np.zeros(chunk, np.uint32)
            batch[: len(part)] = part
            valid = jnp.arange(chunk) < len(part)
            bud = (probe_budget(jnp.int32(placed), cap) if adaptive
                   else jnp.int32(MAX_PROBES))
            table, slot, _, _ = ops.fused_upsert(
                table, jnp.asarray(batch), valid, bud)
            slot = np.asarray(slot)[: len(part)]
            placed_mask[lo: lo + len(part)] = slot >= 0
            placed += int((slot >= 0).sum())
            dropped += [(k, int(bud)) for k, s in zip(part, slot) if s < 0]
        return np.asarray(table), dropped, placed, placed_mask

    @settings(max_examples=10, deadline=None, derandomize=True)
    @given(seed=st.integers(0, 10_000), load=st.floats(0.8, 0.92))
    def check(seed, load):
        rng = np.random.default_rng(seed)
        keys = rng.choice(np.arange(1, 1 << 30, dtype=np.uint32),
                          size=int(cap * load), replace=False)
        table, dropped, placed, placed_mask = fill(keys, adaptive=True)
        # every drop is a genuine exhaustion: all probe-window slots
        # are occupied by OTHER keys (slots never free up, so checking
        # the final table is sound)
        for key, bud in dropped:
            cand = np.asarray(probe_hash(
                jnp.full((bud,), key, jnp.uint32), cap,
                jnp.arange(bud, dtype=jnp.int32)))
            window = table[cand]
            assert (window != 0).all() and (window != key).all(), \
                f"key {key} dropped with a free/own slot in its window"
        # placed keys are retrievable (upsert of them is a pure lookup)
        _, slot2, new2, _ = ops.fused_upsert(
            jnp.asarray(table), jnp.asarray(keys), jnp.asarray(placed_mask),
            probe_budget(jnp.int32(placed), cap))
        s2 = np.asarray(slot2)
        assert (s2 >= 0).sum() == placed
        assert not np.asarray(new2).any()
        # adaptive probing dominates the fixed seed budget
        _, dropped_fixed, _, _ = fill(keys, adaptive=False)
        assert len(dropped) <= len(dropped_fixed)

    check()


def _home(key: int, cap: int) -> int:
    """`probe_hash` round 0 for a uint32 key, in plain Python."""
    h = (key * 0x9E3779B9) & 0xFFFFFFFF
    return (h ^ (h >> 16)) % cap


def _oracle_rounds(table, keys, budget):
    """Plain linear probing, one key after another: the round at which
    each key is found or claims an empty slot, None when its `budget`
    slots hold other keys."""
    table, cap, rounds = list(table), len(table), []
    for k in keys:
        for r in range(budget):
            s = (_home(k, cap) + r) % cap
            if table[s] in (0, k):
                table[s] = k
                rounds.append(r + 1)
                break
        else:
            rounds.append(None)
    return rounds


@pytest.mark.parametrize("chains,want", [((1,), 1), ((1, 3), 3),
                                         ((1, 3, 7), 7),
                                         ((1, 3, 7, None), 8)])
def test_rounds_needed_matches_a_linear_probing_oracle(chains, want):
    """Keys whose probe chains are planted by occupied slots: a chain of
    c places its key at round c, and `None` is a key whose whole budget
    of 8 slots is taken, so it is dropped and counts as the budget."""
    cap, budget = 256, 8
    pool = iter(range(1, 1 << 20))
    table, keys, used = [0] * cap, [], set()
    for c in chains:
        span = budget if c is None else c - 1
        while True:  # a key whose chain lies clear of the others'
            k = next(pool)
            h = _home(k, cap)
            window = {(h + r) % cap for r in range(budget + 1)}
            if not window & used:
                break
        used |= window
        for r in range(span):  # occupy the chain with other keys
            table[(h + r) % cap] = (1 << 30) + len(used) * 64 + r
        keys.append(k)
    rounds = _oracle_rounds(table, keys, budget)
    assert rounds == list(chains)
    n = 16  # padded lanes are invalid
    kv = np.zeros(n, np.uint32)
    kv[: len(keys)] = keys
    valid = jnp.arange(n) < len(keys)
    _, slot, _, ran = ops.fused_upsert(jnp.asarray(table, jnp.uint32),
                                       jnp.asarray(kv), valid,
                                       jnp.int32(budget))
    got = rounds_needed(jnp.asarray(kv), slot, valid, cap, jnp.int32(budget))
    assert int(got) == int(ran) == want == max(r or budget for r in rounds)
    # no valid lane: nothing needed
    none = rounds_needed(jnp.asarray(kv), slot, jnp.zeros(n, bool), cap,
                         jnp.int32(budget))
    assert int(none) == 0


def test_ingest_step_counts_probe_rounds(rng):
    """The loops stop once their last lane is placed, so each runs the
    rounds it needs, within the budget, and the budget (`probe_rounds`)
    is reported unchanged; the rounds needed are the longest
    linear-probing walk from a key's home to the slot the commit gave
    it, past slots that hold other keys; the ingestor sums both sweeps
    into its `CommitRecord`."""
    from repro.core.ingestor import GraphIngestor

    cap = 1 << 8
    ing = GraphIngestor(init_store(cap, 1 << 10, key_dtype=jnp.uint32))
    longest = 0
    for _ in range(3):  # the store fills, so chains grow
        et = _table(rng, n=128, n_keys=120, cap=128)
        et = dataclasses.replace(et, node_ids=et.node_ids.astype(jnp.uint32),
                                 src=et.src.astype(jnp.uint32),
                                 dst=et.dst.astype(jnp.uint32))
        s = ing.push(et)["stats"]
        assert int(s["probe_rounds"]) == MAX_PROBES
        for sweep in ("node", "edge"):
            assert int(s[f"{sweep}_rounds_run"]) \
                == int(s[f"{sweep}_rounds_needed"]) <= MAX_PROBES
        table = np.asarray(ing.store.node_keys).tolist()
        walks = []
        for k, slot in zip(np.asarray(et.node_ids).tolist(),
                           np.asarray(s["nslot"]).tolist()):
            if slot < 0:
                continue
            r = 0
            while (_home(k, cap) + r) % cap != slot:
                assert table[(_home(k, cap) + r) % cap] not in (0, k)
                r += 1
            walks.append(r + 1)
        assert int(s["node_rounds_needed"]) == max(walks)
        longest = max(longest, max(walks))
        rec = ing.commits[-1]
        assert rec.rounds_run == (int(s["node_rounds_run"])
                                  + int(s["edge_rounds_run"]))
        assert rec.rounds_needed == (int(s["node_rounds_needed"])
                                     + int(s["edge_rounds_needed"]))
        assert 1 <= int(s["edge_rounds_needed"]) <= MAX_PROBES
    assert longest > 1  # some chain was walked


@jax.jit
def _full_budget_sweep(table_keys, keys, valid, n_probes):
    """The sweep without its early exit: every round of the budget runs,
    each lane resolved at the first round that hits its key or wins an
    empty slot.  The reference the early-exit sweep is held to."""
    cap, n = table_keys.shape[0], keys.shape[0]

    def body(i, carry):
        tk, slot, is_new, done = carry
        cand = probe_hash(keys, cap, jnp.full((n,), i, jnp.int32))
        cur = tk[cand]
        hit = (cur == keys) & valid & ~done
        empty = (cur == 0) & valid & ~done
        tk = tk.at[jnp.where(empty, cand, cap)].max(keys, mode="drop")
        won = empty & (tk[cand] == keys)
        placed = hit | won
        return (tk, jnp.where(placed, cand, slot), is_new | won,
                done | placed)

    tk, slot, is_new, _ = jax.lax.fori_loop(
        0, n_probes, body,
        (table_keys, jnp.full((n,), -1, jnp.int32), jnp.zeros((n,), bool),
         ~valid))
    return tk, slot, is_new


@pytest.mark.parametrize("load,n_valid,budget", [
    (0.2, 96, MAX_PROBES),          # low load: the sweep ends early
    (0.65, 96, 2 * MAX_PROBES),     # past 0.6: the budget doubles
    (0.95, 96, 4 * MAX_PROBES),     # saturated: lanes drop, budget runs out
    (0.2, 0, MAX_PROBES),           # no valid lane: no round runs
], ids=["low_load", "budget_doubled", "saturated_drops", "all_invalid"])
def test_early_exit_sweep_matches_full_budget_loop(load, n_valid, budget,
                                                   rng):
    """The sweep that stops once its last lane is placed gives the same
    table, slots and is_new bits as running its whole budget, and its
    trip count is the rounds that placed its last lane."""
    cap, n = 512, 128
    pool = rng.choice(np.arange(1, 1 << 30, dtype=np.uint32),
                      size=cap + n, replace=False)
    # fill to `load` with a budget of the whole table, so nothing drops
    old = pool[: int(cap * load)]
    table = _full_budget_sweep(jnp.zeros((cap,), jnp.uint32),
                               jnp.asarray(old), jnp.ones(len(old), bool),
                               jnp.int32(cap))[0]
    n_used = int((np.asarray(table) != 0).sum())
    assert n_used == len(old)
    assert int(probe_budget(jnp.int32(n_used), cap)) == budget
    # half the batch hits keys already stored, half is new; tail invalid
    keys = np.concatenate([rng.choice(old, n // 2, replace=False),
                           pool[cap: cap + n // 2]])
    valid = jnp.arange(n) < n_valid
    args = (table, jnp.asarray(keys), valid, jnp.int32(budget))
    tk, slot, is_new, rounds = ops.fused_upsert(*args)
    for g, w, name in zip((tk, slot, is_new), _full_budget_sweep(*args),
                          ("table", "slot", "is_new")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    assert int(rounds) == int(rounds_needed(args[1], slot, valid, cap,
                                            args[3]))
    dropped = int(np.sum(np.asarray(valid) & (np.asarray(slot) < 0)))
    if n_valid == 0:
        assert int(rounds) == 0
        assert (np.asarray(slot) == -1).all()
    elif load > 0.8:
        assert dropped > 0 and int(rounds) == budget
    else:
        assert dropped == 0 and 0 < int(rounds) < budget


def test_dict_admit_matches_full_budget_sweep(monkeypatch, rng):
    """The dictionary's admit sweep, which now stops early, leaves the
    same `PatternDictionary` as with the sweep that runs its budget out,
    across hits, new entries and aging eviction past the high-water
    mark."""
    from repro.compress import dictionary as dct

    cap, n = 256, 64
    d = dct.init_dictionary(cap, jnp.uint32)
    full = lambda *a: (*_full_budget_sweep(*a), None)  # noqa: E731
    pool = rng.choice(np.arange(1, 1 << 30, dtype=np.uint32), size=8 * n,
                      replace=False)
    prev = pool[:0]
    for step in range(8):
        fresh = pool[step * n: step * n + n - len(prev) // 2]
        keys = np.concatenate([prev[: len(prev) // 2], fresh])
        admit = jnp.asarray(rng.random(n) < 0.9)
        slots = [jnp.asarray(rng.integers(0, 1 << 12, n), jnp.int32)
                 for _ in range(3)]
        args = (d, jnp.asarray(keys), admit, *slots, jnp.asarray(keys))
        got = dct.dict_admit(*args, ttl=2)
        with monkeypatch.context() as m:
            m.setattr(dct, "upsert_sweep", full)
            want = dct.dict_admit.__wrapped__(*args, ttl=2, high_water=0.85)
        for f in dataclasses.fields(want):
            np.testing.assert_array_equal(
                np.asarray(getattr(got, f.name)),
                np.asarray(getattr(want, f.name)),
                err_msg=f"step {step}: {f.name}")
        d = dataclasses.replace(got, tick=got.tick + 3)  # age the entries
        prev = keys
    assert int(d.evictions) > 0  # past the high-water mark: eviction ran


_SHARDED = """
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.graphstore.store import init_store, make_distributed_ingest
from repro.kernels.upsert import probe_hash

D, n = 4, 2048
mesh = Mesh(np.array(jax.devices()[:D]), ("data",))
rng = np.random.default_rng(11)
src = jnp.asarray(rng.integers(1, 1 << 24, n), jnp.uint32)
dst = jnp.asarray(rng.integers(1, 1 << 24, n), jnp.uint32)
etype = jnp.asarray(rng.integers(0, 3, n), jnp.int32)
valid = jnp.ones(n, bool)
store = init_store(1 << 14, 1 << 13, key_dtype=jnp.uint32)
store, s = jax.jit(make_distributed_ingest(mesh))(store, src, dst, etype,
                                                  valid)

def walks(table):
    # one commit into an empty store: every stored key was placed by it,
    # at the round given by its distance from its home slot
    out = []
    for part in np.split(np.asarray(table), D):
        cap, at = len(part), np.flatnonzero(part)
        home = np.asarray(probe_hash(jnp.asarray(part[at]), cap,
                                     jnp.zeros(len(at), jnp.int32)))
        out.append(int(((at - home) % cap).max()) + 1)
    return out

print(json.dumps({"node_walks": walks(store.node_keys),
                  "edge_walks": walks(store.edge_keys),
                  **{k: int(s[k]) for k in (
                      "dropped_inserts", "node_rounds_run", "edge_rounds_run",
                      "node_rounds_needed", "edge_rounds_needed")}}))
"""


def test_distributed_ingest_reports_max_rounds_across_shards():
    """Each shard's sweeps stop on its own lanes, so shards run different
    numbers of rounds; the sharded commit reports the largest."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [src, os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", _SHARDED], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["dropped_inserts"] == 0
    for sweep in ("node", "edge"):
        per_shard = r[f"{sweep}_walks"]
        assert len(set(per_shard)) > 1  # the shards' trip counts differ
        assert r[f"{sweep}_rounds_run"] == r[f"{sweep}_rounds_needed"] \
            == max(per_shard) < MAX_PROBES


# ---------------------------------------------------------------------------
# ingest_step: structural + stats contracts
# ---------------------------------------------------------------------------


def test_commit_runs_exactly_two_probe_loops(rng):
    # the acceptance criterion of the fused rewrite: 6 -> 2 probe loops
    assert count_probe_loops(_table(rng)) == 2


def test_ingest_step_reports_pressure_stats(rng):
    store = init_store(1 << 10, 1 << 12)
    store, stats = ingest_step(store, _table(rng))
    assert int(stats["probe_rounds"]) == MAX_PROBES  # near-empty tables
    assert int(stats["dropped_inserts"]) == 0
    assert 0.0 < float(stats["node_load"]) < 0.1
    # re-ingesting the same batch creates nothing new but counts up
    before = int(np.asarray(store.edge_count).sum())
    store2, stats2 = ingest_step(store, _table(np.random.default_rng(0)))
    assert int(stats2["new_nodes"]) == 0 and int(stats2["new_edges"]) == 0
    assert int(np.asarray(store2.edge_count).sum()) == 2 * before
    # degree invariant survives the fused/slot-reuse path
    assert int(np.asarray(store2.node_degree).sum()) == 2 * int(store2.n_edges)


def test_ingest_step_escalates_probes_under_load(rng):
    # the budget is computed from the PRE-commit load factor
    store = init_store(256, 1 << 12)
    store, stats = ingest_step(store, _table(rng))
    assert int(stats["probe_rounds"]) == MAX_PROBES
    pressured = dataclasses.replace(store, n_nodes=jnp.int32(170))  # 0.66
    _, stats = ingest_step(pressured, _table(rng))
    assert int(stats["probe_rounds"]) == 2 * MAX_PROBES
    saturated = dataclasses.replace(store, n_nodes=jnp.int32(210))  # 0.82
    _, stats = ingest_step(saturated, _table(rng))
    assert int(stats["probe_rounds"]) == 4 * MAX_PROBES


def test_saturated_store_reports_drops(rng):
    store = init_store(64, 1 << 10)
    total_dropped = 0
    for _ in range(8):
        store, stats = ingest_step(
            store, _table(rng, n=256, n_keys=4000, cap=256))
        total_dropped += int(stats["dropped_inserts"])
    assert int(store.n_nodes) <= 64
    assert total_dropped > 0  # pressure signal fires when truly full


def test_controller_throttles_on_dropped_inserts():
    from repro.configs.paper_ingest import IngestConfig
    from repro.core.buffer import BufferController

    ctl = BufferController(IngestConfig(), spill_dir="/tmp/repro_test_pressure")
    assert ctl.decide(64.0, 0.0).action in ("push", "drain+push")
    ctl.perfmon.observe_pressure(0.97, 12)
    assert ctl.decide(64.0, 0.0).action == "throttle"
    # one-shot: the signal is consumed, the next tick retries the push
    assert ctl.decide(64.0, 0.0).action in ("push", "drain+push")


# ---------------------------------------------------------------------------
# incremental snapshots: apply_delta == build_snapshot, bit-exact
# ---------------------------------------------------------------------------


def test_apply_delta_matches_full_rebuild(rng):
    store = init_store(1 << 10, 1 << 12)
    snap = build_snapshot(store)
    for i in range(6):
        store, stats = ingest_step(store, _table(rng, n_keys=80))
        snap, unplaced = apply_delta(snap, stats["delta"])
        assert int(unplaced) == 0
        _assert_snapshots_equal(snap, build_snapshot(store),
                                msg=f"commit {i}: ")


def test_snapshot_maintainer_serves_exact_views(rng):
    store = init_store(1 << 10, 1 << 12)
    m = SnapshotMaintainer(max_pending=4)
    for i in range(9):
        store, stats = ingest_step(store, _table(rng, n_keys=70))
        m.absorb(None, stats)
        if i % 2 == 1:
            _assert_snapshots_equal(m.snapshot(store), build_snapshot(store),
                                    msg=f"query after commit {i}: ")
    assert m.delta_applies > 0
    assert m.full_builds >= 1  # the initial compaction


def test_snapshot_maintainer_rebuilds_on_overflow(rng):
    store = init_store(1 << 10, 1 << 12)
    m = SnapshotMaintainer(max_pending=2)
    m.snapshot(store)
    for _ in range(4):  # 4 pending > max_pending -> full rebuild
        store, stats = ingest_step(store, _table(rng))
        m.absorb(None, stats)
    _assert_snapshots_equal(m.snapshot(store), build_snapshot(store))
    assert m.full_builds == 2 and m.delta_applies == 0


def test_snapshot_maintainer_rebuilds_on_dangling(rng):
    # 16-node table saturates -> edges with unresolvable endpoints;
    # the maintainer must detect it and serve full rebuilds (exactness
    # beats incrementality)
    store = init_store(16, 1 << 10)
    m = SnapshotMaintainer()
    for i in range(4):
        store, stats = ingest_step(store, _table(rng, n=128, n_keys=500,
                                                 cap=128))
        m.absorb(None, stats)
        _assert_snapshots_equal(m.snapshot(store), build_snapshot(store),
                                msg=f"commit {i}: ")
    assert int(store.n_edges) > int(m.snapshot(store).n_edges)  # dangling


def test_query_sink_incremental_snapshot_end_to_end(tmp_path):
    from repro.api import GraphStoreSink, PipelineBuilder
    from repro.configs.paper_ingest import IngestConfig
    from repro.ingest.sources import BurstyTweetSource

    cfg = IngestConfig(store_nodes=1 << 13, store_edges=1 << 15)
    pipe = (PipelineBuilder(cfg)
            .with_source(BurstyTweetSource(seed=3, mean_rate=40.0))
            .with_sink(GraphStoreSink(node_cap=1 << 13, edge_cap=1 << 15))
            .with_query_sink(depth=2, width=128, answer_every=5, top_k=3)
            .spill_dir(str(tmp_path / "spill"))
            .build())
    pipe.run(max_ticks=12)
    snap1 = pipe.sink.snapshot()
    _assert_snapshots_equal(snap1, build_snapshot(pipe.store))
    pipe.run(max_ticks=8)
    snap2 = pipe.sink.snapshot()  # second query: delta path
    _assert_snapshots_equal(snap2, build_snapshot(pipe.store))
    m = pipe.sink.maintainer
    assert m.delta_applies > 0, "live query must not recompact every time"
