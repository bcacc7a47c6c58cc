"""repro.compress: pattern mining, dictionary, pattern-aware commits.

The load-bearing assertion is bit-exactness: committing the SAME
edge-table sequence through the raw path (`ingest_step`) and through
rewrite + `commit_compressed` must leave byte-identical stores (and
therefore byte-identical snapshots).  See the lemma in
repro/compress/stage.py's module docstring.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compress import DictionaryStage, dict_admit, dict_lookup, init_dictionary
from repro.compress.stage import CompressedCommit
from repro.core import compression as C
from repro.core.edge_table import EdgeTable, build_edge_table
from repro.graphstore.store import commit_compressed, ingest_step, init_store
from repro.kernels import ops
from repro.kernels import pattern_mine as PM


def _rand_edges(rng, n, cap, n_nodes=20):
    src = jnp.asarray(rng.integers(1, n_nodes, size=cap).astype(np.uint32))
    dst = jnp.asarray(rng.integers(1, n_nodes, size=cap).astype(np.uint32))
    et = jnp.asarray(rng.integers(1, 4, size=cap).astype(np.int32))
    valid = jnp.arange(cap) < n
    return src, dst, et, valid


# ---------------------------------------------------------------------------
# pattern mining: kernel parity + brute-force semantics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cap,n,pool", [(128, 100, 10), (256, 200, 40),
                                        (512, 512, 6)])
def test_pattern_mine_kernel_matches_oracle(rng, cap, n, pool):
    src, dst, et, valid = _rand_edges(rng, n, cap, n_nodes=pool)
    count = jnp.asarray(rng.integers(1, 4, size=cap).astype(np.int32))
    a = PM.pattern_mine(src, dst, et, count, valid, 3, 2, interpret=True)
    b = ops.pattern_mine(src, dst, et, count, valid, 3, 2)
    for ka, kb, name in zip(a, b, ("fan_out", "fan_in", "flags", "psig")):
        assert jnp.array_equal(ka, kb), f"{name} differs kernel vs oracle"


def test_pattern_mine_matches_numpy_bruteforce(rng):
    cap = 128
    src, dst, et, valid = _rand_edges(rng, 100, cap, n_nodes=12)
    count = jnp.asarray(rng.integers(1, 4, size=cap).astype(np.int32))
    star_min, hot_min = 3, 2
    fo, fi, flags, psig = ops.pattern_mine(
        src, dst, et, count, valid, star_min, hot_min)
    s, d, e, c, v = map(np.asarray, (src, dst, et, count, valid))
    fo, fi, flags, psig = map(np.asarray, (fo, fi, flags, psig))
    srcs = set(s[v].tolist())
    for i in range(cap):
        if not v[i]:
            assert fo[i] == 0 and fi[i] == 0 and flags[i] == 0
            continue
        exp_fo = int(np.sum(v & (s == s[i]) & (e == e[i])))
        exp_fi = int(np.sum(v & (d == d[i]) & (e == e[i])))
        assert fo[i] == exp_fo
        assert fi[i] == exp_fi
        chain = int(d[i]) in srcs and d[i] != s[i]
        exp_flags = ((exp_fo >= star_min) * PM.FLAG_STAR_OUT
                     + (exp_fi >= star_min) * PM.FLAG_STAR_IN
                     + chain * PM.FLAG_CHAIN
                     + (c[i] >= hot_min) * PM.FLAG_HOT)
        assert flags[i] == exp_flags
        assert (psig[i] != 0) == (exp_flags != 0)


def test_pattern_mine_star_burst():
    cap = 64
    # hub 7 fans out to 5 targets under one etype + 2 unrelated edges
    src = jnp.asarray([7, 7, 7, 7, 7, 1, 2] + [0] * 57, dtype=jnp.uint32)
    dst = jnp.asarray([10, 11, 12, 13, 14, 3, 4] + [0] * 57, dtype=jnp.uint32)
    et = jnp.ones((cap,), jnp.int32)
    count = jnp.ones((cap,), jnp.int32)
    valid = jnp.arange(cap) < 7
    fo, fi, flags, psig = ops.pattern_mine(src, dst, et, count, valid, 4, 99)
    fo, flags, psig = map(np.asarray, (fo, flags, psig))
    assert (fo[:5] == 5).all()
    assert all(flags[i] & PM.FLAG_STAR_OUT for i in range(5))
    assert flags[5] == 0 and flags[6] == 0
    # all five star members share one pattern signature (the hub's)
    assert len(set(psig[:5].tolist())) == 1 and psig[0] != 0


def test_pattern_mine_cascade_chain():
    cap = 64
    # relay chain 1 -> 2 -> 3 -> 4: edges whose dst re-appears as a src
    src = jnp.asarray([1, 2, 3] + [0] * 61, dtype=jnp.uint32)
    dst = jnp.asarray([2, 3, 4] + [0] * 61, dtype=jnp.uint32)
    et = jnp.ones((cap,), jnp.int32)
    count = jnp.ones((cap,), jnp.int32)
    valid = jnp.arange(cap) < 3
    _, _, flags, _ = ops.pattern_mine(src, dst, et, count, valid, 99, 99)
    flags = np.asarray(flags)
    assert flags[0] & PM.FLAG_CHAIN  # dst=2 is a source
    assert flags[1] & PM.FLAG_CHAIN  # dst=3 is a source
    assert flags[2] == 0  # dst=4 is terminal


# ---------------------------------------------------------------------------
# satellite: tree_flatten regression (astuple recursion bug)
# ---------------------------------------------------------------------------


def test_compressed_batch_tree_flatten_roundtrip(rng):
    src, dst, et, valid = _rand_edges(rng, 50, 64)
    comp, _ = C.compress_edges(src, dst, et, valid)
    leaves, treedef = jax.tree_util.tree_flatten(comp)
    assert len(leaves) == 6
    # the flatten must hand back the field objects THEMSELVES
    assert leaves[0] is comp.keys
    comp2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(comp2, C.CompressedBatch)
    for f in dataclasses.fields(comp):
        assert jnp.array_equal(getattr(comp, f.name), getattr(comp2, f.name))


@pytest.mark.parametrize("cls", [C.CompressedBatch, EdgeTable])
def test_tree_flatten_preserves_partition_spec_leaves(cls):
    """The astuple() bug: a PartitionSpec (a tuple subclass) leaf came
    back a plain tuple, so sharding-spec pytrees shaped like the batch
    silently lost their spec-ness."""
    from jax.sharding import PartitionSpec as P

    spec = cls(*[P("x") for _ in range(len(dataclasses.fields(cls)))])
    leaves, treedef = jax.tree_util.tree_flatten(
        spec, is_leaf=lambda x: isinstance(x, P))
    assert all(isinstance(l, P) for l in leaves)
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert all(isinstance(getattr(rebuilt, f.name), P)
               for f in dataclasses.fields(cls))


# ---------------------------------------------------------------------------
# satellite: bijective uint64 key packing
# ---------------------------------------------------------------------------


def test_mix_keys_uint64_bijective_when_ids_fit():
    with jax.enable_x64(True):
        rng = np.random.default_rng(7)
        n = 4096
        src = rng.integers(0, 1 << C.PACK_SRC_BITS, n, dtype=np.uint64)
        dst = rng.integers(0, 1 << C.PACK_DST_BITS, n, dtype=np.uint64)
        et = rng.integers(0, 1 << C.PACK_ETYPE_BITS, n, dtype=np.int64)
        keys = np.asarray(C.mix_keys(jnp.asarray(src), jnp.asarray(dst),
                                     jnp.asarray(et, jnp.int32)))
        triples = set(zip(src.tolist(), dst.tolist(), et.tolist()))
        # bijective: exactly one key per distinct triple, and the
        # packing is exact (decodable)
        assert len(set(keys.tolist())) == len(triples)
        assert ((keys >> np.uint64(62)) == 1).all()  # pack tag, not hash
        back_src = (keys >> np.uint64(C.PACK_DST_BITS + C.PACK_ETYPE_BITS)) \
            & np.uint64((1 << C.PACK_SRC_BITS) - 1)
        back_dst = (keys >> np.uint64(C.PACK_ETYPE_BITS)) \
            & np.uint64((1 << C.PACK_DST_BITS) - 1)
        back_et = keys & np.uint64((1 << C.PACK_ETYPE_BITS) - 1)
        assert (back_src == src).all()
        assert (back_dst == dst).all()
        assert (back_et == et.astype(np.uint64)).all()


def test_mix_keys_uint64_wide_ids_fall_back_to_hash():
    with jax.enable_x64(True):
        wide = jnp.asarray(np.asarray([1 << 40, 5], np.uint64))
        dst = jnp.asarray(np.asarray([3, 1 << 50], np.uint64))
        et = jnp.zeros((2,), jnp.int32)
        keys = np.asarray(C.mix_keys(wide, dst, et))
        # hash domain is tagged with bit 63: can never alias a packed key
        assert ((keys >> np.uint64(63)) == 1).all()


def test_mix_keys_uint32_unchanged_by_pack_path(rng):
    src, dst, et, _ = _rand_edges(rng, 64, 64)
    keys = C.mix_keys(src, dst, et)
    assert keys.dtype == jnp.uint32
    assert (np.asarray(keys) != 0).all()  # 0 is the empty-slot marker


# ---------------------------------------------------------------------------
# dictionary lifecycle
# ---------------------------------------------------------------------------


def test_dictionary_miss_admit_hit_cycle(rng):
    src, dst, et, valid = _rand_edges(rng, 40, 64, n_nodes=50)
    keys = C.mix_keys(src, dst, et)
    d = init_dictionary(256, keys.dtype)
    d, hit, es, ss, ds, slot = dict_lookup(d, keys, valid)
    assert int(hit.sum()) == 0  # cold dictionary: all misses
    eslot = jnp.where(valid, jnp.arange(64, dtype=jnp.int32), -1)
    d = dict_admit(d, keys, valid, eslot, eslot + 100, eslot + 200,
                   jnp.where(valid, keys, 0))
    d, hit, es, ss, ds, slot = dict_lookup(d, keys, valid)
    n_unique = int(C.dedup_with_counts(keys, valid).n_unique)
    assert int(hit.sum()) == 40  # every valid lane hits now
    assert int(d.n_entries) == n_unique
    # bindings come back exactly as cached
    hv = np.asarray(hit)
    assert (np.asarray(es)[hv] == np.asarray(eslot)[hv]).all()
    assert (np.asarray(ss)[hv] == np.asarray(eslot)[hv] + 100).all()
    assert (np.asarray(ds)[hv] == np.asarray(eslot)[hv] + 200).all()


def test_dictionary_hit_rate_monotone_on_cascade_replay(rng):
    """Replaying the same cascade makes the hit rate non-decreasing:
    round 1 is all misses, later rounds reference what was admitted."""
    cap = 128
    # star-heavy batch: two hubs + chain, so mining admits everything
    hub = np.concatenate([np.full(20, 3), np.full(20, 5)])
    src = jnp.asarray(np.pad(hub, (0, cap - 40)).astype(np.uint32))
    dst = jnp.asarray(np.pad(np.arange(10, 50), (0, cap - 40)).astype(np.uint32))
    et = jnp.ones((cap,), jnp.int32)
    valid = jnp.arange(cap) < 40
    table = build_edge_table(src, dst, et, valid)
    stage = DictionaryStage(capacity=512, star_min=3, hot_min=1)
    store = init_store(1 << 10, 1 << 11)
    rates = []
    for _ in range(4):
        cc = stage.rewrite(table)
        store, s = commit_compressed(store, cc)
        stage.observe_commit(cc, s)
        rates.append(float(s["dict_hit_rate"]))
    assert rates[0] == 0.0
    assert all(b >= a for a, b in zip(rates, rates[1:]))
    assert rates[-1] > 0.5  # replayed batch is nearly all references


def test_dictionary_shared_across_batches(rng):
    """An edge admitted in batch 1 is a reference in batch 2 even when
    batch 2 is a different table (dictionary survives across batches)."""
    cap = 64
    src = jnp.asarray([9] * 6 + [0] * 58, dtype=jnp.uint32)
    dst = jnp.asarray(list(range(20, 26)) + [0] * 58, dtype=jnp.uint32)
    et = jnp.ones((cap,), jnp.int32)
    t1 = build_edge_table(src, dst, et, jnp.arange(cap) < 6)
    # batch 2 = three of those edges + three fresh ones
    src2 = jnp.asarray([9, 9, 9, 1, 2, 3] + [0] * 58, dtype=jnp.uint32)
    dst2 = jnp.asarray([20, 21, 22, 40, 41, 42] + [0] * 58, dtype=jnp.uint32)
    t2 = build_edge_table(src2, dst2, et, jnp.arange(cap) < 6)
    stage = DictionaryStage(capacity=256, star_min=3, hot_min=1)
    store = init_store(1 << 10, 1 << 11)
    cc1 = stage.rewrite(t1)
    store, s1 = commit_compressed(store, cc1)
    stage.observe_commit(cc1, s1)
    cc2 = stage.rewrite(t2)
    store, s2 = commit_compressed(store, cc2)
    assert int(s1["dict_refs"]) == 0
    assert int(s2["dict_refs"]) == 3


# ---------------------------------------------------------------------------
# bit-exactness: raw path vs pattern-aware path
# ---------------------------------------------------------------------------


def test_commit_compressed_bit_exact_store_and_snapshot(rng):
    from repro.query.snapshot import build_snapshot

    node_cap, edge_cap = 1 << 11, 1 << 12
    batches = []
    for _ in range(8):
        src, dst, et, valid = _rand_edges(rng, 110, 128, n_nodes=60)
        batches.append(build_edge_table(src, dst, et, valid))
    batches = batches + batches  # replay -> dictionary hits in round 2

    store_a = init_store(node_cap, edge_cap)
    store_b = init_store(node_cap, edge_cap)
    stage = DictionaryStage(capacity=512, star_min=3, hot_min=1)
    total_refs = 0
    for et in batches:
        store_a, _ = ingest_step(store_a, et)
        cc = stage.rewrite(et)
        store_b, s = commit_compressed(store_b, cc)
        stage.observe_commit(cc, s)
        total_refs += int(s["dict_refs"])
    assert total_refs > 0  # the compressed path actually referenced
    for f in dataclasses.fields(store_a):
        a, b = getattr(store_a, f.name), getattr(store_b, f.name)
        assert jnp.array_equal(a, b), f"store field {f.name} diverged"
    snap_a, snap_b = build_snapshot(store_a), build_snapshot(store_b)
    for f in dataclasses.fields(snap_a):
        a, b = getattr(snap_a, f.name), getattr(snap_b, f.name)
        assert jnp.array_equal(a, b), f"snapshot field {f.name} diverged"


def _distinct_edges(rng, n, lo, cap):
    """Raw lanes of `n` edges between fresh ids above `lo` (sources and
    targets apart, so the raw and the dictionary path see the same
    nodes), padded to `cap`."""
    src = rng.integers(lo + 1, lo + 4 * cap, size=cap).astype(np.uint32)
    dst = rng.integers(lo + 4 * cap, lo + 8 * cap, size=cap).astype(np.uint32)
    ety = rng.integers(1, 4, size=cap).astype(np.int32)
    return src, dst, ety, np.arange(cap) < n


def _tables(rng, cap, share):
    """Two tables of one capacity: a cold one, then one whose edges are
    none, half or all of the first's."""
    n = 3 * cap // 4
    a = _distinct_edges(rng, n, 0, cap)
    fresh = _distinct_edges(rng, n, 8 * cap, cap)
    keep = {"none": 0, "partial": n // 2, "all": n}[share]
    b = tuple(np.concatenate([x[:keep], y[keep:]]) for x, y in zip(a, fresh))
    return [build_edge_table(*map(jnp.asarray, t)) for t in (a, b)]


def _own_cache(jitted, **kw):
    """`jitted` again, with a program cache of its own: jit shares one
    cache among the wrappers of one function."""
    inner = jitted.__wrapped__
    return jax.jit(functools.wraps(inner)(lambda *a, **k: inner(*a, **k)),
                   **kw)


@pytest.mark.parametrize("share", ["none", "partial", "all"])
@pytest.mark.parametrize("cap", [64, 512, 8192])
def test_dictionary_path_is_bit_exact_with_one_program_per_capacity(
        rng, monkeypatch, cap, share):
    """The dictionary path leaves the raw path's store and snapshot at
    every share of hits, and its programs depend on the table's capacity
    alone: one rewrite, one commit and one admission program serve a
    cold table and a table with any share of hits."""
    from repro.compress import stage as stage_mod
    from repro.graphstore import store as store_mod
    from repro.query.snapshot import build_snapshot

    fresh = {
        "rewrite_table": _own_cache(stage_mod.rewrite_table,
                                    static_argnames=("star_min", "hot_min")),
        "admit_committed": _own_cache(stage_mod.admit_committed,
                                      static_argnames=("ttl",)),
    }
    for name, fn in fresh.items():
        monkeypatch.setattr(stage_mod, name, fn)
    commit = _own_cache(store_mod.commit_compressed)

    tables = _tables(rng, cap, share)
    store_a = store_b = init_store(1 << 17, 1 << 16)
    stage = DictionaryStage(capacity=4 * cap, star_min=1, hot_min=1)
    refs = []
    for et in tables:
        store_a, _ = ingest_step(store_a, et)
        cc = stage.rewrite(et)
        store_b, s = commit(store_b, cc)
        stage.observe_commit(cc, s)
        refs.append(int(s["dict_refs"]))
        assert int(s["dropped_inserts"]) == 0
    n_b = int(tables[1].n_edges)
    assert refs[0] == 0
    if share == "none":
        assert refs[1] == 0
    elif share == "partial":
        assert 0 < refs[1] < n_b
    else:
        assert refs[1] == n_b
    for f in dataclasses.fields(store_a):
        a, b = getattr(store_a, f.name), getattr(store_b, f.name)
        assert jnp.array_equal(a, b), f"store field {f.name} diverged"
    snap_a, snap_b = build_snapshot(store_a), build_snapshot(store_b)
    for f in dataclasses.fields(snap_a):
        a, b = getattr(snap_a, f.name), getattr(snap_b, f.name)
        assert jnp.array_equal(a, b), f"snapshot field {f.name} diverged"
    assert commit._cache_size() == 1
    for name, fn in fresh.items():
        assert fn._cache_size() == 1, name


def test_rewrite_pulls_nothing_to_the_host(rng, monkeypatch):
    """`rewrite` chooses no shape from data: it reads no device value on
    the host, on a cold dictionary or with hits."""
    from jax._src.array import ArrayImpl

    tables = _tables(rng, 128, "partial")
    stage = DictionaryStage(capacity=512, star_min=1, hot_min=1)
    store = init_store(1 << 12, 1 << 12)
    pulls = []
    value = ArrayImpl._value

    def spy(self):
        pulls.append(self.shape)
        return value.fget(self)

    for et in tables:
        monkeypatch.setattr(ArrayImpl, "_value", property(spy))
        cc = stage.rewrite(et)
        monkeypatch.setattr(ArrayImpl, "_value", value)
        store, s = commit_compressed(store, cc)
        stage.observe_commit(cc, s)
    assert pulls == []
    assert int(s["dict_refs"]) > 0  # the second rewrite had hits


def test_commit_compressed_accounting(rng):
    """Stats keep full-batch semantics: batch_edges counts references
    too (rho comparable to the raw path), instructions do not."""
    src, dst, et, valid = _rand_edges(rng, 60, 64, n_nodes=30)
    table = build_edge_table(src, dst, et, valid)
    stage = DictionaryStage(capacity=256, star_min=3, hot_min=1)
    store = init_store(1 << 10, 1 << 11)
    cc1 = stage.rewrite(table)
    store, s1 = commit_compressed(store, cc1)
    stage.observe_commit(cc1, s1)
    cc2 = stage.rewrite(table)
    store, s2 = commit_compressed(store, cc2)
    assert int(s1["batch_edges"]) == int(s2["batch_edges"]) == int(table.n_edges)
    assert int(s2["dict_refs"]) > 0
    # a reference costs 1 instruction < the 1 edge + <=2 nodes it replaces
    assert int(s2["instructions"]) < int(s1["instructions"])
    assert float(cc2.compression_ratio()) < float(cc1.compression_ratio()) < 1.0


# ---------------------------------------------------------------------------
# property tests (hypothesis)
# ---------------------------------------------------------------------------


try:  # hypothesis when available; deterministic fallback otherwise
    from hypothesis import given, settings, strategies as st

    _settings = dict(max_examples=25, deadline=None)
except ImportError:  # pragma: no cover - environment without hypothesis
    class _IntStrategy:
        def __init__(self, lo, hi):
            self.lo, self.hi = lo, hi

        def sample(self, rng):
            return int(rng.integers(self.lo, self.hi + 1))

    class _TupleStrategy:
        def __init__(self, parts):
            self.parts = parts

        def sample(self, rng):
            return tuple(p.sample(rng) for p in self.parts)

    class _ListStrategy:
        def __init__(self, elem, lo, hi):
            self.elem, self.lo, self.hi = elem, lo, hi

        def sample(self, rng):
            n = int(rng.integers(self.lo, self.hi + 1))
            return [self.elem.sample(rng) for _ in range(n)]

    class st:  # noqa: N801 - mimic the hypothesis surface used above
        @staticmethod
        def integers(min_value, max_value):
            return _IntStrategy(min_value, max_value)

        @staticmethod
        def tuples(*parts):
            return _TupleStrategy(parts)

        @staticmethod
        def lists(elem, min_size, max_size):
            return _ListStrategy(elem, min_size, max_size)

    def settings(**kw):
        return lambda fn: fn

    def given(**strategies):
        def deco(fn):
            def run():
                rng = np.random.default_rng(0)
                for _ in range(25):
                    fn(**{k: s.sample(rng) for k, s in strategies.items()})

            run.__name__ = fn.__name__
            return run

        return deco

    _settings = {}


@settings(**_settings)
@given(data=st.lists(st.integers(min_value=1, max_value=60),
                     min_size=1, max_size=100))
def test_dedup_idempotent(data):
    """Dedup of a dedup's unique keys is a fixed point: same uniques,
    every count 1."""
    cap = 128
    keys = jnp.asarray(np.pad(np.asarray(data, np.uint32), (0, cap - len(data))))
    valid = jnp.arange(cap) < len(data)
    once = C.dedup_with_counts(keys, valid)
    twice = C.dedup_with_counts(once.keys, once.valid)
    assert int(twice.n_unique) == int(once.n_unique)
    assert jnp.array_equal(twice.keys, once.keys)
    n = int(once.n_unique)
    assert (np.asarray(twice.counts)[:n] == 1).all()


@settings(**_settings)
@given(
    pairs=st.lists(st.tuples(st.integers(1, 30), st.integers(1, 30)),
                   min_size=1, max_size=100),
)
def test_compression_ratio_in_unit_interval(pairs):
    """Fig. 13 ratio is always in (0, 1] — dedup can only help."""
    cap = 128
    n = len(pairs)
    src = jnp.asarray(np.pad([a for a, _ in pairs], (0, cap - n)).astype(np.uint32))
    dst = jnp.asarray(np.pad([b for _, b in pairs], (0, cap - n)).astype(np.uint32))
    table = build_edge_table(src, dst, jnp.ones((cap,), jnp.int32),
                             jnp.arange(cap) < n)
    ratio = float(table.compression_ratio())
    assert 0.0 < ratio <= 1.0
    # the rewrite's ratio (references cost 1 instruction) never exceeds it
    stage = DictionaryStage(capacity=128, star_min=3, hot_min=1)
    cc = stage.rewrite(table)
    assert 0.0 < float(cc.compression_ratio()) <= ratio + 1e-6
