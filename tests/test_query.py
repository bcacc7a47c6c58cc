"""Query subsystem: sketch bounds, kernel parity, CSR snapshot
round-trip, engine-vs-brute-force, and the end-to-end pipeline demo."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.edge_table import from_raw_batch
from repro.core.transform import RawEdgeBatch
from repro.graphstore.store import init_store, ingest_step
from repro.query import (
    build_snapshot,
    degree_distribution,
    edge_lookup,
    init_sketch,
    k_hop,
    sketch_degree,
    sketch_edge_weight,
    sketch_heavy_hitters,
    sketch_update,
    top_k_degree,
    triangle_count,
)
from repro.query.sketch import _merge_top_k, node_hash


def _raw(src, dst, etype):
    n = len(src)
    return RawEdgeBatch(
        src=np.asarray(src, np.uint64), dst=np.asarray(dst, np.uint64),
        etype=np.asarray(etype, np.int32),
        src_type=np.zeros(n, np.int32), dst_type=np.zeros(n, np.int32),
        n_records=n,
    )


def _table(rng, n=256, n_keys=50, cap=512, n_types=3):
    src = rng.integers(1, n_keys, size=n)
    dst = rng.integers(1, n_keys, size=n)
    et = rng.integers(0, n_types, size=n)
    return src, dst, et, from_raw_batch(_raw(src, dst, et), cap)


def _ingest_batches(rng, store, batches=5, n=256, n_keys=80):
    """Ingest several batches; returns (store, exact edge-count dict)."""
    want = {}
    for _ in range(batches):
        src, dst, et, tbl = _table(rng, n=n, n_keys=n_keys)
        for s, d, t in zip(src, dst, et):
            want[(int(s), int(d), int(t))] = want.get((int(s), int(d), int(t)), 0) + 1
        store, _ = ingest_step(store, tbl)
    return store, want


def _snapshot_edges(snap):
    """Brute-force extraction: {(src_key, dst_key, etype): count}."""
    ncap = snap.node_cap
    er, ec = np.asarray(snap.edge_row), np.asarray(snap.edge_col)
    live = er < ncap
    nk = np.asarray(snap.node_key)
    tt, cc = np.asarray(snap.edge_type), np.asarray(snap.edge_count)
    out = {}
    for r, c, t, cnt in zip(er[live], ec[live], tt[live], cc[live]):
        key = (int(nk[r]), int(nk[c]), int(t))
        assert key not in out, f"edge {key} appears twice in the snapshot"
        out[key] = int(cnt)
    return out


# ---------------------------------------------------------------------------
# sketch: Pallas kernel parity + CMS guarantees
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("depth,width,n", [(2, 128, 64), (4, 128, 256), (3, 256, 512)])
def test_sketch_kernel_matches_oracle(depth, width, n, rng):
    from repro.kernels import ops
    from repro.kernels.sketch import sketch_scatter

    ew = jnp.asarray(rng.integers(0, 50, size=(depth, width, width)).astype(np.int32))
    od = jnp.asarray(rng.integers(0, 50, size=(depth, width)).astype(np.int32))
    idg = jnp.asarray(rng.integers(0, 50, size=(depth, width)).astype(np.int32))
    r = jnp.asarray(rng.integers(0, width, size=(depth, n)).astype(np.int32))
    c = jnp.asarray(rng.integers(0, width, size=(depth, n)).astype(np.int32))
    cnt = jnp.asarray(rng.integers(0, 5, size=n).astype(np.int32))
    got = sketch_scatter(ew, od, idg, r, c, cnt, interpret=True)
    want = ops.sketch_scatter(ew, od, idg, r, c, cnt)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_sketch_update_kernel_path_bit_exact(rng):
    """The Pallas scatter kernel (interpret mode) reproduces the sketch
    cells `sketch_update` writes through its XLA scatter."""
    from repro.kernels.sketch import sketch_scatter

    _, _, _, tbl = _table(rng)
    sk0 = init_sketch(depth=4, width=128)
    a = sketch_update(sk0, tbl)
    cnt = jnp.where(tbl.edge_valid, tbl.count, 0).astype(jnp.int32)
    b = sketch_scatter(sk0.edge_w, sk0.out_deg, sk0.in_deg,
                       node_hash(tbl.src, 4, 128), node_hash(tbl.dst, 4, 128),
                       cnt, interpret=True)
    for name, want in zip(("edge_w", "out_deg", "in_deg"), b):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(want), err_msg=name)


def test_sketch_upper_bounds_and_tracks_exact(rng):
    src, dst, et, tbl = _table(rng, n=512, n_keys=60, cap=1024)
    sk = sketch_update(init_sketch(depth=4, width=256), tbl)
    keys = np.unique(np.concatenate([src, dst]))
    est = np.asarray(sketch_degree(sk, jnp.asarray(keys, sk.hh_keys.dtype)))
    exact = np.asarray([(src == u).sum() + (dst == u).sum() for u in keys])
    assert (est >= exact).all()
    n_total = int(sk.n_updates)
    assert (est - exact).mean() <= max(2.0, 8.0 * n_total / 256)

    q = rng.integers(0, 512, size=64)
    ew = np.asarray(sketch_edge_weight(
        sk, jnp.asarray(src[q], sk.hh_keys.dtype), jnp.asarray(dst[q], sk.hh_keys.dtype)))
    exact_ew = np.asarray([((src == s) & (dst == d)).sum()
                           for s, d in zip(src[q], dst[q])])
    assert (ew >= exact_ew).all()
    assert (ew - exact_ew).mean() <= max(2.0, 8.0 * n_total / 256)


def test_sketch_property_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=15, deadline=None)
    @given(
        edges=st.lists(
            st.tuples(st.integers(1, 30), st.integers(1, 30), st.integers(0, 2)),
            min_size=1, max_size=100),
    )
    def check(edges):
        src = [e[0] for e in edges]
        dst = [e[1] for e in edges]
        et = [e[2] for e in edges]
        tbl = from_raw_batch(_raw(src, dst, et), 128)
        sk = sketch_update(init_sketch(depth=4, width=256), tbl)
        keys = sorted({*src, *dst})
        est = np.asarray(sketch_degree(sk, jnp.asarray(keys, sk.hh_keys.dtype)))
        exact = np.asarray([sum(s == u for s in src) + sum(d == u for d in dst)
                            for u in keys])
        assert (est >= exact).all()
        # degree sketch of distinct keys tracks exact closely at this load
        assert (est - exact).mean() <= max(2.0, 8.0 * len(edges) / 256)

    check()


def test_merge_top_k_keeps_heaviest():
    hk = jnp.asarray([10, 11, 0, 0], jnp.uint32)
    hc = jnp.asarray([5, 3, 0, 0], jnp.int32)
    ck = jnp.asarray([11, 12, 13, 0], jnp.uint32)
    cc = jnp.asarray([7, 9, 1, -1], jnp.int32)
    keys, counts = _merge_top_k(hk, hc, ck, cc)
    got = dict(zip(np.asarray(keys).tolist(), np.asarray(counts).tolist()))
    got.pop(0, None)
    # 11 deduplicates to its max estimate; top-4 of {10:5, 11:7, 12:9, 13:1}
    assert got == {12: 9, 11: 7, 10: 5, 13: 1}


@pytest.mark.parametrize("seed,m", [(0, 4), (1, 64), (2, 256), (3, 1000)])
def test_merge_top_k_matches_dict_oracle(seed, m):
    """Duplicate candidates, keys shared with the table, empty slots and
    tied counts: the merge equals dedup-by-max then the top K ordered by
    (count desc, key asc), as a plain dict computes it."""
    r = np.random.default_rng(seed)
    K = 16
    hk = r.choice(np.arange(1, 60), size=K, replace=False).astype(np.uint32)
    hk[r.random(K) < 0.25] = 0
    hc = np.where(hk != 0, r.integers(0, 6, size=K), 0).astype(np.int32)
    ck = r.integers(0, 60, size=m).astype(np.uint32)
    cc = np.where(ck != 0, r.integers(-1, 6, size=m), -1).astype(np.int32)
    best = {}
    for k, c in zip(np.concatenate([hk, ck]).tolist(),
                    np.concatenate([hc, cc]).tolist()):
        if k != 0:
            best[k] = max(best.get(k, -1), c)
    want = [(k, c) for k, c in sorted(best.items(), key=lambda kc: (-kc[1], kc[0]))
            if c > 0][:K]
    keys, counts = _merge_top_k(jnp.asarray(hk), jnp.asarray(hc),
                                jnp.asarray(ck), jnp.asarray(cc))
    got = list(zip(np.asarray(keys).tolist(), np.asarray(counts).tolist()))
    assert got == want + [(0, 0)] * (K - len(want))


def test_sketch_heavy_hitters_find_hot_nodes(rng):
    # one node participates in half of all edges
    n = 512
    src = rng.integers(2, 40, size=n)
    src[: n // 2] = 1
    dst = rng.integers(2, 40, size=n)
    tbl = from_raw_batch(_raw(src, dst, np.zeros(n, np.int32)), 1024)
    sk = sketch_update(init_sketch(depth=4, width=256, hh_slots=32), tbl)
    hk, hc = sketch_heavy_hitters(sk, 3)
    assert int(np.asarray(hk)[0]) == 1
    assert int(np.asarray(hc)[0]) >= n // 2


# ---------------------------------------------------------------------------
# store invariants (regression: -1 scatter targets used to WRAP to the
# last slot under mode="drop", corrupting counts/degrees/last edge)
# ---------------------------------------------------------------------------


def test_store_degree_and_count_invariants(rng):
    store, want = _ingest_batches(rng, init_store(1 << 10, 1 << 12), batches=6)
    nd = np.asarray(store.node_degree)
    assert nd.sum() == 2 * int(store.n_edges)
    ud = {}
    for (s, d, _t) in want:
        ud[s] = ud.get(s, 0) + 1
        ud[d] = ud.get(d, 0) + 1
    assert nd.max() == max(ud.values())
    assert int(np.asarray(store.edge_count).sum()) == sum(want.values())


# ---------------------------------------------------------------------------
# snapshot: CSR round-trip
# ---------------------------------------------------------------------------


def test_snapshot_roundtrip(rng):
    store, want = _ingest_batches(rng, init_store(1 << 10, 1 << 12), batches=5)
    snap = build_snapshot(store)
    assert int(snap.n_nodes) == int(store.n_nodes)
    assert int(snap.n_edges) == int(store.n_edges)
    got = _snapshot_edges(snap)  # asserts each edge appears exactly once
    assert got == want
    # CSR structure: indptr row sums == per-row edge counts, cols sorted
    indptr = np.asarray(snap.indptr)
    er, ec = np.asarray(snap.edge_row), np.asarray(snap.edge_col)
    live = er < snap.node_cap
    assert indptr[-1] == live.sum()
    for r in range(int(snap.n_nodes)):
        lo, hi = indptr[r], indptr[r + 1]
        assert (er[lo:hi] == r).all()
        assert (np.diff(ec[lo:hi]) >= 0).all()
    # node metadata preserved under the sort
    ud = {}
    for (s, d, _t) in want:
        ud[s] = ud.get(s, 0) + 1
        ud[d] = ud.get(d, 0) + 1
    nk = np.asarray(snap.node_key)[: int(snap.n_nodes)]
    deg = np.asarray(snap.node_degree)[: int(snap.n_nodes)]
    assert {int(k): int(v) for k, v in zip(nk, deg)} == ud


def test_snapshot_empty_store():
    snap = build_snapshot(init_store(1 << 8, 1 << 9))
    assert int(snap.n_nodes) == 0 and int(snap.n_edges) == 0
    assert not np.asarray(snap.edge_valid).any()


# ---------------------------------------------------------------------------
# engine vs brute force
# ---------------------------------------------------------------------------


@pytest.fixture
def graph(rng):
    store, want = _ingest_batches(rng, init_store(1 << 10, 1 << 12), batches=4)
    snap = build_snapshot(store)
    adj = {}
    ud = {}
    for (s, d, _t) in want:
        adj.setdefault(s, set()).add(d)
        adj.setdefault(d, set()).add(s)
        ud[s] = ud.get(s, 0) + 1
        ud[d] = ud.get(d, 0) + 1
    return snap, want, adj, ud


def test_degree_distribution_matches_bincount(graph):
    snap, _want, _adj, _ud = graph
    hist = np.asarray(degree_distribution(snap, num_bins=32))
    deg = np.asarray(snap.node_degree)[: int(snap.n_nodes)]
    np.testing.assert_array_equal(hist, np.bincount(np.clip(deg, 0, 31),
                                                    minlength=32))
    assert hist.sum() == int(snap.n_nodes)


def test_top_k_matches_sorted_degrees(graph):
    snap, _want, _adj, ud = graph
    keys, degs = top_k_degree(snap, 8)
    assert np.asarray(degs).tolist() == sorted(ud.values(), reverse=True)[:8]
    for k, d in zip(np.asarray(keys), np.asarray(degs)):
        assert ud[int(k)] == int(d)


@pytest.mark.parametrize("hops", [1, 2, 3])
@pytest.mark.parametrize("directed", [False, True])
def test_k_hop_matches_bfs(graph, hops, directed):
    snap, want, adj, _ud = graph
    if directed:
        adj = {}
        for (s, d, _t) in want:
            adj.setdefault(s, set()).add(d)
    seed = next(iter(sorted(adj)))
    mask = np.asarray(k_hop(snap, jnp.asarray([seed], snap.node_key.dtype),
                            hops=hops, directed=directed))
    frontier = {seed}
    for _ in range(hops):
        frontier |= {v for u in frontier for v in adj.get(u, ())}
    nk = np.asarray(snap.node_key)
    assert {int(k) for k, m in zip(nk, mask) if m} == frontier


def test_k_hop_absent_seed_is_empty(graph):
    snap, _w, _a, _u = graph
    mask = np.asarray(k_hop(snap, jnp.asarray([999_999_937],
                                              snap.node_key.dtype), hops=2))
    assert not mask.any()


def test_triangle_count_matches_bruteforce(graph):
    snap, want, _adj, ud = graph
    nodes = sorted(ud)
    idx = {u: i for i, u in enumerate(nodes)}
    a = np.zeros((len(nodes), len(nodes)), np.int64)
    for (s, d, _t) in want:
        if s != d:
            a[idx[s], idx[d]] = a[idx[d], idx[s]] = 1
    assert int(triangle_count(snap)) == int(np.trace(a @ a @ a)) // 6


def test_triangle_count_guards_dense_capacity():
    snap = build_snapshot(init_store(1 << 8, 1 << 9))
    with pytest.raises(ValueError):
        triangle_count(snap, max_dense_nodes=64)


def test_edge_lookup_matches_dict(graph, rng):
    snap, want, _adj, _ud = graph
    pair_w = {}
    for (s, d, _t), c in want.items():
        pair_w[(s, d)] = pair_w.get((s, d), 0) + c
    pairs = list(pair_w) + [(1, 999_999), (999_999, 1)]
    srcs = jnp.asarray([p[0] for p in pairs], snap.node_key.dtype)
    dsts = jnp.asarray([p[1] for p in pairs], snap.node_key.dtype)
    got = np.asarray(edge_lookup(snap, srcs, dsts))
    wantv = np.asarray([pair_w.get(p, 0) for p in pairs])
    np.testing.assert_array_equal(got, wantv)


# ---------------------------------------------------------------------------
# end-to-end: pipeline with SketchStage + QuerySink
# ---------------------------------------------------------------------------


def test_end_to_end_pipeline_sketch_and_snapshot(tmp_path):
    from repro.api import GraphStoreSink, PipelineBuilder
    from repro.configs.paper_ingest import IngestConfig
    from repro.ingest.sources import BurstyTweetSource
    from repro.query import SketchStage

    # caps sized for low load factor: open addressing degrades near full
    cfg = IngestConfig(store_nodes=1 << 13, store_edges=1 << 15)
    stage = SketchStage(depth=4, width=256)
    events = []
    pipe = (PipelineBuilder(cfg)
            .with_source(BurstyTweetSource(seed=3, mean_rate=40.0))
            .with_sink(GraphStoreSink(node_cap=1 << 13, edge_cap=1 << 15))
            .with_sketch(stage)
            .with_query_sink(depth=4, width=256, answer_every=2, top_k=3)
            .spill_dir(str(tmp_path / "spill"))
            .on_event(lambda ev: events.append(ev) if ev.kind == "sketch" else None)
            .build())
    rep = pipe.run(max_ticks=40)
    assert rep.total_records > 0
    store = pipe.store
    assert int(store.n_edges) > 0

    # live sketch events flowed during ingestion
    assert events and events[-1].payload["commits"] >= 2
    assert events[-1].payload["hh_keys"][0] != 0

    snap = build_snapshot(store)
    assert int(snap.n_nodes) == int(store.n_nodes)
    assert int(snap.n_edges) == int(store.n_edges)

    # exact top-k vs both sketches: sketch answers upper-bound exact
    keys, degs = top_k_degree(snap, 5)
    keys, degs = np.asarray(keys), np.asarray(degs)
    live = keys != 0
    filter_est = stage.degree(keys[live])
    commit_est = pipe.sink.degree(keys[live])
    assert (filter_est >= degs[live]).all()
    assert (commit_est >= degs[live]).all()
    # filter-time sketch saw everything the commit-time sketch saw
    assert int(stage.sketch.n_updates) >= int(pipe.sink.sketch.n_updates)

    # committed-edge weights: sketch upper-bounds the exact lookup
    er = np.asarray(snap.edge_row)
    take = np.flatnonzero(er < snap.node_cap)[:16]
    nk = np.asarray(snap.node_key)
    s_keys = nk[er[take]]
    d_keys = nk[np.asarray(snap.edge_col)[take]]
    exact_w = np.asarray(edge_lookup(snap, jnp.asarray(s_keys, snap.node_key.dtype),
                                     jnp.asarray(d_keys, snap.node_key.dtype)))
    est_w = pipe.sink.edge_weight(s_keys, d_keys)
    assert (est_w >= exact_w).all()


def test_query_sink_absorbs_retried_and_pooled_commits(rng):
    """Commit-consistency under failures: batches that reach the store
    via archive replay (and pool drains) must also reach the sketch."""
    from repro.api import GraphStoreSink
    from repro.query import QuerySink

    fails = iter([False, True, False])  # 2nd commit raises -> archived
    sink = QuerySink(GraphStoreSink(node_cap=1 << 10, edge_cap=1 << 11,
                                    fail_hook=lambda: next(fails, False)),
                     depth=2, width=128)
    tables = [_table(rng, n=128, n_keys=40)[3] for _ in range(3)]
    outs = [sink.commit(t, now=float(i)) for i, t in enumerate(tables)]
    assert outs[1]["committed"] is False  # archived
    committed_total = sum(int(t.count.sum()) for i, t in enumerate(tables)
                          if outs[i]["committed"])
    assert int(sink.sketch.n_updates) == committed_total
    assert sink.retry_archive(now=3.0) == 1  # replay reaches the sketch too
    assert int(sink.sketch.n_updates) == sum(int(t.count.sum()) for t in tables)
    assert int(np.asarray(sink.store.edge_count).sum()) == int(sink.sketch.n_updates)


def test_with_sketch_inherits_builder_mapping(tmp_path):
    """with_sketch() without an explicit stage must observe the same
    edges the transform commits (builder mapping + batch cap)."""
    from repro.api import PipelineBuilder
    from repro.configs.paper_ingest import IngestConfig
    from repro.core.transform import tweet_mapping

    mapping = tweet_mapping()
    b = (PipelineBuilder(IngestConfig(max_edges_per_batch=2048))
         .with_mapping(mapping)
         .with_sketch(width=128))
    pipe = b.build()
    stage = b.sketch_stage
    assert stage is pipe.stages[0]
    assert stage.mapping is mapping
    assert stage.max_edges_per_batch == 2048


def test_sharded_pipeline_accepts_stages(tmp_path):
    from repro.api import PipelineBuilder
    from repro.configs.paper_ingest import IngestConfig
    from repro.ingest.sources import BurstyTweetSource
    from repro.query import SketchStage

    stage = SketchStage(depth=2, width=128)
    pipe = (PipelineBuilder(IngestConfig(store_nodes=1 << 12, store_edges=1 << 14))
            .with_source(BurstyTweetSource(seed=1, mean_rate=30.0))
            .with_sketch(stage)
            .sharded(2)
            .spill_dir(str(tmp_path / "spill"))
            .build())
    rep = pipe.run(max_ticks=15)
    assert rep.total_records > 0
    assert int(stage.sketch.n_updates) > 0
