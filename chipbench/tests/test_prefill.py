"""The past that set-up commits is what its totals say, and the read-back
finds a window's nodes and edges among it."""
import numpy as np

from chipbench import prefill


def _store():
    from repro.graphstore.store import init_store

    return init_store(1 << 12, 1 << 13)


def test_mix_is_undone():
    import jax.numpy as jnp

    for dt in (jnp.uint64, jnp.uint32):
        x = jnp.asarray([0, 1, 2, 12345, 2**31 + 3], dt)
        assert (prefill._unmix(prefill._mix(x)) == x).all()


def test_plan_rounds_to_whole_chunks():
    cfg = {"prefill": {"edges": 3000, "nodes": 1000, "chunk_edges": 512}}
    assert prefill.plan(cfg) == (6, 512, 171)
    assert prefill.plan({}) == (0, 0, 0)


def test_fill_commits_exactly_the_past():
    cfg = {"prefill": {"edges": 3000, "nodes": 1000, "chunk_edges": 512}}
    store, past = prefill.fill(_store(), cfg, seed=2**33 + 5)
    assert past.dropped == 0
    assert (past.nodes, past.edges) == (6 * 171, 6 * 512)
    (edges, degree, count), totals = prefill.select(store, past)
    assert totals == past.totals()
    assert edges == degree == count == {}


def test_fill_depends_on_the_seed_only():
    cfg = {"prefill": {"edges": 1024, "nodes": 300, "chunk_edges": 512}}
    a, _ = prefill.fill(_store(), cfg, seed=7)
    b, _ = prefill.fill(_store(), cfg, seed=7)
    c, _ = prefill.fill(_store(), cfg, seed=8)
    ka, kb, kc = (np.sort(np.asarray(s.node_keys)) for s in (a, b, c))
    assert (ka == kb).all() and not (ka == kc).all()


def test_select_reads_back_a_window_among_the_past():
    import jax.numpy as jnp

    from repro.core.edge_table import build_edge_table

    cfg = {"prefill": {"edges": 2048, "nodes": 700, "chunk_edges": 512}}
    store, past = prefill.fill(_store(), cfg, seed=11)
    from repro.graphstore.store import ingest_step

    src = jnp.asarray([5, 5, 6, 0], jnp.uint64)
    dst = jnp.asarray([6, 7, 7, 0], jnp.uint64)
    et = build_edge_table(src, dst, jnp.asarray([1, 2, 1, 0], jnp.int32),
                          jnp.asarray([True, True, True, False]))
    store, _ = ingest_step(store, et)
    (edges, degree, count), totals = prefill.select(store, past)
    assert edges == {(5, 6, 1): 1, (5, 7, 2): 1, (6, 7, 1): 1}
    assert degree == {5: 2, 6: 2, 7: 2} and count == {5: 1, 6: 1, 7: 1}
    assert totals["edges"] == past.edges + 3
    assert totals["nodes"] == past.nodes + 3
