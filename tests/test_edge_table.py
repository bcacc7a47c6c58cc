"""`from_raw_batch` pads on the host: the same tables as the device-side
padding it replaced, and one shape per capacity on the device."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression as C
from repro.core.edge_table import build_edge_table, from_raw_batch
from repro.core.transform import RawEdgeBatch

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def ref_from_raw_batch(raw: RawEdgeBatch, capacity: int):
    """The device-side padding `from_raw_batch` was."""
    kd = C.key_dtype()
    n = min(raw.n_edges, capacity)
    pad = capacity - n

    def prep(a, dtype):
        a = np.asarray(a[:n])
        return jnp.concatenate([jnp.asarray(a, dtype), jnp.zeros((pad,), dtype)])

    valid = jnp.arange(capacity) < n
    return build_edge_table(prep(raw.src, kd), prep(raw.dst, kd),
                            prep(raw.etype, jnp.int32), valid)


def _raw(n, seed=0, n_keys=40):
    rng = np.random.default_rng(seed)
    # full 64-bit ids, as the node hash gives, with repeats to dedup
    keys = rng.integers(1, 2**64 - 1, n_keys, dtype=np.uint64)
    return RawEdgeBatch(
        src=keys[rng.integers(0, n_keys, n)],
        dst=keys[rng.integers(0, n_keys, n)],
        etype=rng.integers(1, 5, n).astype(np.int32),
        src_type=np.ones(n, np.int32), dst_type=np.full(n, 2, np.int32),
        n_records=n)


@pytest.mark.parametrize("x64", [True, False], ids=["keys64", "keys32"])
@pytest.mark.parametrize("n", [37, 128, 200, 0],
                         ids=["below_capacity", "at_capacity", "cut", "empty"])
def test_host_padding_gives_the_same_table(n, x64):
    raw = _raw(n, seed=n)
    with jax.enable_x64(x64):
        got, want = from_raw_batch(raw, 128), ref_from_raw_batch(raw, 128)
    # 32-bit keys keep the low half of each id, as the control run needs
    assert got.src.dtype == (jnp.uint64 if x64 else jnp.uint32)
    for f in dataclasses.fields(got):
        g, w = getattr(got, f.name), getattr(want, f.name)
        assert g.dtype == w.dtype and g.shape == w.shape, f.name
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), f.name)
    assert int(got.n_raw) == min(n, 128)


class _Compiles:
    def __init__(self):
        self.n = 0

    def __call__(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.n += 1


def test_new_raw_counts_compile_nothing_at_a_warmed_capacity():
    log = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(log)
    try:
        jax.block_until_ready(from_raw_batch(_raw(100, seed=1), 256).n_edges)
        before = log.n
        for n in (1, 99, 173, 255, 256, 300):
            jax.block_until_ready(from_raw_batch(_raw(n, seed=n), 256).n_edges)
        assert log.n - before == 0
    finally:
        jax.monitoring.unregister_event_duration_listener(log)
