"""Parity + property tests for the blocked Bloom filter kernel.

`kernels/bloom.py` powers the pre-commit bucket-diversity signal rho
(§III-A).  The build/probe pair is validated against a bit-for-bit
numpy re-implementation of the hash rounds, and the Bloom contract is
asserted directly: NO false negatives, ever (false positives allowed
and measured).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import bloom as B


def _np_hash_round(keys: np.ndarray, r: int) -> np.ndarray:
    c1 = np.uint32((0x9E3779B9 + 0x7F4A7C15 * r) & 0xFFFFFFFF)
    c2 = np.uint32(0x85EBCA6B)
    x = ((keys + c1) * c2).astype(np.uint32)
    x = x ^ (x >> np.uint32(13))
    x = (x * np.uint32(0xC2B2AE35)).astype(np.uint32)
    return x ^ (x >> np.uint32(16))


def _np_bit_coords(keys: np.ndarray, r: int, words: int):
    h = _np_hash_round(keys, r)
    return (h >> np.uint32(5)) % np.uint32(words), h % np.uint32(32)


def _np_build(keys: np.ndarray, bitmap: np.ndarray) -> np.ndarray:
    flat = bitmap.reshape(-1).copy()
    words = flat.shape[0]
    for r in range(B.HASHES):
        w, b = _np_bit_coords(keys, r, words)
        for wi, bi in zip(w.tolist(), b.tolist()):
            flat[wi] |= np.uint32(1 << bi)
    return flat.reshape(bitmap.shape)


def _np_probe(keys: np.ndarray, bitmap: np.ndarray) -> np.ndarray:
    flat = bitmap.reshape(-1)
    words = flat.shape[0]
    hit = np.ones(keys.shape, np.int32)
    for r in range(B.HASHES):
        w, b = _np_bit_coords(keys, r, words)
        hit &= ((flat[w] >> b) & np.uint32(1)).astype(np.int32)
    return hit


def _keys(rng, n, hi=10_000):
    return rng.integers(1, hi, size=n).astype(np.uint32)


@pytest.mark.parametrize("rows,n", [(4, 128), (16, 256)])
def test_build_matches_numpy_oracle(rng, rows, n):
    keys = _keys(rng, n)
    bitmap = B.init_bitmap(rows)
    built = B.bloom_build(jnp.asarray(keys), bitmap, interpret=True)
    expect = _np_build(keys, np.asarray(bitmap))
    assert (np.asarray(built) == expect).all()


@pytest.mark.parametrize("rows", [4, 16])
def test_probe_matches_numpy_oracle(rng, rows):
    inserted = _keys(rng, 200)
    queries = np.concatenate([inserted[:100], _keys(rng, 100, hi=1 << 30)])
    bitmap = B.bloom_build(jnp.asarray(inserted), B.init_bitmap(rows), interpret=True)
    hits = B.bloom_probe(jnp.asarray(queries), bitmap, interpret=True)
    expect = _np_probe(queries, np.asarray(bitmap))
    assert (np.asarray(hits) == expect).all()


def test_no_false_negatives(rng):
    """The Bloom contract: every inserted key MUST probe as present."""
    for trial in range(5):
        keys = _keys(rng, 256, hi=1 << 31)
        bitmap = B.bloom_build(jnp.asarray(keys), B.init_bitmap(8), interpret=True)
        hits = np.asarray(B.bloom_probe(jnp.asarray(keys), bitmap, interpret=True))
        assert (hits == 1).all(), f"false negative in trial {trial}"


def test_false_positive_rate_bounded(rng):
    """At ~1.6% fill (512 keys x 4 hashes in 64x32768 bits) the false-
    positive rate must be far under 1% — a sanity bound, not the exact
    (1-e^{-kn/m})^k formula."""
    inserted = _keys(rng, 512, hi=1 << 20)
    bitmap = B.bloom_build(jnp.asarray(inserted), B.init_bitmap(64), interpret=True)
    fresh = (rng.integers(1 << 20, 1 << 30, size=4096)).astype(np.uint32)
    hits = np.asarray(B.bloom_probe(jnp.asarray(fresh), bitmap, interpret=True))
    assert hits.mean() < 0.01


def test_empty_bitmap_probe_all_misses(rng):
    keys = _keys(rng, 128)
    hits = np.asarray(B.bloom_probe(jnp.asarray(keys), B.init_bitmap(4), interpret=True))
    assert (hits == 0).all()


def test_build_idempotent(rng):
    """Re-inserting the same keys cannot change the bitmap."""
    keys = jnp.asarray(_keys(rng, 256))
    once = B.bloom_build(keys, B.init_bitmap(8), interpret=True)
    twice = B.bloom_build(keys, once, interpret=True)
    assert jnp.array_equal(once, twice)


def test_build_monotone(rng):
    """Building only SETS bits: the old bitmap is a subset of the new."""
    a = B.bloom_build(jnp.asarray(_keys(rng, 128)), B.init_bitmap(8), interpret=True)
    b = B.bloom_build(jnp.asarray(_keys(rng, 128, hi=1 << 29)), a, interpret=True)
    assert jnp.array_equal(jnp.bitwise_and(a, b), a)


def test_bloom_diversity_signal(rng):
    """rho = 1 on an all-fresh bucket, 0 on an exact replay."""
    keys = jnp.asarray(_keys(rng, 256, hi=1 << 28))
    rho_fresh, bitmap = B.bloom_diversity(keys, B.init_bitmap(32), interpret=True)
    assert float(rho_fresh) == 1.0
    rho_replay, _ = B.bloom_diversity(keys, bitmap, interpret=True)
    assert float(rho_replay) == 0.0
