"""Per-kernel shape/dtype sweeps, assert_allclose against ref.py oracles.

Kernels run in interpret mode here (`interpret=True`).  Most of them do
not compile for TPU v5e (ROADMAP S7); tests/test_chip_compile.py
compiles what the main path runs on the chip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import bloom, ref
from repro.kernels.edge_dedup import dedup_sorted_counts, sort_dedup
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan

# ---------------------------------------------------------------------------
# edge_dedup
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [64, 256, 1024, 4096])
@pytest.mark.parametrize("dup_range", [5, 1000, 2**31])
def test_sort_dedup_sweep(n, dup_range, rng):
    keys = jnp.asarray(rng.integers(0, dup_range, size=n).astype(np.uint32))
    sk, order, head = sort_dedup(keys, interpret=True)
    sk_r, _, head_r = ref.sort_dedup_ref(keys)
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(sk_r))
    np.testing.assert_array_equal(np.asarray(head), np.asarray(head_r))
    # order is a valid permutation that sorts keys
    assert sorted(np.asarray(order).tolist()) == list(range(n))
    np.testing.assert_array_equal(np.asarray(keys)[np.asarray(order)], np.asarray(sk))


def test_dedup_counts_match_numpy(rng):
    keys = jnp.asarray(rng.integers(0, 37, size=512).astype(np.uint32))
    sk, order, head = sort_dedup(keys, interpret=True)
    counts, nu = dedup_sorted_counts(sk, head)
    vals, cts = np.unique(np.asarray(keys), return_counts=True)
    assert int(nu) == len(vals)
    np.testing.assert_array_equal(np.asarray(counts[: len(vals)]), cts)


# ---------------------------------------------------------------------------
# bloom
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,rows", [(64, 2), (256, 4), (1024, 16)])
def test_bloom_build_matches_ref(n, rows, rng):
    keys = jnp.asarray(rng.integers(1, 2**31, size=n).astype(np.uint32))
    bm = jnp.zeros((rows, 1024), jnp.uint32)
    out = bloom.bloom_build(keys, bm, interpret=True)
    want = ref.bloom_build_ref(np.asarray(keys), np.asarray(bm))
    np.testing.assert_array_equal(np.asarray(out), want)


def test_bloom_no_false_negatives(rng):
    keys = jnp.asarray(rng.integers(1, 2**31, size=500).astype(np.uint32))
    bm = bloom.bloom_build(keys, jnp.zeros((16, 1024), jnp.uint32), interpret=True)
    hit = bloom.bloom_probe(keys, bm, interpret=True)
    assert bool((np.asarray(hit) == 1).all())


def test_bloom_low_false_positive_rate(rng):
    seen = jnp.asarray(rng.integers(1, 2**30, size=1000).astype(np.uint32))
    bm = bloom.bloom_build(seen, jnp.zeros((16, 1024), jnp.uint32), interpret=True)
    fresh = jnp.asarray((rng.integers(1, 2**30, size=2000) + 2**30).astype(np.uint32))
    fp = float(np.asarray(bloom.bloom_probe(fresh, bm, interpret=True)).mean())
    assert fp < 0.05, fp


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S,d,bq,bk", [(128, 32, 32, 32), (256, 64, 64, 128), (512, 128, 128, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(S, d, bq, bk, dtype, causal):
    BH = 3
    q = jax.random.normal(jax.random.key(0), (BH, S, d), dtype)
    k = jax.random.normal(jax.random.key(1), (BH, S, d), dtype)
    v = jax.random.normal(jax.random.key(2), (BH, S, d), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )


def test_flash_attention_sliding_window():
    BH, S, d = 2, 256, 64
    q = jax.random.normal(jax.random.key(0), (BH, S, d))
    k = jax.random.normal(jax.random.key(1), (BH, S, d))
    v = jax.random.normal(jax.random.key(2), (BH, S, d))
    out = flash_attention(q, k, v, causal=True, window=64, block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-6, rtol=2e-6)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S,p,N,chunk", [(64, 16, 8, 16), (128, 32, 16, 32), (256, 64, 64, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(S, p, N, chunk, dtype):
    BH = 2
    x = jax.random.normal(jax.random.key(0), (BH, S, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(jax.random.key(1), (BH, S))).astype(dtype)
    A = -jnp.abs(jax.random.normal(jax.random.key(2), (BH,)))
    B = jax.random.normal(jax.random.key(3), (BH, S, N), dtype)
    C = jax.random.normal(jax.random.key(4), (BH, S, N), dtype)
    y, hT = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    y_r, hT_r = ref.ssd_scan_ref(x, dt, A, B, C)
    tol = 1e-4 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_r, np.float32), atol=tol, rtol=tol
    )
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hT_r), atol=tol, rtol=tol)


def test_ssd_model_chunked_matches_bruteforce():
    """The model's chunked SSD (used in training) == sequential recurrence."""
    from repro.models.mamba2 import ssd_chunked

    B_, S, nh, p, N = 2, 96, 3, 8, 4
    xh = jax.random.normal(jax.random.key(0), (B_, S, nh, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.key(1), (B_, S, nh)))
    A = -jnp.abs(jax.random.normal(jax.random.key(2), (nh,)))
    Bs = jax.random.normal(jax.random.key(3), (B_, S, N))
    Cs = jax.random.normal(jax.random.key(4), (B_, S, N))
    y, hT = ssd_chunked(xh, dt, A, Bs, Cs, chunk=32)
    # brute force via the kernel oracle, vmapped over heads (B,C shared)
    x_f = xh.transpose(0, 2, 1, 3).reshape(B_ * nh, S, p)
    dt_f = dt.transpose(0, 2, 1).reshape(B_ * nh, S)
    A_f = jnp.tile(A, (B_,))
    B_f = jnp.repeat(Bs, nh, axis=0)
    C_f = jnp.repeat(Cs, nh, axis=0)
    y_r, hT_r = ref.ssd_scan_ref(x_f, dt_f, A_f, B_f, C_f)
    y_r = y_r.reshape(B_, nh, S, p).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r), atol=2e-4, rtol=2e-4)
