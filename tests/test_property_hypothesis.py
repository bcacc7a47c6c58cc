"""Property-based tests (hypothesis) on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.configs.paper_ingest import IngestConfig
from repro.core import compression as C
from repro.core.buffer import BufferController
from repro.distributed.grad_compression import int8_roundtrip
from repro.kernels import bloom

_settings = dict(max_examples=25, deadline=None)


# ---------------------------------------------------------------------------
# compression invariants
# ---------------------------------------------------------------------------


@settings(**_settings)
@given(
    data=st.lists(st.integers(min_value=1, max_value=40), min_size=1, max_size=120),
)
def test_dedup_partition_property(data):
    """Dedup is a partition: counts sum to n, uniques match set()."""
    n = len(data)
    cap = 128
    keys = jnp.asarray(np.pad(np.asarray(data, np.uint32), (0, cap - n)))
    valid = jnp.arange(cap) < n
    comp = C.dedup_with_counts(keys, valid)
    assert int(comp.counts.sum()) == n
    assert int(comp.n_unique) == len(set(data))
    uk = np.asarray(comp.keys[: int(comp.n_unique)])
    assert set(uk.tolist()) == set(data)
    assert (np.diff(uk.astype(np.int64)) > 0).all()  # sorted unique


@settings(**_settings)
@given(
    nsrc=st.integers(min_value=2, max_value=30),
    seed=st.integers(min_value=0, max_value=10_000),
)
def test_compression_ratio_bounds(nsrc, seed):
    """0 < ratio <= 1: compressed load never exceeds raw load."""
    rng = np.random.default_rng(seed)
    cap = 64
    n = 48
    src = jnp.asarray(rng.integers(1, nsrc, size=cap).astype(np.uint32))
    dst = jnp.asarray(rng.integers(1, nsrc, size=cap).astype(np.uint32))
    et = jnp.ones((cap,), jnp.int32)
    valid = jnp.arange(cap) < n
    from repro.core.edge_table import build_edge_table

    tbl = build_edge_table(src, dst, et, valid)
    r = float(tbl.compression_ratio())
    assert 0.0 < r <= 1.0


# ---------------------------------------------------------------------------
# bloom: no false negatives, ever
# ---------------------------------------------------------------------------


@settings(**_settings)
@given(
    keys=st.lists(
        st.integers(min_value=1, max_value=2**31 - 1), min_size=1, max_size=64
    )
)
def test_bloom_never_false_negative(keys):
    k = jnp.asarray(np.asarray(keys, np.uint32))
    bm = bloom.bloom_build(k, jnp.zeros((4, 1024), jnp.uint32), interpret=True)
    assert bool((np.asarray(bloom.bloom_probe(k, bm, interpret=True)) == 1).all())


# ---------------------------------------------------------------------------
# controller invariants
# ---------------------------------------------------------------------------


@settings(**_settings)
@given(
    mus=st.lists(st.floats(min_value=0.0, max_value=1.0), min_size=4, max_size=30),
    sizes=st.lists(st.floats(min_value=1.0, max_value=1e5), min_size=4, max_size=30),
)
def test_controller_always_in_bounds_and_total(mus, sizes):
    cfg = IngestConfig(beta_min=100, beta_max=10_000)
    ctl = BufferController(cfg, spill_dir="/tmp/repro_spill_hyp")
    for i, (mu, sz) in enumerate(zip(mus, sizes)):
        ctl.perfmon.observe_mu(mu)
        ctl.perfmon.observe_rate(float(i), sz)
        dec = ctl.decide(sz, density=mu)
        assert cfg.beta_min <= ctl.beta <= cfg.beta_max
        assert dec.action in ("push", "hold", "throttle", "drain+push")
        assert 0.0 <= dec.mu_exp <= 1.0  # predictions clipped to [0,1]


# ---------------------------------------------------------------------------
# workload sampler invariants (repro.workloads)
# ---------------------------------------------------------------------------


@settings(**_settings)
@given(
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    alpha=st.floats(min_value=0.0, max_value=0.9),
    amp=st.floats(min_value=0.0, max_value=0.9),
    flash_mult=st.floats(min_value=1.0, max_value=10.0),
    noise=st.floats(min_value=0.0, max_value=0.45),
)
def test_workload_rates_nonnegative_and_deterministic(
        seed, alpha, amp, flash_mult, noise):
    """Trajectory invariants: rates finite and >= 0, counts in
    [0, cap], and the whole chunk a pure function of the seed."""
    from repro.workloads import rate_trajectory

    args = (64, 0, 0.0, 60.0, noise, alpha, 0.5, amp, 120.0, 20.0,
            flash_mult, 30.0, 3000.0)
    ch = rate_trajectory(np.uint32(seed), *args)
    rates, counts = np.asarray(ch.rates), np.asarray(ch.counts)
    assert np.isfinite(rates).all() and (rates >= 0).all()
    assert (counts >= 0).all() and (counts <= 3000).all()
    again = rate_trajectory(np.uint32(seed), *args)
    np.testing.assert_array_equal(np.asarray(again.counts), counts)


@settings(**_settings)
@given(
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    a=st.floats(min_value=1.2, max_value=2.5),
    n=st.integers(min_value=100, max_value=5000),
)
def test_workload_zipf_skew_bounds(seed, a, n):
    """Zipf ranks stay in [0, n) and the top decile holds at least
    ~70% of its bounded-Pareto mass (heavy-hitter skew)."""
    from repro.kernels.sampler import counter_mix, uniform01, zipf_rank

    ctr = np.arange(4096, dtype=np.uint32)
    u = uniform01(counter_mix(np.uint32(seed), ctr))
    r = np.asarray(zipf_rank(u, n, a))
    assert r.min() >= 0 and r.max() < n
    top = max(n // 10, 1)
    share = float((r < top).mean())
    expect = ((top + 1) ** (1 - a) - 1) / ((n + 1) ** (1 - a) - 1)
    assert share >= 0.7 * expect
    assert share > 0.3


@settings(**_settings)
@given(seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_workload_hawkes_burstier_than_poisson(seed):
    """Self-excitation must raise the Fano factor above the alpha=0
    Poisson-like baseline at matched parameters."""
    from repro.workloads import rate_trajectory

    def fano(alpha):
        ch = rate_trajectory(np.uint32(seed), 256, 0, 0.0, 60.0, 0.0,
                             alpha, 0.4, 0.0, 240.0, 1e9, 1.0, 40.0, 6000.0)
        c = np.asarray(ch.counts, np.float64)
        return c.var() / max(c.mean(), 1e-9)

    assert fano(0.85) > fano(0.0)


# ---------------------------------------------------------------------------
# quantisation error bound
# ---------------------------------------------------------------------------


@settings(**_settings)
@given(
    scale=st.floats(min_value=1e-3, max_value=1e3),
    seed=st.integers(min_value=0, max_value=1000),
)
def test_int8_error_bound_property(scale, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray((rng.normal(size=512) * scale).astype(np.float32))
    y = int8_roundtrip(x)
    blocks = np.abs(np.asarray(x)).reshape(-1, 256).max(axis=1)
    bound = np.repeat(blocks, 256) / 127.0 * 0.5 + 1e-9
    assert (np.abs(np.asarray(y - x)) <= bound + 1e-6).all()


# ---------------------------------------------------------------------------
# tokenizer determinism
# ---------------------------------------------------------------------------


@settings(**_settings)
@given(text=st.text(alphabet=st.characters(codec="ascii"), max_size=200))
def test_tokenizer_deterministic_and_in_range(text):
    from repro.data.tokenizer import HashTokenizer

    tok = HashTokenizer(1024)
    a = tok.encode(text)
    b = tok.encode(text)
    assert a == b
    assert all(0 <= t < 1024 for t in a)


# ---------------------------------------------------------------------------
# retry backoff invariants (repro.resilience)
# ---------------------------------------------------------------------------


@settings(**_settings)
@given(
    base_s=st.floats(min_value=1e-3, max_value=10.0),
    factor=st.floats(min_value=1.0, max_value=8.0),
    cap_mult=st.floats(min_value=1.0, max_value=100.0),
    jitter=st.floats(min_value=0.0, max_value=0.99),
    seed=st.integers(min_value=0, max_value=2**31),
)
def test_retry_backoff_property(base_s, factor, cap_mult, jitter, seed):
    """Capped, monotone (until the cap), jitter-bounded, deterministic:
    the RetryPolicy contract for every parameterisation it accepts."""
    from repro.resilience import RetryPolicy

    p = RetryPolicy(base_s=base_s, factor=factor, cap_s=base_s * cap_mult,
                    jitter=jitter, seed=seed)
    raws = [p.raw_delay(k) for k in range(24)]
    # monotone non-decreasing and capped (incl. huge attempt counts)
    assert all(b >= a for a, b in zip(raws, raws[1:]))
    assert all(r <= p.cap_s for r in raws)
    # the schedule saturates: at the cap when it grows, flat otherwise
    assert p.raw_delay(10**9) == (p.cap_s if factor > 1.0
                                  else min(base_s, p.cap_s))
    for k in range(24):
        d = p.delay(k)
        # jitter stays a +/- fraction of the raw schedule...
        assert raws[k] * (1 - jitter) - 1e-12 <= d
        assert d <= raws[k] * (1 + jitter) + 1e-12
        # ...and is a pure function of (policy params, attempt)
        assert d == RetryPolicy(base_s=base_s, factor=factor,
                                cap_s=base_s * cap_mult, jitter=jitter,
                                seed=seed).delay(k)
