"""Pallas TPU kernel: counter-based traffic-id sampling (repro.workloads).

The scenario generator must synthesise hundreds of thousands of
records per second without stealing cycles from the ingest hot path,
so the per-record id sampling — Zipf heavy-hitter user picks, hot-
topic/long-tail hashtag mixing, and retweet-cascade mention targets —
is one fused, stateless kernel launch per block.  Statelessness is
the point: every lane derives its randomness from a *counter-based*
PRNG (murmur3/lowbias32 finaliser over (seed, lane counter)), so a
block of n records is a pure function of (seed, ctr0) — reproducible
across hosts, shards and re-runs, with no RNG state to thread.

Per lane the kernel draws disjoint counter substreams and produces:
  * `uid`     — Zipf(a_user) rank over n_users (bounded-Pareto inverse
    CDF: the heavy-hitter user skew of real social streams),
  * `tag`     — with probability `burst_frac` a hot-topic hashtag
    (one of `burst_ntags` ids at `topic_base`, the #ReleaseTheMemo
    effect: diversity collapses exactly when volume spikes), else a
    Zipf(a_tag) rank over n_tags,
  * `mention` — with probability `copy_frac` the author of a uniformly
    chosen *earlier record in the block* (the copy-model approximation
    of preferential attachment: retweet cascades re-mention whoever is
    already active), else a Zipf(a_mention) celebrity pick,
  * `u_dup`/`u_dupi` — spare uniforms the host-side source uses for
    duplicate-tweet decisions (kept in-kernel so duplicates are also
    counter-deterministic).

`traffic_body` is the pure body shared verbatim by the Pallas kernel
and the jnp oracle `traffic_ids_ref` (repro.kernels idiom), so the
two are bit-exact by construction; tests assert it anyway.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# one record consumes NSTREAMS consecutive counter lanes (6 used, 2
# reserved) so blocks advance the counter by n * NSTREAMS
NSTREAMS = 8


def _fmix32(x: jax.Array) -> jax.Array:
    """lowbias32 finaliser: bijective uint32 mix with full avalanche."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> jnp.uint32(16))
    return x


def counter_mix(seed: jax.Array, ctr: jax.Array) -> jax.Array:
    """Counter-based PRNG draw: two lowbias32 rounds keyed by the seed.

    The seed is diffused into a key `k = fmix(seed)` that enters both
    before and after the first diffusion round (`fmix(fmix(ctr + k) ^
    k)`), so different seeds are genuinely independent streams — a
    mere additive or XOR pre-mix would make seed s and seed s + d
    produce counter-shifted copies of one sequence.  Pure uint32 ->
    uint32; equal (seed, ctr) gives identical bits."""
    k = _fmix32(jnp.asarray(seed, jnp.uint32))
    x = _fmix32(ctr.astype(jnp.uint32) + k)
    return _fmix32(x ^ k)


def uniform01(bits: jax.Array) -> jax.Array:
    """uint32 bits -> float32 uniforms in [0, 1) (24-bit mantissa).

    The 24-bit value converts through int32, exactly: Mosaic has no
    uint32 -> float32 cast."""
    return ((bits >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
            * jnp.float32(1.0 / (1 << 24)))


def zipf_rank(u: jax.Array, n, a) -> jax.Array:
    """Approximate Zipf(a) ranks in [0, n) via the bounded-Pareto
    inverse CDF on [1, n+1): F^-1(u) = (1 + u((n+1)^(1-a) - 1))^(1/(1-a)).

    Exact for the continuous power law, rank-faithful for the discrete
    Zipf at the skews social streams show (a in ~[1.05, 3]; a must not
    be 1, the harmonic pole)."""
    nf = jnp.asarray(n, jnp.float32)
    af = jnp.asarray(a, jnp.float32)
    one_m_a = 1.0 - af
    top = jnp.power(nf + 1.0, one_m_a) - 1.0
    x = jnp.power(1.0 + u * top, 1.0 / one_m_a)
    return jnp.clip(x.astype(jnp.int32) - 1, 0, jnp.asarray(n, jnp.int32) - 1)


def traffic_body(lanes, pos, seed, n_users, n_tags, burst_ntags, topic_base,
                 a_user, a_tag, a_mention, burst_frac, copy_frac):
    """The shared sampling body (see module docstring).

    lanes (n,) uint32 — base counters, stride NSTREAMS per record;
    pos (n,) int32 — record position within the block (cascade index).
    Returns (uid, tag, mention) int32 and (u_dup, u_dupi) float32.
    """
    u = lambda s: uniform01(counter_mix(seed, lanes + jnp.uint32(s)))
    u_uid, u_tag, u_mix = u(0), u(1), u(2)
    u_cas, u_src, u_men = u(3), u(4), u(5)

    uid = zipf_rank(u_uid, n_users, a_user)
    hot = (jnp.asarray(topic_base, jnp.int32)
           + (u_tag * jnp.asarray(burst_ntags, jnp.float32)).astype(jnp.int32)
           ) % jnp.asarray(n_tags, jnp.int32)
    tag = jnp.where(u_mix < burst_frac, hot, zipf_rank(u_tag, n_tags, a_tag))
    # retweet cascade: copy the author of an earlier record in-block
    j = (u_src * pos.astype(jnp.float32)).astype(jnp.int32)
    use_copy = (u_cas < copy_frac) & (pos > 0)
    mention = jnp.where(use_copy, uid[j],
                        zipf_rank(u_men, n_users, a_mention))
    return uid, tag, mention, u(6), u(7)


def _lanes(ctr0, n: int):
    """Base counter + block position for n records."""
    pos = jnp.arange(n, dtype=jnp.int32)
    lanes = jnp.asarray(ctr0, jnp.uint32) + pos.astype(jnp.uint32) * jnp.uint32(NSTREAMS)
    return lanes, pos


@functools.partial(jax.jit, static_argnames=("n",))
def traffic_ids_ref(seed, ctr0, n: int, iparams, fparams):
    """jnp oracle, and what the main path runs on every backend
    (see repro.kernels.ops).

    iparams (4,) int32: n_users, n_tags, burst_ntags, topic_base;
    fparams (5,) float32: a_user, a_tag, a_mention, burst_frac, copy_frac.
    """
    lanes, pos = _lanes(ctr0, n)
    return traffic_body(lanes, pos, jnp.asarray(seed, jnp.uint32),
                        iparams[0], iparams[1], iparams[2], iparams[3],
                        fparams[0], fparams[1], fparams[2], fparams[3],
                        fparams[4])


def _traffic_kernel(seed_ref, ip_ref, fp_ref, lanes_ref, pos_ref,
                    uid_out, tag_out, men_out, dup_out, dupi_out):
    uid, tag, men, u_dup, u_dupi = traffic_body(
        lanes_ref[...], pos_ref[...], seed_ref[0],
        ip_ref[0], ip_ref[1], ip_ref[2], ip_ref[3],
        fp_ref[0], fp_ref[1], fp_ref[2], fp_ref[3], fp_ref[4])
    uid_out[...] = uid
    tag_out[...] = tag
    men_out[...] = men
    dup_out[...] = u_dup
    dupi_out[...] = u_dupi


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def traffic_ids(seed, ctr0, n: int, iparams, fparams, interpret: bool = False):
    """Fused traffic-id sampling through the Pallas kernel.

    Same contract as `traffic_ids_ref`; one launch per block, all
    operands VMEM-resident (6n uniforms + 5n outputs: ~90 KB at the
    default n=2048 block)."""
    lanes, pos = _lanes(ctr0, n)
    seed_a = jnp.asarray(seed, jnp.uint32).reshape(1)
    return pl.pallas_call(
        _traffic_kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
        ],
        interpret=interpret,
    )(seed_a, jnp.asarray(iparams, jnp.int32), jnp.asarray(fparams, jnp.float32),
      lanes, pos)
