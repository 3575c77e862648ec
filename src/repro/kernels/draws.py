"""Feedback randomness for the fused TM epoch kernel.

The reference trainer (:mod:`repro.core.tm`) draws its stochastic
choices *inside* the per-sample scan, with exactly this key discipline:

* per sample ``i``: ``k_neg, k_t, k_n = split(keys[i], 3)`` where
  ``keys = split(epoch_key, n_samples)`` — the negative class is
  ``(y + randint(k_neg, 1, C)) % C``;
* per feedback role (target ``k_t`` / negative ``k_n``):
  ``k_act, k_s1, k_s2 = split(k, 3)`` — clause-activation draws from
  ``k_act``, the Type-I increment/decrement coins from ``k_s1`` /
  ``k_s2`` (``uniform(k_s1, (m, L))`` / ``uniform(k_s2, (m, L))``).

:func:`epoch_draws` makes that discipline's small draws for a whole
epoch outside the kernel — the negative-class offsets and the ``(2, m)``
activation draws — and hands the kernel the *key words* of each
sample's four coin keys in place of the coins themselves, with each
role's Type-I rows sorted by activation draw.  The kernel hashes the
coins (:func:`coin_word`) of the active rows alone.

**One word an automaton, the reference's word.**  Under both threefry
streams every word of ``bits(k, (m, L))`` is a function of its own
index, so the kernel can hash word ``(r, l)`` alone:

* partitionable (``jax_threefry_partitionable``, JAX's default): word
  ``j = r·L + l`` is ``x0 ^ x1`` of ``threefry2x32(k, (0, j))``;
* original, ``n = m·L``, ``h = ⌈n/2⌉``: word ``j < h`` is ``x0`` of
  ``threefry2x32(k, (j, j + h))``, word ``j ≥ h`` is ``x1`` of
  ``threefry2x32(k, (j − h, j))`` (an odd ``n`` pads its last pair with
  a zero counter).

A Type-I automaton reads at most one of its two coins: the increment
coin (``k_s1``) where its clause fired and its literal is true, the
decrement coin (``k_s2``) everywhere else.  Type I goes to the even
(positive-polarity) clauses on the target role and to the odd ones on
the negative role, so row parity names the role, and the clause's
``fired`` bit, known only inside the kernel, names the key.  A row
whose clause is not activated reads neither coin.  So the kernel hashes
one word an automaton of an active Type-I row, with that key, and no
coin the feedback does not read is drawn.  :func:`threefry_stream`
says which stream, from ``jax.config`` at trace time.

Both draws are compared in the integer domain: jax's float32
``uniform(k, shape)`` is exactly ``(bits(k) >> 9) * 2**-23``, so

    uniform(k, shape) < p   ⟺   (bits(k) >> 9) < ceil(float32(p) · 2²³)

bit-for-bit (both sides of the float compare are exact f32 values;
:func:`int_threshold` is pinned against ``jax.random.uniform`` by
``tests/test_kernels.py``).

The clause-activation draws use the same trick.  Their probability
``p_act = (T ∓ v) / 2T`` depends on the clipped vote ``v``, so it can only
be formed inside the kernel; but the numerator ``T ∓ v`` is an integer in
``[0, 2T]``, so :func:`activation_thresholds` tabulates the integer
threshold of every possible f32 quotient once, on the host, with
IEEE-rounded numpy division.  The reference trainer and the kernel both
compare the 23-bit draw against that table — neither divides on the
device, so the two agree bit for bit on any backend (a device's f32
divide need not round like the host's).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry2x32_p

# f32 uniforms carry exactly 23 mantissa bits: u = (bits >> 9) * 2^-23
_MANTISSA = float(1 << 23)
# a draw no threshold exceeds: every threshold is at most 2^23
NEVER = 1 << 23


def int_threshold(p: float) -> int:
    """uniform(k, s) < p  ⟺  (bits(k, s) >> 9) < int_threshold(p)."""
    return math.ceil(float(np.float32(p)) * _MANTISSA)


def act_bits(key: jax.Array, shape) -> jnp.ndarray:
    """The 23-bit integer behind ``jax.random.uniform(key, shape)``."""
    return (jax.random.bits(key, shape, jnp.uint32) >> 9).astype(jnp.int32)


def activation_thresholds(T: int) -> np.ndarray:
    """(2T+1,) int32: entry ``n`` is ``int_threshold(f32(n) / f32(2T))``.

    ``act_bits(k) < table[T - v]`` is ``bernoulli(k, (T - v) / 2T)`` for
    the target class, ``table[T + v]`` for the sampled negative.
    """
    n = np.arange(2 * T + 1, dtype=np.float32)
    p = n / np.float32(2 * T)
    return np.array([int_threshold(float(q)) for q in p], dtype=np.int32)


def threefry_stream() -> str:
    """The stream ``jax.random.bits`` follows: ``"partitionable"`` or
    ``"original"`` threefry.  Read at trace time; any other PRNG
    implementation is refused, since :func:`coin_word` hashes threefry's
    words."""
    if jax.config.jax_default_prng_impl != "threefry2x32":
        raise NotImplementedError(
            "the TM epoch kernel hashes threefry2x32 words; the default "
            f"PRNG is {jax.config.jax_default_prng_impl!r}")
    return ("partitionable" if jax.config.jax_threefry_partitionable
            else "original")


def coin_word(k1, k2, j, n: int) -> jnp.ndarray:
    """Word ``j`` of the ``n`` words of ``jax.random.bits(k, shape,
    uint32)`` (``n = prod(shape)``, ``j`` a flat row-major int32 index)
    for the key whose ``key_data`` is ``(k1, k2)`` (uint32, broadcast to
    ``j``'s shape), under :func:`threefry_stream`.  The epoch kernel
    hashes its coins with it."""
    if n >= 2 ** 31:
        raise ValueError(f"{n} words need counters past int32")
    k1 = jnp.broadcast_to(k1, j.shape)
    k2 = jnp.broadcast_to(k2, j.shape)
    if threefry_stream() == "partitionable":
        x0, x1 = threefry2x32_p.bind(k1, k2, jnp.zeros_like(j, jnp.uint32),
                                     j.astype(jnp.uint32))
        return x0 ^ x1
    h = (n + 1) // 2
    lo = j < h
    c0 = jnp.where(lo, j, j - h)
    c1 = jnp.where(lo, j + h, j)
    if n % 2:                       # the odd count's pad counter is 0
        c1 = jnp.where(c1 == n, 0, c1)
    x0, x1 = threefry2x32_p.bind(k1, k2, c0.astype(jnp.uint32),
                                 c1.astype(jnp.uint32))
    return jnp.where(lo, x0, x1)


def epoch_draws(key: jax.Array, n_samples: int, n_clauses: int,
                n_classes: int):
    """One epoch's draws outside the kernel, reference key discipline
    (see module doc).

    Returns ``(offsets, act, coin_keys, order)``:

    * ``offsets``   (S,) int32 — negative-class offset in [1, C);
    * ``act``       (S, 2, m) int32 — clause-activation draws as 23-bit
      integers (:func:`act_bits`), role 0 = target, 1 = negative;
    * ``coin_keys`` (S, 8) uint32 — ``key_data`` of the target's
      ``k_s1``, ``k_s2``, then the negative's ``k_s1``, ``k_s2``: the
      keys the kernel hashes the Type-I coins from;
    * ``order``     (S, 2, ⌈m/2⌉) int32 — each role's Type-I rows (the
      target's even rows, the negative's odd ones) ascending by their
      activation draw.  A row is active iff its draw is under the
      sample's threshold, so for every threshold the active rows are a
      prefix of ``order``, however ties fall.  An odd ``m`` leaves the
      negative one row short: its last slot holds row ``m``, past the
      clauses, drawn :data:`NEVER`, so it sorts last and is never
      active.
    """
    m = n_clauses
    half = (m + 1) // 2

    def per_sample(k):
        k_neg, k_t, k_n = jax.random.split(k, 3)
        ka_t, k1_t, k2_t = jax.random.split(k_t, 3)
        ka_n, k1_n, k2_n = jax.random.split(k_n, 3)
        act = jnp.stack([act_bits(ka_t, (m,)), act_bits(ka_n, (m,))])
        off = jax.random.randint(k_neg, (), 1, n_classes)
        words = jnp.concatenate([jax.random.key_data(c)
                                 for c in (k1_t, k2_t, k1_n, k2_n)])
        # role r's Type-I rows are 2i + r: row pairs, drawn by the role
        pairs = jnp.pad(act, ((0, 0), (0, 2 * half - m)),
                        constant_values=NEVER).reshape(2, half, 2)
        by_role = jnp.stack([pairs[0, :, 0], pairs[1, :, 1]])
        rank = jnp.argsort(by_role, axis=-1).astype(jnp.int32)
        order = 2 * rank + jnp.arange(2, dtype=jnp.int32)[:, None]
        return off.astype(jnp.int32), act, words, order

    return jax.vmap(per_sample)(jax.random.split(key, n_samples))
