"""Pre-generated feedback randomness for the fused TM epoch kernel.

The reference trainer (:mod:`repro.core.tm`) draws its stochastic
choices *inside* the per-sample scan — fine for jnp, but a Pallas kernel
body cannot host the threefry hash portably (counter-based PRNG inside a
Mosaic kernel is TPU-generation-specific).  So the fused epoch kernel
consumes the whole epoch's randomness as plain arrays, generated here
with exactly the reference key discipline:

* per sample ``i``: ``k_neg, k_t, k_n = split(keys[i], 3)`` where
  ``keys = split(epoch_key, n_samples)`` — the negative class is
  ``(y + randint(k_neg, 1, C)) % C``;
* per feedback role (target ``k_t`` / negative ``k_n``):
  ``k_act, k_s1, k_s2 = split(k, 3)`` — clause-activation draws from
  ``k_act``, the Type-I increment/decrement coin flips from ``k_s1`` /
  ``k_s2``.

The coin flips are stored pre-compared, two bits per (clause, literal)
in one int8 plane (bit 1 = increment draw hit, bit 2 = decrement draw
hit), via the **int-domain compare trick**: jax's float32
``uniform(k, shape)`` is exactly ``(bits(k) >> 9) * 2**-23``, so

    uniform(k, shape) < p   ⟺   (bits(k) >> 9) < ceil(float32(p) · 2²³)

bit-for-bit (both sides of the float compare are exact f32 values;
:func:`int_threshold` is pinned against ``jax.random.uniform`` by
``tests/test_kernels.py``).  This skips the uint32→f32 convert and the
f32 compare for the two (m, L) planes per role — the dominant draw
volume — while staying bit-identical to the reference path.

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

# f32 uniforms carry exactly 23 mantissa bits: u = (bits >> 9) * 2^-23
_MANTISSA = float(1 << 23)


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


def epoch_draws(key: jax.Array, n_samples: int, n_clauses: int,
                n_literals: int, n_classes: int,
                p_inc: float, p_dec: float):
    """One epoch's randomness, reference key discipline (see module doc).

    Returns ``(offsets, act, coin)``:

    * ``offsets`` (S,) int32 — negative-class offset in [1, C);
    * ``act``     (S, 2, m) int32 — clause-activation draws as 23-bit
      integers (:func:`act_bits`), role 0 = target, 1 = negative;
    * ``coin``    (S, 2, m, L) int8 — bit 1: Type-I increment draw hit
      (``u < p_inc``), bit 2: decrement draw hit (``u < p_dec``).
    """
    m, L = n_clauses, n_literals
    t_inc = int_threshold(p_inc)
    t_dec = int_threshold(p_dec)
    keys = jax.random.split(key, n_samples)

    def per_sample(_, k):
        k_neg, k_t, k_n = jax.random.split(k, 3)

        def role(kr):
            k_act, k_s1, k_s2 = jax.random.split(kr, 3)
            h1 = jax.random.bits(k_s1, (m, L), jnp.uint32) >> 9
            h2 = jax.random.bits(k_s2, (m, L), jnp.uint32) >> 9
            return act_bits(k_act, (m,)), ((h1 < t_inc).astype(jnp.int8)
                                          + 2 * (h2 < t_dec).astype(jnp.int8))

        a_t, c_t = role(k_t)
        a_n, c_n = role(k_n)
        off = jax.random.randint(k_neg, (), 1, n_classes)
        return 0, (off.astype(jnp.int32), jnp.stack([a_t, a_n]),
                   jnp.stack([c_t, c_n]))

    _, (offsets, act, coin) = jax.lax.scan(per_sample, 0, keys)
    return offsets, act, coin
