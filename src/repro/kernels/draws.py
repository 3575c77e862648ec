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
f32 compare for the (m, L) coin planes — the dominant draw volume —
while staying bit-identical to the reference path.

**One coin plane a sample, not one a role.**  Both coin bits are read
only on Type-I rows, and Type I goes to the even (positive-polarity)
clauses on the target role and to the odd ones on the negative role.
So a single (m, L) plane carries both roles: row ``r`` holds the coins
of the role whose Type-I clauses own it — the target's ``k_s1`` /
``k_s2`` words for even ``r``, the negative's for odd ``r``.  Under the
partitionable threefry (``jax_threefry_partitionable``, the default),
word ``(r, l)`` of ``bits(k, (m, L))`` is ``x0 ^ x1`` of
``threefry2x32(k, (0, r·L + l))``, a function of its own index alone,
so the plane is hashed once with each row's key chosen by parity: half
the hashes of drawing both roles' planes, and every word the kernel
reads is the word it would read from them.  Under the original threefry
a word pairs with the one half a plane away, which need not share its
row's parity; there both roles' planes are drawn in full and their rows
picked by parity — the same plane, at the old cost.
:func:`merged_coins` says which, from ``jax.config`` at trace time.

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


def merged_coins() -> bool:
    """Whether :func:`epoch_draws` hashes each coin plane once for both
    roles (partitionable threefry) or draws both roles' planes and picks
    rows by parity (any other stream).  Read at trace time."""
    return (jax.config.jax_threefry_partitionable
            and jax.config.jax_default_prng_impl == "threefry2x32")


def _coin_words(k_even: jax.Array, k_odd: jax.Array, m: int, L: int
                ) -> jnp.ndarray:
    """(m, L) uint32: row ``r`` is row ``r`` of ``bits(k, (m, L))`` with
    ``k = k_even`` for even ``r`` and ``k_odd`` for odd ``r`` — the
    partitionable threefry's word ``(r, l)``, hashed once."""
    if m * L >= 2 ** 32:
        raise ValueError(f"a coin plane of {m}×{L} words needs the "
                         "counter's high word, which is taken as 0")
    row = jax.lax.broadcasted_iota(jnp.uint32, (m, L), 0)
    col = jax.lax.broadcasted_iota(jnp.uint32, (m, L), 1)
    even = row % 2 == 0
    k_even = jax.random.key_data(k_even)
    k_odd = jax.random.key_data(k_odd)
    k1 = jnp.where(even, k_even[0], k_odd[0])
    k2 = jnp.where(even, k_even[1], k_odd[1])
    x0, x1 = threefry2x32_p.bind(k1, k2, jnp.zeros_like(row), row * L + col)
    return x0 ^ x1


def epoch_draws(key: jax.Array, n_samples: int, n_clauses: int,
                n_literals: int, n_classes: int,
                p_inc: float, p_dec: float):
    """One epoch's randomness, reference key discipline (see module doc).

    Returns ``(offsets, act, coin)``:

    * ``offsets`` (S,) int32 — negative-class offset in [1, C);
    * ``act``     (S, 2, m) int32 — clause-activation draws as 23-bit
      integers (:func:`act_bits`), role 0 = target, 1 = negative;
    * ``coin``    (S, m, L) int8 — bit 1: Type-I increment draw hit
      (``u < p_inc``), bit 2: decrement draw hit (``u < p_dec``); even
      rows from the target role's coin keys, odd rows from the
      negative role's.
    """
    m, L = n_clauses, n_literals
    t_inc = int_threshold(p_inc)
    t_dec = int_threshold(p_dec)
    keys = jax.random.split(key, n_samples)
    merged = merged_coins()
    even = (jnp.arange(m) % 2 == 0)[:, None]

    def plane(kt, kn):
        """23-bit words: the target's (kt) on even rows, kn's on odd."""
        if merged:
            return _coin_words(kt, kn, m, L) >> 9
        return jnp.where(even, jax.random.bits(kt, (m, L), jnp.uint32),
                         jax.random.bits(kn, (m, L), jnp.uint32)) >> 9

    def per_sample(_, k):
        k_neg, k_t, k_n = jax.random.split(k, 3)
        ka_t, k1_t, k2_t = jax.random.split(k_t, 3)
        ka_n, k1_n, k2_n = jax.random.split(k_n, 3)
        h1 = plane(k1_t, k1_n)
        h2 = plane(k2_t, k2_n)
        coin = ((h1 < t_inc).astype(jnp.int8)
                + 2 * (h2 < t_dec).astype(jnp.int8))
        act = jnp.stack([act_bits(ka_t, (m,)), act_bits(ka_n, (m,))])
        off = jax.random.randint(k_neg, (), 1, n_classes)
        return 0, (off.astype(jnp.int32), act, coin)

    _, (offsets, act, coin) = jax.lax.scan(per_sample, 0, keys)
    return offsets, act, coin
