"""Fused TM training-epoch kernel: one ``pallas_call`` per epoch.

The reference ``tm.train_epoch`` scans samples on the host side of the
kernel boundary: each scan step re-launches batch-1 clause evaluation
and two TA updates, so the clause banks round-trip HBM every sample.
This kernel inverts that: a client's whole parameter state (its
``(C, m, L)`` TA bank and ``(C, m)`` weights) is copied into VMEM
scratch once, stays resident while the per-sample feedback loop runs
over it, and is copied back once.

Layout is client-batched: a leading ``N`` axis carries all clients of a
federated round through one launch, as a grid ``(client, sample)``.
The sample axis is sequential (``arbitrary``): the TA bank and weights
live in scratch across it, loaded by DMA at the client's first sample
and stored at its last.  Per-sample inputs — the literal row and the two
roles' activation draws — are ordinary blocks, so the pipeline streams
them from HBM one sample ahead of the compute; the sample's eight coin
key words arrive the same way as an SMEM block.  The per-(client,
sample) class pair is scalar-prefetched into SMEM and indexes the
resident bank directly (a dynamic index on its leading, untiled axis).

**The Type-I coins are hashed here**, not drawn outside.  A sample
first computes both roles' ``fired``, votes, activations and weight
updates from the pre-sample banks.  Each clause row then takes Type I
from one role and Type II from the other — on an even row the target's
Type I and the negative's Type II, on an odd row the reverse — so the
sample walks the clauses in blocks of :data:`_ROWS` rows, one
``(_ROWS, 128)`` tile at a time, and for each automaton ``(r, l)``
hashes one threefry word (:func:`repro.kernels.draws.coin_word`): the
Type-I role's ``k_s1`` word where its clause fired and the literal is
true (the increment coin), its ``k_s2`` word elsewhere (the decrement
coin), at counter ``r·L + l`` of the unpadded plane.  The word is
compared against the matching integer threshold, and both roles' banks
take the tile's transitions.  Padded rows are never activated, and
padded lanes are masked off the coin.

VMEM holds one client's bank and weights, the per-row flag and key
columns, two samples' streamed blocks and the pre-sample pass's
``(m, L)`` temporaries — under 32 MiB at the paper's MNIST widths
(C=10, m=300, L=1568).  :func:`vmem_bytes` computes it;
:func:`repro.fl.runtime.engine.Engine` refuses ``tm_backend="pallas"``
for a machine whose need exceeds :data:`VMEM_BUDGET`.

Bit-parity with the reference scan (pinned in ``tests/test_tm.py`` and
``tests/test_fl_conformance.py``) holds because:

* the offsets and activation draws are made outside with the reference
  key discipline (:mod:`repro.kernels.draws`), and the clause-activation
  compare runs against the same host-built integer threshold table on
  both paths;
* every coin a Type-I automaton reads is the reference's word of
  ``bits(k_s1 | k_s2, (m, L))``, hashed from the same key and counter
  and compared against the same integer threshold; the coin it does not
  read is not hashed;
* class votes are per-class independent — ``votes[c]`` reads only class
  ``c``'s clauses/weights, and the negative class ``ȳ ≠ y`` — so both
  roles' pre-sample values can be computed before either bank changes,
  exactly the reference's values;
* violation counts and votes are int32 sums: no float rounding anywhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import draws

# Scoped-VMEM ceiling the kernel may request.  A v5e TensorCore has
# 128 MiB of VMEM (jax.experimental.pallas.tpu.get_tpu_info); the rest is
# left to the compiler's own buffers.
VMEM_BUDGET = 96 * 1024 * 1024
_LANES = 128
# clause rows a coin-hashing step covers: each step hashes (_ROWS, 128)
# words, so the unrolled threefry's temporaries stay in vregs
_ROWS = 16


def vmem_bytes(n_classes: int, n_clauses: int, n_literals: int) -> int:
    """VMEM the epoch kernel holds for one client: the int32 TA bank, the
    lane-padded weight column, the per-row flag and coin-key columns,
    two samples' double-buffered literal rows and activation columns,
    and the pre-sample pass's (m, L) int32 temporaries."""
    C, m = n_classes, _ceil_to(n_clauses, _ROWS)
    L = _ceil_to(n_literals, _LANES)
    bank = 4 * C * m * L
    columns = 4 * (C + 5) * m * _LANES
    stream = 2 * (2 * 4 * m * _LANES + 4 * 8 * L)
    temps = 3 * 4 * m * L
    return bank + columns + stream + temps


def _ceil_to(x: int, mult: int) -> int:
    return (x + mult - 1) // mult * mult


def _epoch_kernel(cls_ref, ta_hbm, w_hbm, lits_ref, act_ref, key_ref,
                  thr_ref, ta_out, w_out, bank, wbank, flags, keys, sem, *,
                  n_states: int, T: int, n_samples: int, n_clauses: int,
                  n_literals: int, t_inc: int, t_dec: int):
    n, s = pl.program_id(0), pl.program_id(1)

    @pl.when(s == 0)
    def _load():
        cp_ta = pltpu.make_async_copy(ta_hbm.at[n], bank, sem.at[0])
        cp_w = pltpu.make_async_copy(w_hbm.at[n], wbank, sem.at[1])
        cp_ta.start()
        cp_w.start()
        cp_ta.wait()
        cp_w.wait()

    m, L = bank.shape[1:]
    lit = lits_ref[0, 0] != 0                          # (1, L)
    row = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    pos = row % 2 == 0                                 # positive polarity
    real = row < n_clauses                             # padded rows stay put
    pol = jnp.where(pos, 1, -1)
    lane = jax.lax.broadcasted_iota(jnp.int32, thr_ref.shape, 1)
    cls = [cls_ref[(n * n_samples + s) * 2 + role] for role in (0, 1)]

    # both roles' pre-sample values first: y ≠ ȳ, so neither role's
    # update touches the bank the other reads
    fired, t1, t2f = [], [], []
    for role in (0, 1):                                # target, negative
        ta = bank[cls[role]]                           # (m, L)
        w = wbank[cls[role]][:, :1]                    # (m, 1)
        viol = jnp.sum(jnp.where((ta > n_states) & ~lit, 1, 0), axis=1,
                       keepdims=True)
        f = viol == 0                                  # (m, 1)
        votes = jnp.sum(jnp.where(f, pol * w, 0), axis=0,
                        keepdims=True)                 # (1, 1)
        v = jnp.clip(votes, -T, T)
        idx = T - v if role == 0 else T + v
        thr = jnp.sum(jnp.where(lane == idx, thr_ref[...], 0), axis=1,
                      keepdims=True)                   # (1, 1)
        active = (act_ref[0, 0, role] < thr) & real    # (m, 1)
        # Type I goes to same-polarity clauses on the target, opposite on
        # the negative; Type II is the complement
        t1r = (pos if role == 0 else ~pos) & active
        t2r = (~pos if role == 0 else pos) & active
        wbank[cls[role]] = jnp.broadcast_to(jnp.maximum(
            w + (t1r & f).astype(jnp.int32) - (t2r & f).astype(jnp.int32),
            0), wbank.shape[1:])
        fired.append(f.astype(jnp.int32))
        t1.append(t1r.astype(jnp.int32))
        t2f.append((t2r & f).astype(jnp.int32))

    # Each row takes Type I from one role and Type II from the other: on
    # an even row the target's Type I and the negative's Type II, on an
    # odd row the reverse.  Per row, lane-replicated: bit 0 the Type-I
    # role's activation, bit 1 its fired, bit 2 the Type-II role's
    # fired activation; and the Type-I role's coin keys
    flags[...] = jnp.broadcast_to(
        jnp.where(pos, t1[0] | fired[0] << 1 | t2f[1] << 2,
                  t1[1] | fired[1] << 1 | t2f[0] << 2), flags.shape)
    # the sample's coin keys: target k_s1, k_s2, negative k_s1, k_s2
    key = [key_ref[0, 0, i] for i in range(8)]
    for i in range(4):                  # k_s1 words 0, 1; k_s2 words 0, 1
        keys[i] = jnp.broadcast_to(
            jnp.where(pos, key[i], key[4 + i]), keys.shape[1:])

    sub = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, _LANES), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, _LANES), 1)
    even = sub % 2 == 0                 # _ROWS is even: r0 + sub alike
    base = sub * n_literals + col       # counter of (sub, col)

    def block(b, carry):
        r0 = pl.multiple_of(b * _ROWS, _ROWS)
        rows = pl.ds(r0, _ROWS)
        f = flags[rows, :]                             # (R, 128)
        type1 = (f & 1) != 0
        fired1 = (f & 2) != 0
        type2f = (f & 4) != 0
        inc0, inc1, dec0, dec1 = (keys[i, rows, :] for i in range(4))
        jb = base + r0 * n_literals
        for c0 in range(0, L, _LANES):                 # static lane tiles
            lanes = pl.ds(c0, _LANES)
            lit_c = lit[:, c0:c0 + _LANES]
            # the Type-I role reads its increment coin (k_s1) where its
            # clause fired and the literal is true, else its decrement
            # coin (k_s2): one word an automaton
            up = fired1 & lit_c
            word = draws.coin_word(jnp.where(up, inc0, dec0),
                                   jnp.where(up, inc1, dec1), jb + c0,
                                   n_clauses * n_literals)
            u = jax.lax.bitcast_convert_type(word >> 9, jnp.int32)
            hit = type1 & (u < jnp.where(up, t_inc, t_dec))
            if c0 + _LANES > n_literals:               # padded lanes here
                hit = hit & (c0 + col < n_literals)
            d1 = jnp.where(hit, jnp.where(up, 1, -1), 0)
            for role in (0, 1):
                ta = bank[cls[role], rows, lanes]      # (R, 128)
                d2 = (type2f & ~lit_c & (ta <= n_states)).astype(jnp.int32)
                delta = jnp.where(even if role == 0 else ~even, d1, d2)
                bank[cls[role], rows, lanes] = jnp.clip(ta + delta, 1,
                                                        2 * n_states)
        return carry

    jax.lax.fori_loop(0, m // _ROWS, block, 0)

    @pl.when(s == n_samples - 1)
    def _store():
        cp_ta = pltpu.make_async_copy(bank, ta_out.at[n], sem.at[0])
        cp_w = pltpu.make_async_copy(wbank, w_out.at[n], sem.at[1])
        cp_ta.start()
        cp_w.start()
        cp_ta.wait()
        cp_w.wait()


@functools.partial(jax.jit,
                   static_argnames=("n_states", "T", "p_inc", "p_dec",
                                    "interpret"))
def train_epoch_pallas(ta_state: jax.Array, weights: jax.Array,
                       lits: jax.Array, cls2: jax.Array,
                       act: jax.Array, coin_keys: jax.Array,
                       *, n_states: int, T: int, p_inc: float,
                       p_dec: float, interpret: bool):
    """One TM epoch over all clients in a single kernel launch.

    Args:
      ta_state:  (N, C, m, L) int32 — per-client TA banks.
      weights:   (N, C, m) int32 — per-client clause weights.
      lits:      (N, S, L) int32 0/1 — per-client literal planes.
      cls2:      (N, S, 2) int32 — per (client, sample): [target, negative].
      act:       (N, S, 2, m) int32 — 23-bit activation draws per role.
      coin_keys: (N, S, 8) uint32 — per sample, ``key_data`` of the
                 target's ``k_s1``, ``k_s2`` and the negative's
                 (``draws.epoch_draws``); the kernel hashes the Type-I
                 coins from them, ``p_inc`` / ``p_dec`` their odds.

    Returns ``(ta_state, weights)`` after the sample-sequential epoch,
    bit-identical to the reference ``tm.train_epoch`` per client.
    """
    N, C, m0, L0 = ta_state.shape
    S = lits.shape[1]
    # Mosaic slices VMEM only along (8, 128) tiles: pad clauses to the
    # hashing block's rows and literals to 128.  Padded clauses are never activated (the kernel
    # masks them); padded literals are excluded (state 1) and read as
    # true, and the kernel masks them off the coin, so no feedback
    # reaches them.
    m, L = _ceil_to(m0, _ROWS), _ceil_to(L0, _LANES)
    pad_m, pad_l = m - m0, L - L0
    # the pads and re-layouts are named in the device trace (op metadata
    # only: the compiled program is the same)
    with jax.named_scope("tm.epoch_pad"):
        ta_p = jnp.pad(ta_state.astype(jnp.int32),
                       ((0, 0), (0, 0), (0, pad_m), (0, pad_l)),
                       constant_values=1)
        # weights travel as lane-replicated (m, 128) columns: a DMA moves
        # whole (8, 128) tiles, so a width-1 lane slice cannot be copied
        w_p = jnp.broadcast_to(
            jnp.pad(weights.astype(jnp.int32),
                    ((0, 0), (0, 0), (0, pad_m)))[..., None],
            (N, C, m, _LANES))
        lits_p = jnp.pad(lits, ((0, 0), (0, 0), (0, pad_l)),
                         constant_values=1)[:, :, None, :]
        act_p = jnp.pad(act,
                        ((0, 0), (0, 0), (0, 0), (0, pad_m)))[..., None]
    table = draws.activation_thresholds(T)
    thr = jnp.zeros((1, _ceil_to(table.size, _LANES)), jnp.int32)
    thr = thr.at[0, :table.size].set(table)
    kernel = functools.partial(
        _epoch_kernel, n_states=n_states, T=T, n_samples=S, n_clauses=m0,
        n_literals=L0, t_inc=draws.int_threshold(p_inc),
        t_dec=draws.int_threshold(p_dec))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N, S),
        in_specs=[
            hbm,
            hbm,
            pl.BlockSpec((1, 1, 1, L), lambda n, s, c: (n, s, 0, 0)),
            pl.BlockSpec((1, 1, 2, m, 1), lambda n, s, c: (n, s, 0, 0, 0)),
            # (1, 8) is the whole of the last two axes, as a block's
            # last two axes must be
            pl.BlockSpec((1, 1, 8), lambda n, s, c: (n * S + s, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(thr.shape, lambda n, s, c: (0, 0)),
        ],
        out_specs=[hbm, hbm],
        scratch_shapes=[pltpu.VMEM((C, m, L), jnp.int32),
                        pltpu.VMEM((C, m, _LANES), jnp.int32),
                        pltpu.VMEM((m, _LANES), jnp.int32),
                        pltpu.VMEM((4, m, _LANES), jnp.uint32),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    need = vmem_bytes(C, m, L)
    ta, w = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(ta_p.shape, jnp.int32),
                   jax.ShapeDtypeStruct(w_p.shape, jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(VMEM_BUDGET, max(need, 32 << 20))),
        interpret=interpret,
        name="tm_train_epoch_fused",
    )(cls2.reshape(-1).astype(jnp.int32), ta_p, w_p, lits_p, act_p,
      coin_keys.reshape(N * S, 1, 8).astype(jnp.uint32), thr)
    with jax.named_scope("tm.epoch_pad"):
        return ta[:, :, :m0, :L0], w[:, :, :m0, 0]
