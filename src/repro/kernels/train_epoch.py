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
key words and its two roles' row orders arrive the same way as SMEM
blocks.  The per-(client,
sample) class pair is scalar-prefetched into SMEM and indexes the
resident bank directly (a dynamic index on its leading, untiled axis).

**The Type-I coins are hashed here**, not drawn outside, and only for
the rows that read them.  A sample first computes both roles' ``fired``,
votes, activations and weight updates from the pre-sample banks.  Each
clause row then takes Type I from one role and Type II from the other —
on an even row the target's Type I and the negative's Type II, on an
odd row the reverse — and the two touch disjoint (bank, row) pairs, so
they run as two passes in either order:

* **Type II, dense.**  The sample walks the clauses in blocks of
  :data:`_ROWS` rows, one ``(_ROWS, 128)`` tile at a time, and raises
  the excluded automata of each fired, active Type-II row where the
  literal is false.  It draws nothing.
* **Type I, packed.**  A role's Type-I row is active iff its activation
  draw is under the sample's threshold, and the sample's row order
  (``draws.epoch_draws``' ``order``, one SMEM block a sample) sorts the
  role's Type-I rows by that draw, so its active rows are the first
  ``n_r`` of the order — ``n_r`` counted from the activations, a
  scalar.  The pass gathers them :data:`_GROUP` rows at a time into an
  ``(_GROUP, L)`` tile, and for each automaton ``(r, l)`` hashes one
  threefry word (:func:`repro.kernels.draws.coin_word`): the role's
  ``k_s1`` word where its clause fired and the literal is true (the
  increment coin), its ``k_s2`` word elsewhere (the decrement coin), at
  counter ``r·L + l`` of the unpadded plane.  The word is compared
  against the matching integer threshold, the row takes its transitions,
  and is scattered back; the last group's spare slots write nothing.
  Inactive rows are neither hashed nor touched.  Padded lanes are masked
  off the coin.

The kernel also counts, per client, the Type-I rows it hashed (``Σ
n_r`` over samples and roles): the engine's ``tm.type1_hash_share``.

VMEM holds one client's bank and weights, the per-row flag column, the
Type-I group's gathered rows, two samples' streamed blocks and the
pre-sample pass's ``(m, L)`` temporaries — under 32 MiB at the paper's
MNIST widths (C=10, m=300, L=1568).  :func:`vmem_bytes` computes it;
:func:`repro.fl.runtime.engine.Engine` refuses ``tm_backend="pallas"``
for a machine whose need exceeds :data:`VMEM_BUDGET`.

Bit-parity with the reference scan (pinned in ``tests/test_tm.py`` and
``tests/test_fl_conformance.py``) holds because:

* the offsets and activation draws are made outside with the reference
  key discipline (:mod:`repro.kernels.draws`), and the clause-activation
  compare runs against the same host-built integer threshold table on
  both paths;
* every coin an active Type-I automaton reads is the reference's word
  of ``bits(k_s1 | k_s2, (m, L))``, hashed from the same key and
  counter and compared against the same integer threshold; the coin it
  does not read, and every coin of an inactive row, is not hashed;
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
# clause rows a Type-II step covers, one (_ROWS, 128) tile at a time
_ROWS = 16
# active Type-I rows a hashing step gathers: each step hashes (_GROUP,
# 128) words, one vreg, so the unrolled threefry's temporaries stay in
# vregs
_GROUP = 8


def vmem_bytes(n_classes: int, n_clauses: int, n_literals: int) -> int:
    """VMEM the epoch kernel holds for one client: the int32 TA bank, the
    lane-padded weight column, the per-row flag column, the Type-I
    group's gathered rows, two samples' double-buffered literal rows and
    activation columns, and the pre-sample pass's (m, L) int32
    temporaries."""
    C, m = n_classes, _ceil_to(n_clauses, _ROWS)
    L = _ceil_to(n_literals, _LANES)
    bank = 4 * C * m * L + 4 * _GROUP * (L + _LANES)
    columns = 4 * (C + 1) * m * _LANES
    stream = 2 * (2 * 4 * m * _LANES + 4 * 8 * L)
    temps = 3 * 4 * m * L
    return bank + columns + stream + temps


def _ceil_to(x: int, mult: int) -> int:
    return (x + mult - 1) // mult * mult


def _epoch_kernel(cls_ref, ta_hbm, w_hbm, lits_ref, act_ref, key_ref,
                  ord_ref, thr_ref, ta_out, w_out, cnt_out, bank, wbank,
                  flags, stage, fstage, sem, *, n_states: int, T: int,
                  n_samples: int, n_clauses: int, n_literals: int,
                  half: int, t_inc: int, t_dec: int):
    n, s = pl.program_id(0), pl.program_id(1)

    @pl.when(s == 0)
    def _load():
        cp_ta = pltpu.make_async_copy(ta_hbm.at[n], bank, sem.at[0])
        cp_w = pltpu.make_async_copy(w_hbm.at[n], wbank, sem.at[1])
        cp_ta.start()
        cp_w.start()
        cnt_out[0, 0, 0] = 0
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
    fired, n_type1, t2f = [], [], []
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
        # the role's active Type-I rows: a prefix of its row order
        n_type1.append(jnp.sum(t1r.astype(jnp.int32)))
        t2f.append((t2r & f).astype(jnp.int32))
    cnt_out[0, 0, 0] += n_type1[0] + n_type1[1]

    # Per row, lane-replicated: bit 0 the target's fired, bit 1 the
    # negative's, bit 2 the fired activation of the row's Type-II role
    # (the negative on an even row, the target on an odd one)
    flags[...] = jnp.broadcast_to(
        fired[0] | fired[1] << 1 | jnp.where(pos, t2f[1], t2f[0]) << 2,
        flags.shape)

    # Type II, dense: each row takes it from one role, the role whose
    # Type I it does not take.  It reads the pre-sample fired and the
    # automaton's own state, and draws nothing
    sub = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, _LANES), 0)
    even = sub % 2 == 0                 # _ROWS is even: r0 + sub alike

    def block(b, carry):
        rows = pl.ds(pl.multiple_of(b * _ROWS, _ROWS), _ROWS)
        type2f = (flags[rows, :] & 4) != 0             # (R, 128)
        for c0 in range(0, L, _LANES):                 # static lane tiles
            lanes = pl.ds(c0, _LANES)
            raise_ = type2f & ~lit[:, c0:c0 + _LANES]
            for role in (0, 1):
                ta = bank[cls[role], rows, lanes]      # (R, 128)
                up = raise_ & (ta <= n_states) & (~even if role == 0
                                                  else even)
                bank[cls[role], rows, lanes] = ta + up.astype(jnp.int32)
        return carry

    jax.lax.fori_loop(0, m // _ROWS, block, 0)

    # Type I, packed: only a role's active Type-I rows are hashed, eight
    # at a time.  The sample's row order sorts each role's Type-I rows by
    # their activation draw, so the active ones are its first n_type1.
    # Each group gathers its rows into (8, L), hashes one word an
    # automaton — the role's increment coin (k_s1) where its clause
    # fired and the literal is true, its decrement coin (k_s2) elsewhere,
    # at counter r·L + l of the unpadded plane — and scatters back the
    # rows it owns; the last group's spare slots write nothing.
    sub8 = jax.lax.broadcasted_iota(jnp.int32, (_GROUP, _LANES), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (_GROUP, _LANES), 1)

    def type1(role):
        c, count = cls[role], n_type1[role]
        inc0, inc1, dec0, dec1 = (key_ref[0, 0, 4 * role + i]
                                  for i in range(4))

        def group(g, carry):
            slot0 = g * _GROUP
            rs = [ord_ref[0, 0, role * half + slot0 + i]
                  for i in range(_GROUP)]
            rowv = jnp.zeros((_GROUP, _LANES), jnp.int32)
            for i, r in enumerate(rs):
                stage[pl.ds(i, 1), :] = bank[c, pl.ds(r, 1), :]
                fstage[pl.ds(i, 1), :] = flags[pl.ds(r, 1), :]
                rowv = jnp.where(sub8 == i, r, rowv)
            fired1 = (fstage[...] >> role & 1) != 0    # (8, 128)
            jrow = rowv * n_literals + col
            for c0 in range(0, L, _LANES):             # static lane tiles
                lanes = slice(c0, c0 + _LANES)
                up = fired1 & lit[:, lanes]
                word = draws.coin_word(jnp.where(up, inc0, dec0),
                                       jnp.where(up, inc1, dec1),
                                       jrow + c0, n_clauses * n_literals)
                u = jax.lax.bitcast_convert_type(word >> 9, jnp.int32)
                hit = u < jnp.where(up, t_inc, t_dec)
                if c0 + _LANES > n_literals:           # padded lanes here
                    hit = hit & (c0 + col < n_literals)
                stage[:, lanes] = jnp.clip(
                    stage[:, lanes] + jnp.where(hit, jnp.where(up, 1, -1),
                                                0), 1, 2 * n_states)
            for i, r in enumerate(rs):
                @pl.when(slot0 + i < count)
                def _scatter():
                    bank[c, pl.ds(r, 1), :] = stage[pl.ds(i, 1), :]
            return carry

        jax.lax.fori_loop(0, (count + _GROUP - 1) // _GROUP, group, 0)

    type1(0)
    type1(1)

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
                       order: jax.Array, *, n_states: int, T: int, p_inc: float,
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
      order:     (N, S, 2, ⌈m/2⌉) int32 — per sample and role, the
                 role's Type-I rows ascending by activation draw
                 (``draws.epoch_draws``); the kernel hashes the active
                 prefix.

    Returns ``(ta_state, weights, hashed)`` after the sample-sequential
    epoch: the state bit-identical to the reference ``tm.train_epoch``
    per client, and ``hashed`` (N,) int32, the Type-I rows each client's
    epoch hashed (its active Type-I rows, summed over samples and
    roles).
    """
    N, C, m0, L0 = ta_state.shape
    S = lits.shape[1]
    # Mosaic slices VMEM only along (8, 128) tiles: pad clauses to the
    # Type-II block's rows and literals to 128.  Padded clauses are never
    # activated (the kernel masks them); padded literals are excluded
    # (state 1) and read as true, and the kernel masks them off the coin,
    # so no feedback reaches them.  Each role's row order is padded to
    # whole groups; the kernel reads those slots but never writes them.
    m, L = _ceil_to(m0, _ROWS), _ceil_to(L0, _LANES)
    pad_m, pad_l = m - m0, L - L0
    half = _ceil_to(order.shape[-1], _GROUP)
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
        order_p = jnp.pad(order.astype(jnp.int32),
                          ((0, 0), (0, 0), (0, 0),
                           (0, half - order.shape[-1]))
                          ).reshape(N * S, 1, 2 * half)
    table = draws.activation_thresholds(T)
    thr = jnp.zeros((1, _ceil_to(table.size, _LANES)), jnp.int32)
    thr = thr.at[0, :table.size].set(table)
    kernel = functools.partial(
        _epoch_kernel, n_states=n_states, T=T, n_samples=S, n_clauses=m0,
        n_literals=L0, half=half, t_inc=draws.int_threshold(p_inc),
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
            pl.BlockSpec((1, 1, 2 * half),
                         lambda n, s, c: (n * S + s, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(thr.shape, lambda n, s, c: (0, 0)),
        ],
        # the hashed-row count stays resident across a client's samples;
        # (1, 1) is the whole of the last two axes
        out_specs=[hbm, hbm,
                   pl.BlockSpec((1, 1, 1), lambda n, s, c: (n, 0, 0),
                                memory_space=pltpu.SMEM)],
        scratch_shapes=[pltpu.VMEM((C, m, L), jnp.int32),
                        pltpu.VMEM((C, m, _LANES), jnp.int32),
                        pltpu.VMEM((m, _LANES), jnp.int32),
                        pltpu.VMEM((_GROUP, L), jnp.int32),
                        pltpu.VMEM((_GROUP, _LANES), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    need = vmem_bytes(C, m, L)
    ta, w, hashed = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(ta_p.shape, jnp.int32),
                   jax.ShapeDtypeStruct(w_p.shape, jnp.int32),
                   jax.ShapeDtypeStruct((N, 1, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(VMEM_BUDGET, max(need, 32 << 20))),
        interpret=interpret,
        name="tm_train_epoch_fused",
    )(cls2.reshape(-1).astype(jnp.int32), ta_p, w_p, lits_p, act_p,
      coin_keys.reshape(N * S, 1, 8).astype(jnp.uint32), order_p, thr)
    with jax.named_scope("tm.epoch_pad"):
        return ta[:, :, :m0, :L0], w[:, :, :m0, 0], hashed[:, 0, 0]
