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
and stored at its last.  Per-sample inputs — the literal row, the two
roles' activation draws and the sample's ``(m, L)`` int8 coin plane — are
ordinary blocks, so the pipeline streams them from HBM one sample ahead
of the compute.  The per-(client, sample) class pair is scalar-prefetched
into SMEM and indexes the resident bank directly (a dynamic index on its
leading, untiled axis).

VMEM holds one client's bank and weights, two samples' coin planes
and one feedback step's ``(m, L)`` temporaries — 34 MiB at the paper's
MNIST widths (C=10, m=300, L=1568).  :func:`vmem_bytes` computes it;
:func:`repro.fl.runtime.engine.Engine` refuses ``tm_backend="pallas"``
for a machine whose need exceeds :data:`VMEM_BUDGET`.

Bit-parity with the reference scan (pinned in ``tests/test_tm.py`` and
``tests/test_fl_conformance.py``) holds because:

* randomness is pre-generated outside with the reference key discipline
  (:mod:`repro.kernels.draws`), and the clause-activation compare runs
  against the same host-built integer threshold table on both paths;
* both roles read one coin plane: the coins matter only on Type-I rows,
  the even rows on the target role and the odd rows on the negative,
  and the plane holds each role's words on exactly those rows;
* class votes are per-class independent — ``votes[c]`` reads only class
  ``c``'s clauses/weights, and the negative class ``ȳ ≠ y`` — so
  processing (sample, target-role) then (sample, negative-role) in turn
  recomputes exactly the reference's pre-sample values;
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


def vmem_bytes(n_classes: int, n_clauses: int, n_literals: int) -> int:
    """VMEM the epoch kernel holds for one client: the int32 TA bank, the
    lane-padded weight column, two samples' double-buffered coin planes
    and activation columns, and the (m, L) int32 temporaries of one
    feedback step."""
    C, m, L = n_classes, _ceil_to(n_clauses, 8), _ceil_to(n_literals, _LANES)
    bank = 4 * C * m * L
    weights = 4 * C * m * _LANES
    stream = 2 * (m * L + 2 * 4 * m * _LANES + 4 * 8 * L)
    temps = 6 * 4 * m * L
    return bank + weights + stream + temps


def _ceil_to(x: int, mult: int) -> int:
    return (x + mult - 1) // mult * mult


def _epoch_kernel(cls_ref, ta_hbm, w_hbm, lits_ref, act_ref, coin_ref,
                  thr_ref, ta_out, w_out, bank, wbank, sem, *,
                  n_states: int, T: int, n_samples: int, n_clauses: int):
    n, s = pl.program_id(0), pl.program_id(1)

    @pl.when(s == 0)
    def _load():
        cp_ta = pltpu.make_async_copy(ta_hbm.at[n], bank, sem.at[0])
        cp_w = pltpu.make_async_copy(w_hbm.at[n], wbank, sem.at[1])
        cp_ta.start()
        cp_w.start()
        cp_ta.wait()
        cp_w.wait()

    m = bank.shape[1]
    lit = lits_ref[0, 0] != 0                          # (1, L)
    row = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    pos = row % 2 == 0                                 # positive polarity
    real = row < n_clauses                             # padded rows stay put
    pol = jnp.where(pos, 1, -1)
    lane = jax.lax.broadcasted_iota(jnp.int32, thr_ref.shape, 1)

    for role in (0, 1):                                # target, negative
        cls = cls_ref[(n * n_samples + s) * 2 + role]
        ta = bank[cls]                                 # (m, L)
        w = wbank[cls][:, :1]                          # (m, 1)
        inc = ta > n_states
        viol = jnp.sum(jnp.where(inc & ~lit, 1, 0), axis=1, keepdims=True)
        fired = viol == 0                              # (m, 1)
        votes = jnp.sum(jnp.where(fired, pol * w, 0), axis=0,
                        keepdims=True)                 # (1, 1)
        v = jnp.clip(votes, -T, T)
        idx = T - v if role == 0 else T + v
        thr = jnp.sum(jnp.where(lane == idx, thr_ref[...], 0), axis=1,
                      keepdims=True)                   # (1, 1)
        active = (act_ref[0, 0, role] < thr) & real    # (m, 1)
        # Type I goes to same-polarity clauses on the target, opposite on
        # the negative; Type II is the complement
        t1 = (pos if role == 0 else ~pos) & active
        t2 = (~pos if role == 0 else pos) & active
        t1f, t2f = t1 & fired, t2 & fired

        # one coin plane serves both roles: each reads its Type-I rows
        cn = coin_ref[0, 0].astype(jnp.int32)          # (m, L)
        up1 = t1f & lit & ((cn & 1) != 0)
        down1 = t1 & ~(fired & lit) & ((cn & 2) != 0)
        up2 = t2f & ~lit & ~inc
        delta = (up1.astype(jnp.int32) - down1.astype(jnp.int32)
                 + up2.astype(jnp.int32))
        bank[cls] = jnp.clip(ta + delta, 1, 2 * n_states)
        wbank[cls] = jnp.broadcast_to(jnp.maximum(
            w + t1f.astype(jnp.int32) - t2f.astype(jnp.int32), 0),
            wbank.shape[1:])

    @pl.when(s == n_samples - 1)
    def _store():
        cp_ta = pltpu.make_async_copy(bank, ta_out.at[n], sem.at[0])
        cp_w = pltpu.make_async_copy(wbank, w_out.at[n], sem.at[1])
        cp_ta.start()
        cp_w.start()
        cp_ta.wait()
        cp_w.wait()


@functools.partial(jax.jit,
                   static_argnames=("n_states", "T", "interpret"))
def train_epoch_pallas(ta_state: jax.Array, weights: jax.Array,
                       lits: jax.Array, cls2: jax.Array,
                       act: jax.Array, coin: jax.Array,
                       *, n_states: int, T: int, interpret: bool):
    """One TM epoch over all clients in a single kernel launch.

    Args:
      ta_state: (N, C, m, L) int32 — per-client TA banks.
      weights:  (N, C, m) int32 — per-client clause weights.
      lits:     (N, S, L) int32 0/1 — per-client literal planes.
      cls2:     (N, S, 2) int32 — per (client, sample): [target, negative].
      act:      (N, S, 2, m) int32 — 23-bit activation draws per role.
      coin:     (N, S, m, L) int8 — pre-compared Type-I coin flips, the
                target role's on even rows, the negative's on odd.

    Returns ``(ta_state, weights)`` after the sample-sequential epoch,
    bit-identical to the reference ``tm.train_epoch`` per client.
    """
    N, C, m0, L0 = ta_state.shape
    S = lits.shape[1]
    # Mosaic slices VMEM only along (8, 128) tiles: pad clauses to 8 and
    # literals to 128.  Padded clauses are never activated (the kernel
    # masks them); padded literals are excluded (state 1) and read as
    # true, so no feedback reaches them.
    m, L = _ceil_to(m0, 8), _ceil_to(L0, _LANES)
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
        coin_p = jnp.pad(coin, ((0, 0), (0, 0), (0, pad_m), (0, pad_l)))
    table = draws.activation_thresholds(T)
    thr = jnp.zeros((1, _ceil_to(table.size, _LANES)), jnp.int32)
    thr = thr.at[0, :table.size].set(table)
    kernel = functools.partial(_epoch_kernel, n_states=n_states, T=T,
                               n_samples=S, n_clauses=m0)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N, S),
        in_specs=[
            hbm,
            hbm,
            pl.BlockSpec((1, 1, 1, L), lambda n, s, c: (n, s, 0, 0)),
            pl.BlockSpec((1, 1, 2, m, 1), lambda n, s, c: (n, s, 0, 0, 0)),
            pl.BlockSpec((1, 1, m, L), lambda n, s, c: (n, s, 0, 0)),
            pl.BlockSpec(thr.shape, lambda n, s, c: (0, 0)),
        ],
        out_specs=[hbm, hbm],
        scratch_shapes=[pltpu.VMEM((C, m, L), jnp.int32),
                        pltpu.VMEM((C, m, _LANES), jnp.int32),
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
    )(cls2.reshape(-1).astype(jnp.int32), ta_p, w_p, lits_p, act_p, coin_p,
      thr)
    with jax.named_scope("tm.epoch_pad"):
        return ta[:, :, :m0, :L0], w[:, :, :m0, 0]
