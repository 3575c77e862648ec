"""Pallas TPU kernels for Tsetlin-Machine clause evaluation.

The TM hot-spot is the conjunctive clause evaluation: for every sample `b`
and clause `j`, count how many *included* literals are violated
(`included & literal==0`) — the clause fires iff the count is zero
(paper §4.1).  On TPU this is a boolean-matmul-shaped reduction that maps
straight onto the MXU: the {0,1} operands are exact in bf16/f32 and the
violation counts accumulate exactly in f32 (counts < 2^24).

Two kernels:

* :func:`clause_outputs_pallas` — tiled `(B, L) × (L, CM) → (B, CM)`
  violation count with a k-loop over literal tiles, then `== 0`.
  BlockSpecs keep one `(bt, lt)` literal tile and one `(ct, lt)`
  include tile resident in VMEM per grid step; `bt, ct, lt` default to
  MXU/VPU-aligned multiples of (8, 128).

* :func:`fused_votes_batched_pallas` — clause eval fused with the Eq.-1
  weighted class vote, for a stack of N clients in one launch (the
  engine's evaluate/confidence paths and the serving plane; a single
  client is N = 1).  Grid `(client, class)`: each step holds one client's
  `(B, L)` literal plane and one class's `(m, L)` include bank in VMEM,
  counts violations on the MXU, and sums `fired · (polarity·weight)` over
  the class's clauses in int32 — exact for any weight, so the vote never
  passes through a rounded float product.  The class axis of the output
  is lane-padded to 128: the `(B, 128)` output block stays resident
  across the class steps and step `c` writes lane `c`, which keeps every
  block's last two dims (8, 128)-aligned as the TPU lowering requires.
  The predict-mode empty-clause rule is folded into the weight plane
  (`wpol · nonempty`).

Interpret mode (CPU) or Mosaic (TPU) is chosen by the caller, see
:mod:`repro.kernels.ops`; the test suite pins both kernels against
:mod:`repro.kernels.ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ceil_to(x: int, mult: int) -> int:
    return (x + mult - 1) // mult * mult


def _pad2(a: jnp.ndarray, rows: int, cols: int) -> jnp.ndarray:
    return jnp.pad(a, ((0, rows - a.shape[0]), (0, cols - a.shape[1])))


# ---------------------------------------------------------------------------
# Kernel 1: tiled violation-count matmul → clause outputs
# ---------------------------------------------------------------------------

def _clause_kernel(nlit_ref, inc_ref, out_ref):
    """out[bt, ct] += nlit[bt, lt] @ inc[ct, lt]^T  (f32 accumulation)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    nlit = nlit_ref[...].astype(jnp.float32)
    inc = inc_ref[...].astype(jnp.float32)
    out_ref[...] += jax.lax.dot_general(
        nlit, inc, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("predict", "bt", "ct", "lt", "interpret"))
def clause_outputs_pallas(include: jnp.ndarray, lits: jnp.ndarray,
                          predict: bool = False, bt: int = 8, ct: int = 128,
                          lt: int = 128, *, interpret: bool) -> jnp.ndarray:
    """include: (CM, L) {0,1}; lits: (B, L) {0,1} → fired (B, CM) int32."""
    CM, L = include.shape
    B = lits.shape[0]
    Bp, CMp, Lp = _ceil_to(B, bt), _ceil_to(CM, ct), _ceil_to(L, lt)
    # pad: extra literals are zero in both operands → no violation contribution
    nlit = _pad2((1 - lits).astype(jnp.int8), Bp, Lp)
    # padded literal columns of real clauses must not count as violations:
    # (1-lits) pads to 0 there, so include padding value is irrelevant; pad 0.
    inc = _pad2(include.astype(jnp.int8), CMp, Lp)

    grid = (Bp // bt, CMp // ct, Lp // lt)
    viol = pl.pallas_call(
        _clause_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, lt), lambda b, c, k: (b, k)),
            pl.BlockSpec((ct, lt), lambda b, c, k: (c, k)),
        ],
        out_specs=pl.BlockSpec((bt, ct), lambda b, c, k: (b, c)),
        out_shape=jax.ShapeDtypeStruct((Bp, CMp), jnp.float32),
        interpret=interpret,
        name="tm_clause_eval",
    )(nlit, inc)

    fired = (viol[:B, :CM] == 0).astype(jnp.int32)
    if predict:
        fired = fired * (include.sum(-1) > 0).astype(jnp.int32)[None, :]
    return fired


# ---------------------------------------------------------------------------
# Kernel 2: client-batched fused clause eval + weighted class vote (Eq. 1)
# ---------------------------------------------------------------------------

_LANES = 128


def _votes_kernel(nlit_ref, inc_ref, wp_ref, out_ref):
    """Grid (client n, class c): out[n, :, c] = Σ_j fired[:, j]·wp[c, j]."""
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    viol = jax.lax.dot_general(                        # (Bp, mp)
        nlit_ref[0], inc_ref[0, 0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    contrib = jnp.where(viol == 0.0, wp_ref[0, 0], 0)  # wp: (1, mp) int32
    vote = jnp.sum(contrib, axis=1, keepdims=True)     # (Bp, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape[1:], 1)
    out_ref[0] = jnp.where(lane == c, vote, out_ref[0])


@functools.partial(jax.jit, static_argnames=("predict", "interpret"))
def fused_votes_batched_pallas(include: jnp.ndarray, lits: jnp.ndarray,
                               wpol: jnp.ndarray, predict: bool = True, *,
                               interpret: bool) -> jnp.ndarray:
    """include: (N,C,m,L); lits: (N,B,L); wpol: (N,C,m) → votes (N,B,C) i32.

    Unclipped Eq.-1 votes.  Empty clauses are killed by zeroing their
    weight instead of their clause output — ``fired·(wpol·nonempty) ==
    (fired·nonempty)·wpol`` exactly in int32.  Padded clauses (rows
    m..mp) have empty includes and zero weight; padded literal columns
    are 0 in the negated-literal plane, so they never count as
    violations.
    """
    N, C, m, L = include.shape
    B = lits.shape[1]
    Bp, mp, Lp = _ceil_to(B, 16), _ceil_to(m, _LANES), _ceil_to(L, _LANES)
    inc = jnp.pad(include.astype(jnp.bfloat16),
                  ((0, 0), (0, 0), (0, mp - m), (0, Lp - L)))
    nlit = jnp.pad((1 - lits).astype(jnp.bfloat16),
                   ((0, 0), (0, Bp - B), (0, Lp - L)))
    wp = wpol.astype(jnp.int32)
    if predict:
        wp = wp * (include.sum(-1) > 0).astype(jnp.int32)
    wp = jnp.pad(wp, ((0, 0), (0, 0), (0, mp - m)))[:, :, None, :]
    Cp = _ceil_to(C, _LANES)

    votes = pl.pallas_call(
        _votes_kernel,
        grid=(N, C),
        in_specs=[
            pl.BlockSpec((1, Bp, Lp), lambda n, c: (n, 0, 0)),
            pl.BlockSpec((1, 1, mp, Lp), lambda n, c: (n, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, mp), lambda n, c: (n, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Bp, Cp), lambda n, c: (n, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, Bp, Cp), jnp.int32),
        interpret=interpret,
        name="tm_fused_votes_batched",
    )(nlit, inc, wp)
    return votes[:, :B, :C]
