"""Pallas TPU kernel for the Tsetlin-Automaton state transition.

The TA update is a memory-bound elementwise op over the `(m, 2o)` state
tile of the two classes touched per training sample (target + sampled
negative).  The kernel fuses the Type I / Type II feedback masks, the
stochastic reward/penalty draws (uniforms generated outside, passed in),
the delta and the `[1, 2N]` clamp into a single VMEM pass — one read and
one write of the state tile instead of the ~8 intermediate tensors the
unfused jnp path materializes.

Tiling: grid over `(m/mt, L/lt)`; per-step residency is one `(mt, lt)`
int32 state tile + two uniform tiles + broadcast rows/cols, well under
VMEM at the default (256, 512) tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ceil_to(x: int, mult: int) -> int:
    return (x + mult - 1) // mult * mult


def _ta_kernel(ta_ref, lit_ref, fired_ref, t1_ref, t2_ref,
               u_inc_ref, u_dec_ref, out_ref, *,
               p_inc: float, p_dec: float, n_states: int):
    ta = ta_ref[...]
    lit = lit_ref[...] != 0            # (1, lt)  broadcast over clauses
    fired = fired_ref[...] != 0        # (mt, 1)  broadcast over literals
    t1 = t1_ref[...] != 0
    t2 = t2_ref[...] != 0

    up1 = t1 & fired & lit & (u_inc_ref[...] < p_inc)
    down1 = t1 & ((fired & (~lit)) | (~fired)) & (u_dec_ref[...] < p_dec)
    up2 = t2 & fired & (~lit) & (ta <= n_states)
    delta = up1.astype(jnp.int32) - down1.astype(jnp.int32) \
        + up2.astype(jnp.int32)
    out_ref[...] = jnp.clip(ta + delta, 1, 2 * n_states)


@functools.partial(
    jax.jit,
    static_argnames=("p_inc", "p_dec", "n_states", "mt", "lt", "interpret"))
def ta_update_pallas(ta: jnp.ndarray, lit: jnp.ndarray, fired: jnp.ndarray,
                     type1: jnp.ndarray, type2: jnp.ndarray,
                     u_inc: jnp.ndarray, u_dec: jnp.ndarray,
                     p_inc: float, p_dec: float, n_states: int,
                     mt: int = 256, lt: int = 512, *,
                     interpret: bool) -> jnp.ndarray:
    """See :func:`repro.kernels.ref.ta_update_ref` for exact semantics."""
    m, L = ta.shape
    mt = min(mt, _ceil_to(m, 8))
    lt = min(lt, _ceil_to(L, 128))
    mp, Lp = _ceil_to(m, mt), _ceil_to(L, lt)

    def pad(a, rows, cols):
        return jnp.pad(a, ((0, rows - a.shape[0]), (0, cols - a.shape[1])))

    # state pads to 1 (valid) so the clamp never sees 0; masks pad to 0.
    ta_p = jnp.pad(ta, ((0, mp - m), (0, Lp - L)), constant_values=1)
    args = (
        ta_p,
        pad(lit.astype(jnp.int32), 1, Lp),
        pad(fired.astype(jnp.int32), mp, 1),
        pad(type1.astype(jnp.int32), mp, 1),
        pad(type2.astype(jnp.int32), mp, 1),
        pad(u_inc.astype(jnp.float32), mp, Lp),
        pad(u_dec.astype(jnp.float32), mp, Lp),
    )
    out = pl.pallas_call(
        functools.partial(_ta_kernel, p_inc=float(p_inc), p_dec=float(p_dec),
                          n_states=int(n_states)),
        grid=(mp // mt, Lp // lt),
        in_specs=[
            pl.BlockSpec((mt, lt), lambda i, j: (i, j)),
            pl.BlockSpec((1, lt), lambda i, j: (0, j)),
            pl.BlockSpec((mt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((mt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((mt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((mt, lt), lambda i, j: (i, j)),
            pl.BlockSpec((mt, lt), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((mt, lt), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, Lp), jnp.int32),
        interpret=interpret,
        name="tm_ta_update",
    )(*args)
    return out[:m, :L]
