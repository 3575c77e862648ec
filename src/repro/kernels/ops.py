"""Jit'd public wrappers around the TM Pallas kernels.

This is the one place that decides how a kernel runs: compiled by Mosaic
on a TPU, and in Pallas interpret mode only when JAX's backend is the
CPU (the test suite's harness).  Any other backend compiles, so a
missing accelerator surfaces as an error instead of a silent
interpreter run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import clause_eval as _ce
from repro.kernels import ta_update as _ta
from repro.kernels import train_epoch as _te


def interpret_mode() -> bool:
    return jax.default_backend() == "cpu"


def clause_outputs(include: jnp.ndarray, lits: jnp.ndarray,
                   predict: bool = False) -> jnp.ndarray:
    """include: (C, m, L) or (CM, L); lits: (B, L) → fired int32.

    Returns (B, C, m) when given a 3-D include mask, else (B, CM).
    """
    interp = interpret_mode()
    if include.ndim == 3:
        C, m, L = include.shape
        out = _ce.clause_outputs_pallas(include.reshape(C * m, L), lits,
                                        predict=predict, interpret=interp)
        return out.reshape(lits.shape[0], C, m)
    return _ce.clause_outputs_pallas(include, lits, predict=predict,
                                     interpret=interp)


def fused_votes(include: jnp.ndarray, lits: jnp.ndarray, wpol: jnp.ndarray,
                predict: bool = True) -> jnp.ndarray:
    """(C,m,L) × (B,L) × (C,m) → unclipped Eq.-1 votes (B, C): the
    client-batched kernel at N = 1."""
    return fused_votes_batched(include[None], lits[None], wpol[None],
                               predict=predict)[0]


def fused_votes_batched(include: jnp.ndarray, lits: jnp.ndarray,
                        wpol: jnp.ndarray, predict: bool = True
                        ) -> jnp.ndarray:
    """Client-batched Eq.-1 votes: (N,C,m,L) × (N,B,L) × (N,C,m) → (N,B,C)."""
    return _ce.fused_votes_batched_pallas(include, lits, wpol,
                                          predict=predict,
                                          interpret=interpret_mode())


def train_epoch_fused(ta: jnp.ndarray, w: jnp.ndarray, lits: jnp.ndarray,
                      cls2: jnp.ndarray, act: jnp.ndarray,
                      coin: jnp.ndarray, *, n_states: int, T: int
                      ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One fused training epoch over stacked clients; see train_epoch.py."""
    return _te.train_epoch_pallas(ta, w, lits, cls2, act, coin,
                                  n_states=n_states, T=T,
                                  interpret=interpret_mode())


def ta_update(ta: jnp.ndarray, lit: jnp.ndarray, fired: jnp.ndarray,
              type1: jnp.ndarray, type2: jnp.ndarray,
              u_inc: jnp.ndarray, u_dec: jnp.ndarray,
              p_inc: float, p_dec: float, n_states: int) -> jnp.ndarray:
    """Fused Type I/II TA transition; see ref.ta_update_ref."""
    return _ta.ta_update_pallas(ta, lit, fired, type1, type2, u_inc, u_dec,
                                p_inc=p_inc, p_dec=p_dec, n_states=n_states,
                                interpret=interpret_mode())
