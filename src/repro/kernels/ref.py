"""Pure-jnp oracles for the TM Pallas kernels.

Each function here is the semantic reference for the identically-named
kernel in :mod:`repro.kernels.clause_eval` / :mod:`repro.kernels.ta_update`.
Tests sweep shapes/dtypes and assert the kernels (interpret mode on the
CPU backend; compiled on TPUs) match these bit-exactly.
"""
from __future__ import annotations

import jax.numpy as jnp


def clause_outputs_ref(include: jnp.ndarray, lits: jnp.ndarray,
                       predict: bool = False) -> jnp.ndarray:
    """include: (CM, L) {0,1}; lits: (B, L) {0,1} → fired (B, CM) int32.

    fired[b, j] = 1 iff every included literal of clause j is 1 in sample b.
    Empty clauses fire during learning, not during prediction.
    """
    viol = (1 - lits).astype(jnp.int32) @ include.T.astype(jnp.int32)
    fired = (viol == 0).astype(jnp.int32)
    if predict:
        fired = fired * (include.sum(-1) > 0).astype(jnp.int32)[None, :]
    return fired


def fused_votes_ref(include: jnp.ndarray, lits: jnp.ndarray,
                    wpol: jnp.ndarray, predict: bool = True) -> jnp.ndarray:
    """Fused clause-eval + weighted class vote (paper Eq. 1).

    include: (C, m, L) {0,1}; lits: (B, L) {0,1}; wpol: (C, m) int32
    (polarity·weight) → votes (B, C) int32 (unclipped).
    """
    C, m, L = include.shape
    fired = clause_outputs_ref(include.reshape(C * m, L), lits, predict)
    return jnp.einsum("bcm,cm->bc", fired.reshape(-1, C, m), wpol)


def ta_update_ref(ta: jnp.ndarray, lit: jnp.ndarray, fired: jnp.ndarray,
                  type1: jnp.ndarray, type2: jnp.ndarray,
                  u_inc: jnp.ndarray, u_dec: jnp.ndarray,
                  p_inc: float, p_dec: float, n_states: int) -> jnp.ndarray:
    """Type I / Type II TA state transition for one clause bank.

    ta: (m, L) int32 states in [1, 2N]; lit: (1, L) {0,1};
    fired/type1/type2: (m, 1) {0,1}; u_inc/u_dec: (m, L) uniforms in [0,1).

    Type I  (on type1 clauses):
      fired & lit          → +1 w.p. p_inc      (recognize)
      fired & ¬lit | ¬fired → −1 w.p. p_dec     (erase / forget)
    Type II (on type2 clauses):
      fired & ¬lit & excluded → +1 deterministically (reject false positive)
    """
    litb = lit.astype(bool)
    firedb = fired.astype(bool)
    t1 = type1.astype(bool)
    t2 = type2.astype(bool)
    up1 = t1 & firedb & litb & (u_inc < p_inc)
    down1 = t1 & ((firedb & (~litb)) | (~firedb)) & (u_dec < p_dec)
    up2 = t2 & firedb & (~litb) & (ta <= n_states)
    delta = up1.astype(jnp.int32) - down1.astype(jnp.int32) + up2.astype(jnp.int32)
    return jnp.clip(ta + delta, 1, 2 * n_states)
