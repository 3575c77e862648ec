"""Plain reference of TPFL rounds and of personalized prediction on the
weighted Tsetlin machine.

Written from the paper (TPFL, arXiv 2409.10392: Alg. 1 local training
and confidence, Alg. 2 confidence-clustered aggregation, Eq. 1 votes)
and Granmo's Type I / Type II feedback, in straightforward
``jax.numpy`` on integers, one client-sample at a time.  It imports
nothing of the system under test and takes nothing it made: it builds
its own initial state from the seed, and follows the same key
discipline, so that a sound system matches it exactly:

* ``k_init, k_rounds = split(key)``; client ``i`` starts from
  ``bernoulli(split(k_init, n)[i], 0.5)`` → state N or N+1, weights 1;
* round ``r`` uses ``rk = fold_in(k_rounds, r)``: the cohort is
  ``choice(fold_in(rk, 0x5C4ED), n, (K,), replace=False)`` and client
  ``i`` trains with ``split(rk, n)[i]``, one key per epoch
  (``split(key, epochs)``) and per sample (``split(epoch_key, S)``);
* a sample's key splits into (negative-class draw, target role,
  negative role); a role's key into (clause activation, Type I
  increment coins, Type I decrement coins).

``draw_dtype`` is the precision of the uniform draws and of the
activation probability: float32 as the configuration states, or
bfloat16 for the control.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_TAG_SELECT = 0x5C4ED


class Widths:
    def __init__(self, cfg: dict):
        self.C, self.m = cfg["n_classes"], cfg["n_clauses"]
        self.L = 2 * cfg["n_features"]
        self.N, self.s, self.T = cfg["n_states"], float(cfg["s"]), cfg["T"]

    def __hash__(self):
        return hash((self.C, self.m, self.L, self.N, self.s, self.T))

    def __eq__(self, other):
        return hash(self) == hash(other)


def init_population(k_init, n: int, w: Widths):
    def one(k):
        coin = jax.random.bernoulli(k, 0.5, (w.C, w.m, w.L))
        return jnp.where(coin, w.N, w.N + 1).astype(jnp.int32)
    ta = jax.vmap(one)(jax.random.split(k_init, n))
    return ta, jnp.ones((n, w.C, w.m), jnp.int32)


def _literals(x):
    x = x.astype(jnp.int32)
    return jnp.concatenate([x, 1 - x], axis=-1)


def _polarity(m: int):
    return jnp.where(jnp.arange(m) % 2 == 0, 1, -1).astype(jnp.int32)


def _activation_table(T: int, dtype) -> jnp.ndarray:
    """p(n) = n / 2T for n in [0, 2T], divided on the host (IEEE f32)."""
    n = np.arange(2 * T + 1, dtype=np.float32)
    return jnp.asarray(n / np.float32(2 * T)).astype(dtype)


def _feedback(ta, wc, lits, fired, vote, is_target: bool, key, w: Widths,
              draw_dtype):
    """One class bank's Type I / Type II feedback for one sample."""
    k_act, k_s1, k_s2 = jax.random.split(key, 3)
    v = jnp.clip(vote, -w.T, w.T)
    table = _activation_table(w.T, draw_dtype)
    p_act = table[(w.T - v) if is_target else (w.T + v)]
    active = jax.random.uniform(k_act, (w.m,), draw_dtype) < p_act
    pos = _polarity(w.m) > 0
    t1 = (pos if is_target else ~pos) & active
    t2 = ((~pos) if is_target else pos) & active
    u_inc = jax.random.uniform(k_s1, (w.m, w.L), draw_dtype)
    u_dec = jax.random.uniform(k_s2, (w.m, w.L), draw_dtype)
    p_inc = jnp.asarray((w.s - 1.0) / w.s, draw_dtype)
    p_dec = jnp.asarray(1.0 / w.s, draw_dtype)
    lit = lits[None, :] == 1
    f = fired[:, None]
    up1 = t1[:, None] & f & lit & (u_inc < p_inc)
    down1 = t1[:, None] & ((f & ~lit) | ~f) & (u_dec < p_dec)
    up2 = t2[:, None] & f & ~lit & (ta <= w.N)
    ta = jnp.clip(ta + up1.astype(jnp.int32) - down1.astype(jnp.int32)
                  + up2.astype(jnp.int32), 1, 2 * w.N)
    wc = jnp.maximum(wc + (t1 & fired).astype(jnp.int32)
                     - (t2 & fired).astype(jnp.int32), 0)
    return ta, wc


def _sample_step(carry, inp, w: Widths, draw_dtype):
    ta, wt = carry
    x, y, key = inp
    lits = _literals(x)
    include = ta > w.N
    fired = ~jnp.any(include & (lits == 0)[None, None, :], axis=-1)
    votes = jnp.sum(jnp.where(fired, _polarity(w.m)[None] * wt, 0), axis=1)
    k_neg, k_t, k_n = jax.random.split(key, 3)
    ybar = (y + jax.random.randint(k_neg, (), 1, w.C)) % w.C
    for cls, target, k in ((y, True, k_t), (ybar, False, k_n)):
        t_c, w_c = _feedback(ta[cls], wt[cls], lits, fired[cls], votes[cls],
                             target, k, w, draw_dtype)
        ta, wt = ta.at[cls].set(t_c), wt.at[cls].set(w_c)
    return (ta, wt), None


def _train_client(ta, wt, xs, ys, key, epochs: int, w: Widths, draw_dtype):
    step = partial(_sample_step, w=w, draw_dtype=draw_dtype)

    def epoch(carry, ek):
        keys = jax.random.split(ek, xs.shape[0])
        return jax.lax.scan(step, carry, (xs, ys.astype(jnp.int32),
                                          keys))[0], None

    return jax.lax.scan(epoch, (ta, wt), jax.random.split(key, epochs))[0]


def votes(ta, wt, x, w: Widths, clip: bool = True):
    """Eq. 1 votes in predict mode (empty clauses vote 0), clipped to
    ±T: ta (C,m,L), x (B,o) → (B,C)."""
    lits = _literals(x)
    include = ta > w.N
    viol = jnp.einsum("bl,cml->bcm", (1 - lits), include.astype(jnp.int32))
    fired = (viol == 0) & jnp.any(include, axis=-1)[None]
    v = jnp.sum(jnp.where(fired, _polarity(w.m)[None, None] * wt[None], 0),
                axis=-1)
    return jnp.clip(v, -w.T, w.T) if clip else v


def _margin(ta, x, w: Widths):
    """Alg. 1 confidence: the unweighted clause-vote margin summed over
    the confidence split → (C,)."""
    return votes(ta, jnp.ones_like(ta[..., 0]), x, w, clip=False).sum(axis=0)


@partial(jax.jit, static_argnames=("w", "cohort", "epochs", "draw_dtype"))
def _round(ta, wt, server, data, rk, *, w: Widths, cohort: int, epochs: int,
           draw_dtype):
    n = ta.shape[0]
    if cohort < n:
        idx = jax.random.choice(jax.random.fold_in(rk, _TAG_SELECT), n,
                                (cohort,), replace=False).astype(jnp.int32)
    else:
        idx = jnp.arange(n, dtype=jnp.int32)
    keys = jax.random.split(rk, n)[idx]
    sub = lambda a: a[idx]
    new_ta, new_w = jax.vmap(partial(_train_client, epochs=epochs, w=w,
                                     draw_dtype=draw_dtype))(
        sub(ta), sub(wt), sub(data["x_train"]), sub(data["y_train"]), keys)
    conf = jax.lax.map(lambda a: _margin(a[0], a[1], w),
                       (new_ta, sub(data["x_conf"])))
    c_top = jnp.argmax(conf, axis=-1)
    rows = jnp.arange(cohort)
    uploads = new_w[rows, c_top].astype(jnp.float32)
    sums = jnp.zeros((w.C, w.m), jnp.float32).at[c_top].add(uploads)
    counts = jnp.zeros((w.C,), jnp.float32).at[c_top].add(1.0)
    mean = sums / jnp.maximum(counts[:, None], 1)
    server = jnp.where(counts[:, None] > 0, mean, server)
    new_w = new_w.at[rows, c_top].set(
        jnp.round(server[c_top]).astype(jnp.int32))
    ta, wt = ta.at[idx].set(new_ta), wt.at[idx].set(new_w)
    correct = jax.lax.map(
        lambda a: jnp.mean(jnp.argmax(votes(a[0], a[1], a[2], w), -1)
                           == a[3]),
        (ta, wt, data["x_test"], data["y_test"]))
    return ta, wt, server, correct


def run_rounds(key, data: dict, cfg: dict, cohort: int, n_rounds: int,
               control: bool = False) -> list[dict]:
    """Host snapshots (``ta``, ``w``, ``server``, ``acc``) after each of
    the first ``n_rounds`` rounds, run from the seed's initial state
    (entry r + 1; entry 0, the initial state, is not kept).  ``control``
    draws in bfloat16."""
    w = Widths(cfg)
    draw_dtype = jnp.bfloat16 if control else jnp.float32
    with jax.default_matmul_precision("highest"):
        k_init, k_rounds = jax.random.split(key)
        n = data["x_train"].shape[0]
        ta, wt = init_population(k_init, n, w)
        server = jnp.zeros((w.C, w.m), jnp.float32)
        dev = {k: jnp.asarray(v) for k, v in data.items()}
        out = [{}]
        for r in range(n_rounds):
            ta, wt, server, acc = _round(
                ta, wt, server, dev, jax.random.fold_in(k_rounds, r), w=w,
                cohort=cohort, epochs=cfg["local_epochs"],
                draw_dtype=draw_dtype)
            out.append(snapshot(ta, wt, server, acc))
    return out


def snapshot(ta, wt, server, acc) -> dict:
    return {"ta": np.asarray(ta), "w": np.asarray(wt),
            "server": np.asarray(server, np.float32),
            "acc": np.asarray(acc, np.float64)}


def compare(prog: list[dict], ref: list[dict], cfg: dict) -> dict[str, float]:
    """Per compared round: the share of TA states and of clause weights
    that differ, and the widest per-client accuracy gap."""
    del cfg
    out = {}
    for r, (p, q) in enumerate(zip(prog[1:], ref[1:])):
        out[f"ta_diff.r{r}"] = float(np.mean(p["ta"] != q["ta"]))
        out[f"w_diff.r{r}"] = float(np.mean(p["w"] != q["w"]))
        out[f"acc_gap.r{r}"] = float(np.max(np.abs(p["acc"] - q["acc"])))
    return out


def predict(ta, wt, x, cfg: dict) -> np.ndarray:
    """Served predictions of one client model per request:
    ta (R,C,m,L), wt (R,C,m), x (R,o) → (R,) int."""
    w = Widths(cfg)
    with jax.default_matmul_precision("highest"):
        v = jax.vmap(lambda t, ww, xx: votes(t, ww, xx[None], w)[0])(
            jnp.asarray(ta), jnp.asarray(wt), jnp.asarray(x))
        return np.asarray(jnp.argmax(v, axis=-1))


def predict_control(ta, wt, x, cfg: dict) -> np.ndarray:
    """The control: the same votes summed in bfloat16, the nearest
    precision below the float32 accumulation the configuration states."""
    w = Widths(cfg)

    def one(t, ww, xx):
        lits = _literals(xx[None])
        include = t > w.N
        viol = jnp.einsum("bl,cml->bcm", 1 - lits, include.astype(jnp.int32))
        fired = (viol == 0) & jnp.any(include, axis=-1)[None]
        wp = (_polarity(w.m)[None] * ww).astype(jnp.bfloat16)
        v = jnp.einsum("bcm,cm->bc", fired.astype(jnp.bfloat16), wp,
                       preferred_element_type=jnp.bfloat16)
        return jnp.clip(v, -w.T, w.T)[0]

    v = jax.vmap(one)(jnp.asarray(ta), jnp.asarray(wt), jnp.asarray(x))
    return np.asarray(jnp.argmax(v, axis=-1))
