"""Plain reference of FedAvg rounds on a one-hidden-layer MLP.

Written from the FedAvg algorithm (McMahan et al., arXiv 1602.05629,
Alg. 1: each sampled client runs E epochs of minibatch SGD from the
global model, the server averages the returned models) in
straightforward ``jax.numpy`` at float32 with ``highest`` matmul
precision.  It imports nothing of the system under test and builds its
own initial model from the seed, under the same key discipline:

* ``k_init, k_rounds = split(key)``; the global model is He-normal from
  ``k_init`` (``k1, k2 = split(k_init)``), biases zero, and every
  client starts as a copy of it;
* round ``r`` uses ``rk = fold_in(k_rounds, r)``: the cohort is
  ``choice(fold_in(rk, 0x5C4ED), n, (K,), replace=False)``; client
  ``i`` trains with ``split(rk, n)[i]``, one permutation key per epoch
  (``split(key, epochs)``), whole batches only.

``dtype`` is the precision of parameters, activations, gradients and
the average: float32 as the configuration states, or bfloat16 for the
control.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_TAG_SELECT = 0x5C4ED


def layout(cfg: dict):
    i, h, o = cfg["n_features"], cfg["n_hidden"], cfg["n_classes"]
    return (("w1", (i, h)), ("b1", (h,)), ("w2", (h, o)), ("b2", (o,)))


def _flatten(p: dict, lay) -> jnp.ndarray:
    return jnp.concatenate([p[k].ravel() for k, _ in lay])


def _unflatten(v: jnp.ndarray, lay) -> dict:
    out, off = {}, 0
    for k, shape in lay:
        size = int(np.prod(shape))
        out[k] = v[off:off + size].reshape(shape)
        off += size
    return out


def init_global(k_init, cfg: dict) -> jnp.ndarray:
    i, h, o = cfg["n_features"], cfg["n_hidden"], cfg["n_classes"]
    k1, k2 = jax.random.split(k_init)
    p = {"w1": jax.random.normal(k1, (i, h)) * (2.0 / i) ** 0.5,
         "b1": jnp.zeros((h,)),
         "w2": jax.random.normal(k2, (h, o)) * (2.0 / h) ** 0.5,
         "b2": jnp.zeros((o,))}
    return _flatten(p, layout(cfg))


def _loss(p, x, y):
    h = jax.nn.relu(x @ p["w1"] + p["b1"])
    logits = h @ p["w2"] + p["b2"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def _local_train(v, x, y, key, *, lay, epochs, batch, lr, dtype):
    p = jax.tree.map(lambda a: a.astype(dtype), _unflatten(v, lay))
    x = x.astype(dtype)
    steps = max(x.shape[0] // batch, 1)

    def epoch(p, k):
        perm = jax.random.permutation(k, x.shape[0])
        xb = x[perm][:steps * batch].reshape(steps, batch, -1)
        yb = y[perm][:steps * batch].reshape(steps, batch)

        def step(p, b):
            g = jax.grad(_loss)(p, b[0], b[1])
            return jax.tree.map(lambda w, gw: w - jnp.asarray(lr, dtype) * gw,
                                p, g), None

        return jax.lax.scan(step, p, (xb, yb))[0], None

    p = jax.lax.scan(epoch, p, jax.random.split(key, epochs))[0]
    return _flatten(p, lay)


def _accuracy(v, x, y, lay):
    p = _unflatten(v.astype(jnp.float32), lay)
    h = jax.nn.relu(x.astype(jnp.float32) @ p["w1"] + p["b1"])
    return jnp.mean(jnp.argmax(h @ p["w2"] + p["b2"], -1) == y)


@partial(jax.jit, static_argnames=("lay", "cohort", "epochs", "batch", "lr",
                                   "dtype"))
def _round(pop, server, data, rk, *, lay, cohort, epochs, batch, lr, dtype):
    n = pop.shape[0]
    if cohort < n:
        idx = jax.random.choice(jax.random.fold_in(rk, _TAG_SELECT), n,
                                (cohort,), replace=False).astype(jnp.int32)
    else:
        idx = jnp.arange(n, dtype=jnp.int32)
    keys = jax.random.split(rk, n)[idx]
    uploads = jax.vmap(partial(_local_train, lay=lay, epochs=epochs,
                               batch=batch, lr=lr, dtype=dtype),
                       in_axes=(None, 0, 0, 0))(
        server.astype(dtype), data["x_train"][idx],
        data["y_train"][idx].astype(jnp.int32), keys)
    server = jnp.mean(uploads, axis=0).astype(jnp.float32)
    pop = pop.at[idx].set(jnp.broadcast_to(server, (cohort,) + server.shape))
    acc = jax.vmap(partial(_accuracy, lay=lay))(pop, data["x_test"],
                                               data["y_test"])
    return pop, server, acc


def run_rounds(key, data: dict, cfg: dict, cohort: int, n_rounds: int,
               control: bool = False) -> list[dict]:
    """Host snapshots: entry 0 is the initial global model, entry r + 1
    the global model (``server``) and per-client accuracy (``acc``)
    after round r.  ``control`` computes in bfloat16."""
    lay = layout(cfg)
    dtype = jnp.bfloat16 if control else jnp.float32
    with jax.default_matmul_precision("highest"):
        k_init, k_rounds = jax.random.split(key)
        g = init_global(k_init, cfg)
        n = data["x_train"].shape[0]
        pop = jnp.broadcast_to(g, (n,) + g.shape)
        server = g
        dev = {k: jnp.asarray(v) for k, v in data.items()}
        out = [{"server": np.asarray(g, np.float64)}]
        for r in range(n_rounds):
            pop, server, acc = _round(
                pop, server, dev, jax.random.fold_in(k_rounds, r), lay=lay,
                cohort=cohort, epochs=cfg["local_epochs"],
                batch=cfg["batch"], lr=float(cfg["lr"]), dtype=dtype)
            out.append({"server": np.asarray(server, np.float64),
                        "acc": np.asarray(acc, np.float64)})
    return out


def change_gap(p0, p1, q0, q1, lay) -> float:
    """Worst-leaf gap between the norms of the program's change
    ``p1 − p0`` and the reference's ``q1 − q0``, each over the larger of
    that leaf's reference norm and the median leaf's.  Leaves whose
    reference change is under a thousandth of the median leaf's move by
    round-off alone and are left out."""
    norms_p, norms_q, off = [], [], 0
    for _, shape in lay:
        size = int(np.prod(shape))
        sl = slice(off, off + size)
        norms_p.append(np.linalg.norm(p1[sl] - p0[sl]))
        norms_q.append(np.linalg.norm(q1[sl] - q0[sl]))
        off += size
    med = float(np.median(norms_q))
    gaps = [abs(a - b) / max(b, med) for a, b in zip(norms_p, norms_q)
            if b >= 1e-3 * med]
    return float(max(gaps))


def compare(prog: list[dict], ref: list[dict], cfg: dict) -> dict[str, float]:
    """The first round's change of the global model, the change over all
    compared rounds, and each round's population accuracy gap."""
    lay = layout(cfg)
    last = len(ref) - 1
    out = {"update_gap.r0": change_gap(prog[0]["server"], prog[1]["server"],
                                       ref[0]["server"], ref[1]["server"],
                                       lay),
           f"change_gap.r{last - 1}": change_gap(
               prog[0]["server"], prog[last]["server"], ref[0]["server"],
               ref[last]["server"], lay)}
    for r in range(1, last + 1):
        out[f"acc_gap.r{r - 1}"] = float(abs(
            np.mean(prog[r]["acc"]) - np.mean(ref[r]["acc"])))
    return out
