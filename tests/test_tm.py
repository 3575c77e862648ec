"""Tsetlin Machine unit + property(seed-swept) tests."""
import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import tm


def _cfg(**kw):
    base = dict(n_classes=4, n_clauses=20, n_features=16, n_states=63,
                s=3.0, T=15)
    base.update(kw)
    return tm.TMConfig(**base)


def _blocky_data(n, key, n_classes=4, n_features=16):
    """class c ⇔ bits [4c, 4c+4) set (plus noise)."""
    ky, kn = jax.random.split(key)
    y = jax.random.randint(ky, (n,), 0, n_classes)
    x = (jax.random.uniform(kn, (n, n_features)) < 0.05).astype(jnp.int32)
    idx = jnp.arange(n_features)[None, :]
    on = (idx >= 4 * y[:, None]) & (idx < 4 * y[:, None] + 4)
    return jnp.where(on, 1, x), y


def test_init_shapes_and_bounds():
    cfg = _cfg()
    p = tm.init_params(cfg, jax.random.PRNGKey(0))
    assert p.ta_state.shape == (4, 20, 32)
    assert p.weights.shape == (4, 20)
    assert int(p.ta_state.min()) >= 1
    assert int(p.ta_state.max()) <= 2 * cfg.n_states


def test_literals():
    x = jnp.array([[1, 0, 1]])
    lits = tm.literals(x)
    assert (lits == jnp.array([[1, 0, 1, 0, 1, 0]])).all()


def test_clause_outputs_are_boolean_and_empty_clause_convention():
    cfg = _cfg()
    p = tm.init_params(cfg, jax.random.PRNGKey(1))
    # force one clause fully excluded (empty)
    ta = p.ta_state.at[0, 0].set(1)
    p = p._replace(ta_state=ta)
    x, _ = _blocky_data(8, jax.random.PRNGKey(2))
    learn = tm.clause_outputs(p, tm.literals(x), cfg, predict=False)
    pred = tm.clause_outputs(p, tm.literals(x), cfg, predict=True)
    assert set(jnp.unique(learn).tolist()) <= {0, 1}
    assert (learn[:, 0, 0] == 1).all()     # empty fires while learning
    assert (pred[:, 0, 0] == 0).all()      # and not during inference


@pytest.mark.parametrize("seed", range(3))
def test_learning_improves_accuracy(seed):
    cfg = _cfg()
    p = tm.init_params(cfg, jax.random.PRNGKey(seed))
    x, y = _blocky_data(200, jax.random.PRNGKey(seed + 10))
    xt, yt = _blocky_data(100, jax.random.PRNGKey(seed + 20))
    before = float(tm.accuracy(p, xt, yt, cfg))
    p = tm.train(p, x, y, jax.random.PRNGKey(seed + 30), cfg, epochs=5)
    after = float(tm.accuracy(p, xt, yt, cfg))
    assert after > max(before, 0.8), (before, after)


@pytest.mark.parametrize("seed", range(3))
def test_ta_states_stay_bounded_after_training(seed):
    cfg = _cfg()
    p = tm.init_params(cfg, jax.random.PRNGKey(seed))
    x, y = _blocky_data(100, jax.random.PRNGKey(seed))
    p = tm.train(p, x, y, jax.random.PRNGKey(seed), cfg, epochs=2)
    assert int(p.ta_state.min()) >= 1
    assert int(p.ta_state.max()) <= 2 * cfg.n_states
    assert int(p.weights.min()) >= 0


def test_votes_clipped_at_threshold():
    cfg = _cfg(T=5)
    p = tm.init_params(cfg, jax.random.PRNGKey(0))
    # saturate weights to force large raw votes
    p = p._replace(weights=jnp.full_like(p.weights, 1000),
                   ta_state=jnp.full_like(p.ta_state, 1))  # all excluded
    x, _ = _blocky_data(4, jax.random.PRNGKey(1))
    _, votes = tm.forward(p, x, cfg)
    assert int(jnp.abs(votes).max()) <= cfg.T


def test_confidence_tracks_data_skew():
    """A client trained only on class 0 should be most confident in 0."""
    cfg = _cfg()
    p = tm.init_params(cfg, jax.random.PRNGKey(0))
    x, y = _blocky_data(300, jax.random.PRNGKey(1))
    keep = y == 0
    x0 = jnp.where(keep[:, None], x, x[0][None])   # mostly class-0 samples
    y0 = jnp.zeros_like(y)
    p = tm.train(p, x0, y0, jax.random.PRNGKey(2), cfg, epochs=3)
    xc, _ = _blocky_data(80, jax.random.PRNGKey(3))
    conf = tm.confidence_scores(p, xc, cfg)
    assert int(jnp.argmax(conf)) == 0


def test_kernel_path_equals_jnp_path():
    """cfg.use_kernel=True must be bit-identical (same uniforms)."""
    cfg_a = _cfg()
    cfg_b = _cfg(use_kernel=True)
    p = tm.init_params(cfg_a, jax.random.PRNGKey(0))
    x, y = _blocky_data(50, jax.random.PRNGKey(1))
    pa = tm.train(p, x, y, jax.random.PRNGKey(2), cfg_a, epochs=1)
    pb = tm.train(p, x, y, jax.random.PRNGKey(2), cfg_b, epochs=1)
    assert (pa.ta_state == pb.ta_state).all()
    assert (pa.weights == pb.weights).all()


@pytest.mark.parametrize("partitionable", [True, False],
                         ids=["partitionable", "original"])
@pytest.mark.parametrize("epochs", [1, 2])
@pytest.mark.parametrize("seed", range(2))
@pytest.mark.parametrize("m,o", [(33, 65), (14, 100)],
                         ids=["L130", "L200"])
def test_kernel_train_bit_identical_at_unaligned_shapes(epochs, seed,
                                                         partitionable, m, o):
    """Full jit'd train through the fused epoch kernel at tile-unaligned
    shapes (L = 130 or 200, padded to 256; m = 33 or 14, padded to 40 or
    16): params must equal the reference scan bit for bit, not just
    single-op parity — under both threefry streams, the kernel hashing
    each Type-I coin word from the unpadded plane's counter."""
    cfg = tm.TMConfig(n_classes=3, n_clauses=m, n_features=o,
                      n_states=63, s=3.0, T=15)
    kcfg = dataclasses.replace(cfg, use_kernel=True)
    key = jax.random.PRNGKey(seed)
    kp, kx, ky, kt = jax.random.split(key, 4)
    p = tm.init_params(cfg, kp)
    x = (jax.random.uniform(kx, (23, cfg.n_features)) < 0.4).astype(jnp.int32)
    y = jax.random.randint(ky, (23,), 0, cfg.n_classes)
    with jax.threefry_partitionable(partitionable):
        pa = tm.train(p, x, y, kt, cfg, epochs=epochs)
        pb = tm.train(p, x, y, kt, kcfg, epochs=epochs)
    assert (pa.ta_state == pb.ta_state).all()
    assert (pa.weights == pb.weights).all()


@pytest.mark.parametrize("partitionable", [True, False],
                         ids=["partitionable", "original"])
def test_batched_entry_points_bit_identical_to_vmap(partitionable, seed=0):
    """The client-batched kernel entry points (one launch for a stacked
    cohort) must match the vmapped per-client reference bit for bit,
    under both threefry streams."""
    cfg = tm.TMConfig(n_classes=3, n_clauses=33, n_features=65,
                      n_states=63, s=3.0, T=15)
    kcfg = dataclasses.replace(cfg, use_kernel=True)
    N, S = 4, 17
    key = jax.random.PRNGKey(seed)
    kp, kx, ky, kt, ke = jax.random.split(key, 5)
    params = jax.vmap(lambda k: tm.init_params(cfg, k))(
        jax.random.split(kp, N))
    xs = (jax.random.uniform(kx, (N, S, cfg.n_features)) < 0.4).astype(
        jnp.int32)
    ys = jax.random.randint(ky, (N, S), 0, cfg.n_classes)
    keys = jax.random.split(kt, N)
    with jax.threefry_partitionable(partitionable):
        pa, none = tm.train_batched(params, xs, ys, keys, cfg, epochs=2)
        pb, hashed = tm.train_batched(params, xs, ys, keys, kcfg, epochs=2)
    assert (pa.ta_state == pb.ta_state).all()
    assert (pa.weights == pb.weights).all()
    # the kernel counts the Type-I rows it hashed; the reference, none
    assert none is None and hashed.shape == (N,)
    assert ((hashed > 0) & (hashed <= tm.type1_rows(cfg, S, 2))).all()
    xe = (jax.random.uniform(ke, (N, 9, cfg.n_features)) < 0.4).astype(
        jnp.int32)
    ye = jax.random.randint(jax.random.fold_in(ke, 1), (N, 9), 0,
                            cfg.n_classes)
    assert (tm.accuracy_batched(pa, xe, ye, cfg)
            == tm.accuracy_batched(pb, xe, ye, kcfg)).all()
    for weighted in (False, True):
        assert (tm.confidence_scores_batched(pa, xe, cfg, weighted=weighted)
                == tm.confidence_scores_batched(pb, xe, kcfg,
                                                weighted=weighted)).all()


def test_predict_kernel_clips_votes_before_argmax():
    """Regression: the kernel predict path used to argmax *unclipped*
    fused votes.  Craft vote saturation — class 0 fires weight 2, class
    1 fires weight 3, T = 1 — so clipped votes tie at +T (argmax → 0)
    while unclipped votes would pick class 1."""
    cfg = tm.TMConfig(n_classes=2, n_clauses=4, n_features=2,
                      n_states=63, s=3.0, T=1)
    kcfg = dataclasses.replace(cfg, use_kernel=True)
    p = tm.init_params(cfg, jax.random.PRNGKey(0))
    ta = jnp.ones_like(p.ta_state)          # everything excluded (empty)
    ta = ta.at[0, 0, 0].set(cfg.n_states + 1)   # class 0, clause 0: x0
    ta = ta.at[1, 0, 0].set(cfg.n_states + 1)   # class 1, clause 0: x0
    w = jnp.ones_like(p.weights).at[0, 0].set(2).at[1, 0].set(3)
    p = tm.TMParams(ta_state=ta, weights=w)
    x = jnp.array([[1, 0]], jnp.int32)          # both clauses fire
    r = tm.predict(p, x, cfg)
    k = tm.predict(p, x, kcfg)
    assert int(r[0]) == 0                       # ±T tie → first argmax
    assert (r == k).all()
    # and the batched kernel evaluate path clips identically
    y = jnp.zeros((1, 1), jnp.int32)
    stack = jax.tree.map(lambda a: a[None], p)
    assert float(tm.accuracy_batched(stack, x[None], y, kcfg)[0]) == 1.0


# ---------------------------------------------------------------------------
# names in the device trace: scopes change op metadata, not the program
# ---------------------------------------------------------------------------

_METADATA = re.compile(r",? metadata=\{[^}]*\}")
# the source tables an optimized module opens with, each ended by a
# blank line: FileNames, FunctionNames, FileLocations, StackFrames
_SOURCE_TABLES = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n.*?\n\n",
    re.M | re.S)


def _program(hlo: str) -> str:
    """An optimized HLO module without its metadata and source tables."""
    return _METADATA.sub("", _SOURCE_TABLES.sub("", hlo))


def _lower_train_batched(kcfg, N=2, S=5):
    p = jax.vmap(lambda k: tm.init_params(kcfg, k))(
        jax.random.split(jax.random.PRNGKey(0), N))
    xs = jnp.zeros((N, S, kcfg.n_features), jnp.int32)
    ys = jnp.zeros((N, S), jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(1), N)
    jax.clear_caches()              # trace anew: scopes live in the trace
    return tm.train_batched.lower(p, xs, ys, keys, kcfg, epochs=2)


def test_train_batched_names_draws_and_pads_and_keeps_its_program(
        monkeypatch):
    """The pallas path's epoch draws and the kernel wrapper's pads carry
    the ``tm.draws`` / ``tm.epoch_pad`` scopes in their locations, and
    the optimized program, metadata stripped, is the one compiled with
    the scopes removed."""
    kcfg = tm.TMConfig(n_classes=3, n_clauses=9, n_features=20,
                       n_states=63, s=3.0, T=15, use_kernel=True)
    named = _lower_train_batched(kcfg)
    locs = named.as_text(debug_info=True)
    assert "tm.draws" in locs and "tm.epoch_pad" in locs
    with_scopes = named.compile().as_text()
    assert "tm.draws" in with_scopes                  # kept to the end

    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _lower_train_batched(kcfg)
    assert "tm.draws" not in bare.as_text(debug_info=True)
    without = bare.compile().as_text()
    assert "FileNames" not in _program(with_scopes)
    assert _program(with_scopes) == _program(without)


# ---------------------------------------------------------------------------
# the packed Type-I pass: only active rows hashed, bit for bit
# ---------------------------------------------------------------------------

def _ref_epoch(p, lits, ys, key, cfg):
    """The reference scan over a literal plane, and per sample the active
    Type-I rows its pre-sample votes give each role: the target's even
    rows whose draw is under the (T − v) / 2T threshold, the negative's
    odd rows under (T + v) / 2T."""
    from repro.kernels import draws
    S, m = ys.shape[0], cfg.n_clauses
    _, act, _, _ = draws.epoch_draws(key, S, m, cfg.n_classes)
    table = jnp.asarray(draws.activation_thresholds(cfg.T))
    row = jnp.arange(m)

    def step(p, inp):
        lit, y, k, a = inp
        cl = tm.clause_outputs(p, lit[None], cfg)
        v = jnp.clip(tm.class_votes(p, cl, cfg)[0], -cfg.T, cfg.T)
        k_neg = jax.random.split(k, 3)[0]
        ybar = (y + jax.random.randint(k_neg, (), 1, cfg.n_classes)) \
            % cfg.n_classes
        n = (jnp.sum((a[0] < table[cfg.T - v[y]]) & (row % 2 == 0))
             + jnp.sum((a[1] < table[cfg.T + v[ybar]]) & (row % 2 == 1)))
        return tm._train_one_sample(p, lit, y, k, cfg), n

    keys = jax.random.split(key, S)
    return jax.lax.scan(step, p, (lits, ys, keys, act))


def _kernel_epoch(p, lits, ys, key, cfg):
    """``tm.train_epoch``'s kernel path over a literal plane (N = 1)."""
    from repro.kernels import draws, ops
    offs, act, coin_keys, order = draws.epoch_draws(
        key, ys.shape[0], cfg.n_clauses, cfg.n_classes)
    cls2 = jnp.stack([ys, (ys + offs) % cfg.n_classes], axis=-1)
    p_inc, p_dec = tm._feedback_probs(cfg)
    ta, w, hashed = ops.train_epoch_fused(
        p.ta_state[None], p.weights[None], lits[None], cls2[None],
        act[None], coin_keys[None], order[None], n_states=cfg.n_states,
        T=cfg.T, p_inc=p_inc, p_dec=p_dec)
    return tm.TMParams(ta[0], w[0]), hashed[0]


def _saturated(p, ys, cfg, target_sign):
    """Every clause empty (so every clause fires) and weights that pin
    each vote to ±T: the label class's at ``target_sign``·T, every
    other class's at the opposite.  With +1 no sample activates a row,
    so nothing moves; with −1 the first sample activates every row."""
    m = cfg.n_clauses
    big = jnp.where(jnp.arange(m) % 2 == 0, 1, 0) * 4 * cfg.T
    flip = jnp.where(jnp.arange(m) % 2 == 0, 0, 1) * 4 * cfg.T
    label = ys[0]
    w = jnp.where((jnp.arange(cfg.n_classes) == label)[:, None],
                  big if target_sign > 0 else flip,
                  flip if target_sign > 0 else big)
    ta = jnp.full_like(p.ta_state, cfg.n_states)
    return tm.TMParams(ta, w.astype(jnp.int32)), jnp.full_like(ys, label)


PACKED_CASES = {
    # (m, L, literal plane): m = 300 pads to 304 rows; m = 32 pads
    # none; L = 131 is odd (a literal plane no o gives)
    "m300-L130": (300, 130, "random"),
    "m32-L131": (32, 131, "random"),
    "m33-L64": (33, 64, "random"),
    "no-active-row": (20, 64, "saturated-none"),
    "every-row-active": (20, 64, "saturated-all"),
}


@pytest.mark.parametrize("case", sorted(PACKED_CASES))
def test_packed_type1_pass_matches_reference_scan(case):
    """The epoch kernel hashes only each role's active Type-I rows,
    gathered eight to a tile in activation-draw order, and still equals
    the reference scan bit for bit (TA states and weights); the rows it
    reports hashed are the active Type-I rows the reference's votes
    give.  Cases: padded and unpadded clause counts, an odd literal
    width, active counts that are not whole groups of eight, a sample
    with no active Type-I row and one with every row active."""
    m, L, kind = PACKED_CASES[case]
    cfg = tm.TMConfig(n_classes=3, n_clauses=m, n_features=L // 2,
                      n_states=63, s=3.0, T=15)
    S = 6
    key = jax.random.PRNGKey(len(case))
    kp, kx, ky, kt = jax.random.split(key, 4)
    p = tm.init_params(cfg, kp)
    p = p._replace(ta_state=jax.random.randint(kp, (3, m, L), 1, 127))
    lits = (jax.random.uniform(kx, (S, L)) < 0.5).astype(jnp.int32)
    ys = jax.random.randint(ky, (S,), 0, cfg.n_classes)
    if kind != "random":
        p, ys = _saturated(p, ys, cfg, 1 if kind == "saturated-none" else -1)
    want, per_sample = jax.jit(_ref_epoch, static_argnums=4)(
        p, lits, ys, kt, cfg)
    got, hashed = jax.jit(_kernel_epoch, static_argnums=4)(
        p, lits, ys, kt, cfg)
    assert (got.ta_state == want.ta_state).all()
    assert (got.weights == want.weights).all()
    assert int(hashed) == int(per_sample.sum())
    if kind == "saturated-none":
        assert int(hashed) == 0
        assert (got.ta_state == p.ta_state).all()
    elif kind == "saturated-all":
        assert int(per_sample[0]) == m            # ⌈m/2⌉ + ⌊m/2⌋ rows
    else:
        assert (per_sample % 8 != 0).any()        # a part-filled group
        assert 0 < int(hashed) < S * m
