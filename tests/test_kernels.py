"""Per-kernel exact sweeps: Pallas (interpret mode on the CPU backend,
as :func:`repro.kernels.ops.interpret_mode` decides) vs pure-jnp oracle.

Shape sweep covers unaligned sizes (padding paths), paper-scale machines,
and both int/bool-ish dtype inputs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import clause_eval, draws, ops, ref, ta_update

INTERPRET = dict(interpret=ops.interpret_mode())

SHAPES_CLAUSE = [
    # (CM, L, B)
    (8, 32, 4),
    (300, 1568, 16),      # paper scale: 300 clauses × 784 features
    (130, 200, 7),        # unaligned everything
    (1, 128, 1),
]


@pytest.mark.parametrize("cm,L,B", SHAPES_CLAUSE)
@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("predict", [False, True])
def test_clause_outputs_kernel_vs_ref(cm, L, B, seed, predict):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    include = jax.random.bernoulli(k1, 0.1, (cm, L)).astype(jnp.int32)
    lits = jax.random.bernoulli(k2, 0.5, (B, L)).astype(jnp.int32)
    r = ref.clause_outputs_ref(include, lits, predict=predict)
    k = clause_eval.clause_outputs_pallas(include, lits, predict=predict,
                                          **INTERPRET)
    assert r.shape == k.shape
    assert (r == k).all()


@pytest.mark.parametrize("C,m,L,B", [(4, 16, 32, 8), (10, 300, 1568, 4),
                                     (3, 33, 130, 5)])
@pytest.mark.parametrize("seed", [0, 1])
def test_fused_votes_kernel_vs_ref(C, m, L, B, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    include = jax.random.bernoulli(ks[0], 0.1, (C, m, L)).astype(jnp.int32)
    lits = jax.random.bernoulli(ks[1], 0.5, (B, L)).astype(jnp.int32)
    wpol = jax.random.randint(ks[2], (C, m), -7, 8)
    r = ref.fused_votes_ref(include, lits, wpol, predict=True)
    k = ops.fused_votes(include, lits, wpol, predict=True)
    assert (r == k).all()


@pytest.mark.parametrize("m,L", [(20, 32), (300, 1568), (7, 130), (256, 512)])
@pytest.mark.parametrize("seed", range(3))
def test_ta_update_kernel_vs_ref(m, L, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    ta = jax.random.randint(ks[0], (m, L), 1, 255)
    lit = jax.random.bernoulli(ks[1], 0.5, (1, L)).astype(jnp.int32)
    fired = jax.random.bernoulli(ks[2], 0.5, (m, 1)).astype(jnp.int32)
    t1 = jax.random.bernoulli(ks[3], 0.5, (m, 1)).astype(jnp.int32)
    t2 = (1 - t1) * jax.random.bernoulli(ks[4], 0.5, (m, 1)).astype(jnp.int32)
    u1 = jax.random.uniform(ks[5], (m, L))
    u2 = jax.random.uniform(ks[6], (m, L))
    args = (ta, lit, fired, t1, t2, u1, u2)
    r = ref.ta_update_ref(*args, p_inc=0.9, p_dec=0.1, n_states=127)
    k = ta_update.ta_update_pallas(*args, p_inc=0.9, p_dec=0.1, n_states=127,
                                   **INTERPRET)
    assert (r == k).all()
    assert int(k.min()) >= 1 and int(k.max()) <= 254


def test_ta_update_kernel_extreme_probs():
    m, L = 16, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    ta = jax.random.randint(ks[0], (m, L), 1, 255)
    lit = jnp.ones((1, L), jnp.int32)
    fired = jnp.ones((m, 1), jnp.int32)
    t1 = jnp.ones((m, 1), jnp.int32)
    t2 = jnp.zeros((m, 1), jnp.int32)
    u1 = jax.random.uniform(ks[5], (m, L))
    u2 = jax.random.uniform(ks[6], (m, L))
    # p_inc = 1.0 (boost_true_positive): every (fired, lit) TA moves up
    out = ta_update.ta_update_pallas(ta, lit, fired, t1, t2, u1, u2,
                                     p_inc=1.0, p_dec=0.0, n_states=127,
                                     **INTERPRET)
    expect = jnp.clip(ta + 1, 1, 254)
    assert (out == expect).all()


@pytest.mark.parametrize("C,m,L,B,N,wmax", [(4, 16, 32, 8, 3, 8),
                                             (3, 33, 130, 5, 4, 8),
                                             (2, 40, 64, 3, 2, 100_000)])
@pytest.mark.parametrize("seed", [0, 1])
def test_fused_votes_batched_kernel_vs_ref(C, m, L, B, N, wmax, seed):
    """The client-batched votes kernel row-for-row equals the per-client
    fused-votes reference (unaligned shapes; weights far beyond bf16's
    exact integers — the vote is an int32 sum, never a rounded float
    product)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    include = jax.random.bernoulli(ks[0], 0.1, (N, C, m, L)).astype(jnp.int32)
    lits = jax.random.bernoulli(ks[1], 0.5, (N, B, L)).astype(jnp.int32)
    wpol = jax.random.randint(ks[2], (N, C, m), -wmax + 1, wmax)
    k = clause_eval.fused_votes_batched_pallas(include, lits, wpol,
                                               predict=True, **INTERPRET)
    for i in range(N):
        r = ref.fused_votes_ref(include[i], lits[i], wpol[i], predict=True)
        assert (r == k[i]).all()


@pytest.mark.parametrize("p", [0.2, 1.0 / 3.0, 0.8, 2.0 / 3.0, 1e-7, 1.0])
def test_int_threshold_matches_uniform_compare(p):
    """The fused epoch kernel compares its hashed coin words via the
    int-domain trick (bits >> 9 < ceil(f32(p)·2²³)); pin it against the
    f32 uniform compare the reference trainer performs, including
    non-representable thresholds like 1/3 and s=3's p_inc=2/3."""
    k = jax.random.PRNGKey(0)
    a = jax.random.uniform(k, (8192,)) < p
    b = (jax.random.bits(k, (8192,), jnp.uint32) >> 9) < draws.int_threshold(p)
    assert (a == b).all()


@pytest.mark.parametrize("partitionable", [True, False],
                         ids=["partitionable", "original"])
@pytest.mark.parametrize("m,L", [(6, 130), (7, 130)])
def test_kernel_coin_word_is_the_reference_word(m, L, partitionable):
    """``epoch_draws`` hands the epoch kernel each sample's coin keys,
    and the kernel hashes the word of automaton ``(r, l)`` with
    ``draws.coin_word``: for the target's and the negative's ``k_s1``
    and ``k_s2``, that word is ``bits(k_s, (m, L))[r, l]``, the word
    the reference trainer's uniform reads.  ``offsets`` and ``act`` are
    the reference key discipline's too.  Pinned for an odd and an even
    clause count, a lane-unaligned L, and both threefry streams."""
    S, C = 5, 4
    key = jax.random.PRNGKey(11)
    row = jax.lax.broadcasted_iota(jnp.int32, (m, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (m, L), 1)
    j = row * L + col                   # automaton (r, l)'s counter
    with jax.threefry_partitionable(partitionable):
        assert draws.threefry_stream() == (
            "partitionable" if partitionable else "original")
        offs, act, coin_keys, _ = jax.jit(
            lambda k: draws.epoch_draws(k, S, m, C))(key)
        assert coin_keys.shape == (S, 8) and coin_keys.dtype == jnp.uint32
        word = jax.jit(lambda k1, k2: draws.coin_word(k1, k2, j, m * L))
        for i, k in enumerate(jax.random.split(key, S)):
            k_neg, k_t, k_n = jax.random.split(k, 3)
            assert offs[i] == jax.random.randint(k_neg, (), 1, C)
            for role, kr in enumerate((k_t, k_n)):
                k_act, k_s1, k_s2 = jax.random.split(kr, 3)
                assert (act[i, role] == draws.act_bits(k_act, (m,))).all()
                for c, k_s in enumerate((k_s1, k_s2)):
                    kd = coin_keys[i, 4 * role + 2 * c:4 * role + 2 * c + 2]
                    assert (kd == jax.random.key_data(k_s)).all()
                    want = jax.random.bits(k_s, (m, L), jnp.uint32)
                    assert (word(kd[0], kd[1]) == want).all(), (i, role, c)


def test_coin_word_covers_an_odd_count_and_refuses_other_prngs():
    """Under the original stream an odd word count pads its last
    counter pair with 0; ``coin_word`` forms it so.  A PRNG other than
    threefry has no such word, and the kernel's stream is refused."""
    m, L = 3, 5
    key = jax.random.PRNGKey(3)
    j = jnp.arange(m * L, dtype=jnp.int32).reshape(m, L)
    kd = jax.random.key_data(key)
    for partitionable in (True, False):
        with jax.threefry_partitionable(partitionable):
            assert (draws.coin_word(kd[0], kd[1], j, m * L)
                    == jax.random.bits(key, (m, L), jnp.uint32)).all()
    with jax.default_prng_impl("rbg"):
        with pytest.raises(NotImplementedError, match="threefry"):
            draws.threefry_stream()


@pytest.mark.parametrize("T", [1, 15, 40, 1000])
def test_activation_table_matches_bernoulli(T):
    """Clause resampling compares the 23-bit draw against the host-built
    table of f32((T ∓ v) / 2T) thresholds; pin it against the
    ``bernoulli`` draw it replaced, over every numerator in [0, 2T]."""
    table = draws.activation_thresholds(T)
    k = jax.random.PRNGKey(T)
    bits = draws.act_bits(k, (4096,))
    for n in range(0, 2 * T + 1, max(1, T // 20)):
        p = jnp.int32(n) / (2.0 * T)
        want = jax.random.bernoulli(k, p, (4096,))
        assert (want == (bits < table[n])).all(), n


@pytest.mark.parametrize("bt,ct,lt", [(8, 128, 128), (16, 256, 256)])
def test_clause_kernel_tile_invariance(bt, ct, lt):
    """Result must not depend on BlockSpec tiling choices."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    include = jax.random.bernoulli(k1, 0.15, (200, 300)).astype(jnp.int32)
    lits = jax.random.bernoulli(k2, 0.5, (24, 300)).astype(jnp.int32)
    base = ref.clause_outputs_ref(include, lits)
    out = clause_eval.clause_outputs_pallas(include, lits, bt=bt, ct=ct,
                                            lt=lt, **INTERPRET)
    assert (base == out).all()


@pytest.mark.parametrize("m", [6, 7, 300])
def test_epoch_draws_order_sorts_each_roles_type1_rows(m):
    """``order`` holds, for each sample and role, the role's real Type-I
    rows (the target's even rows, the negative's odd ones) ascending by
    activation draw; an odd ``m`` gives the negative's last slot to the
    padded row ``m``, drawn past every threshold.  For every threshold,
    ``act < thr`` picks exactly a prefix of ``order``."""
    S, C = 4, 5
    offs, act, coin_keys, order = jax.jit(
        lambda k: draws.epoch_draws(k, S, m, C))(jax.random.PRNGKey(m))
    half = (m + 1) // 2
    assert order.shape == (S, 2, half) and order.dtype == jnp.int32
    act, order = np.asarray(act), np.asarray(order)
    thresholds = list(draws.activation_thresholds(40)) + [0, 1 << 23]
    for i in range(S):
        for role in (0, 1):
            rows = np.arange(role, m, 2)
            o = order[i, role]
            assert sorted(o[:rows.size]) == list(rows)
            assert (o[rows.size:] == m).all()     # the odd m's pad slot
            drawn = act[i, role, o[:rows.size]]
            assert (np.diff(drawn) >= 0).all()
            for thr in thresholds:
                active = set(rows[act[i, role, rows] < thr])
                assert set(o[:len(active)]) == active
