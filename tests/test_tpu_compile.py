"""The TM kernels compile for a TPU v5e at the paper's MNIST widths.

Nothing runs: each kernel is lowered and compiled by the TPU compiler
for a described (not attached) ``v5e:2x2`` chip, which refuses what
interpret mode accepts — unaligned block shapes, primitives Mosaic
cannot lower, more VMEM than a kernel may use.  Widths: C = 10 classes,
m = 300 clauses, L = 1568 literals (28×28 bool encoding), B = 40
samples; N = 20 clients for the batched votes, and one client over
S = 8 samples for the training epoch.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import clause_eval, ta_update, train_epoch

C, M, L, B = 10, 300, 1568, 40


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_clause_eval_compiles(one_chip):
    _compile(lambda inc, lits: clause_eval.clause_outputs_pallas(
        inc, lits, interpret=False), one_chip,
        ((C * M, L), jnp.int32), ((B, L), jnp.int32))


def test_ta_update_compiles(one_chip):
    _compile(lambda *a: ta_update.ta_update_pallas(
        *a, p_inc=0.8, p_dec=0.2, n_states=63, interpret=False), one_chip,
        ((M, L), jnp.int32), ((1, L), jnp.int32), ((M, 1), jnp.int32),
        ((M, 1), jnp.int32), ((M, 1), jnp.int32), ((M, L), jnp.float32),
        ((M, L), jnp.float32))


@pytest.mark.parametrize("n", [1, 20], ids=["single_client", "round_N20"])
def test_fused_votes_compiles(one_chip, n):
    """N = 1 is ``tm.predict`` (offline/serving verify); N = 20 is one
    round's evaluate/confidence launch."""
    _compile(lambda inc, lits, w: clause_eval.fused_votes_batched_pallas(
        inc, lits, w, interpret=False), one_chip,
        ((n, C, M, L), jnp.int32), ((n, B, L), jnp.int32),
        ((n, C, M), jnp.int32))


def test_train_epoch_compiles_within_vmem(one_chip):
    """No coin plane goes in: each sample's eight coin key words and its
    two roles' row orders do, and the kernel hashes the active Type-I
    rows' coins (threefry lowered by Mosaic; the active count a scalar,
    the rows gathered one at a time) within the VMEM ``vmem_bytes`` asks
    for."""
    n, s = 1, 8
    assert train_epoch.vmem_bytes(C, M, L) <= train_epoch.VMEM_BUDGET
    _compile(lambda *a: train_epoch.train_epoch_pallas(
        *a, n_states=63, T=40, p_inc=0.9, p_dec=0.1, interpret=False),
        one_chip,
        ((n, C, M, L), jnp.int32), ((n, C, M), jnp.int32),
        ((n, s, L), jnp.int32), ((n, s, 2), jnp.int32),
        ((n, s, 2, M), jnp.int32), ((n, s, 8), jnp.uint32),
        ((n, s, 2, (M + 1) // 2), jnp.int32))


def test_train_epoch_compiles_for_a_round_of_clients(one_chip):
    """A round's launch, 40 clients over 8 samples: every per-client
    block — the hashed-row count among them — tiles a client axis that
    is not 1."""
    n, s = 40, 8
    _compile(lambda *a: train_epoch.train_epoch_pallas(
        *a, n_states=127, T=1000, p_inc=0.9, p_dec=0.1, interpret=False),
        one_chip,
        ((n, C, M, L), jnp.int32), ((n, C, M), jnp.int32),
        ((n, s, L), jnp.int32), ((n, s, 2), jnp.int32),
        ((n, s, 2, M), jnp.int32), ((n, s, 8), jnp.uint32),
        ((n, s, 2, (M + 1) // 2), jnp.int32))


def test_train_batched_scopes_leave_the_chip_program_unchanged(
        one_chip, monkeypatch):
    """The ``tm.draws`` / ``tm.epoch_pad`` scopes name the round's
    training work in the device trace; the program the TPU compiler
    makes of ``train_batched`` (pallas path, one client, 8 samples, the
    paper's widths) is the same without them, metadata stripped."""
    import contextlib
    import re

    from repro.core import tm
    from repro.kernels import ops

    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    cfg = tm.TMConfig(n_classes=C, n_clauses=M, n_features=L // 2,
                      n_states=127, s=10.0, T=1000, use_kernel=True)
    n, s = 1, 8

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    args = (tm.TMParams(ta_state=sds((n, C, M, L), jnp.int32),
                        weights=sds((n, C, M), jnp.int32)),
            sds((n, s, L // 2), jnp.int32), sds((n, s), jnp.int32),
            sds((n, 2), jnp.uint32))

    def program() -> str:
        jax.clear_caches()
        text = tm.train_batched.lower(*args, cfg, epochs=1).compile(
            ).as_text()
        assert "tpu_custom_call" in text
        text = re.sub(r"^(FileNames|FunctionNames|FileLocations|"
                      r"StackFrames)\n.*?\n\n", "", text, flags=re.M | re.S)
        return re.sub(r",? metadata=\{[^}]*\}", "", text)

    # both from one line: the kernel's serialized body records where it
    # was traced from.  Only the program's own scopes go: JAX's Mosaic
    # lowering names the kernel's threefry with a scope of its own
    real_scope = jax.named_scope
    ours = ("tm.draws", "tm.epoch_pad")
    texts = []
    for scoped in (True, False):
        if not scoped:
            monkeypatch.setattr(
                jax, "named_scope", lambda name: contextlib.nullcontext()
                if name in ours else real_scope(name))
        texts.append(program())
    assert texts[0] == texts[1]
