"""The shard-mapped TPFL round on a four-device ``clients`` mesh.

A child process sees four virtual CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``; the test
process itself keeps the one real CPU device) and runs the same small
federation twice, three rounds each: ``backend="shardmap"`` with the
fused Pallas TM kernels (interpret mode) on ``make_clients_mesh(4)``,
and ``backend="inprocess"`` on the reference TM path.  It reports what
the test then checks:

* the two runs agree bit for bit: TA states, clause weights, the server
  matrix and every round's per-client accuracy;
* after ``init`` the client state and the data lie on the mesh as
  ``P("clients")`` — one block of clients a device — when the mesh
  divides the population, and replicated on it when it does not;
* ``_fused_program`` compiles once across rounds 0–2: round 0's placed
  state has the layout every later round hands back.

Widths: C = 4 classes, m = 16 clauses, o = 32 features, 2 local epochs,
N = K = 8 (four blocks of two) and N = K = 6 (the mesh does not divide
it: padded per call).
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]

CHILD = r"""
import json, sys
import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import tm
from repro.data import partition, synthetic
from repro.fl.runtime import (Engine, RuntimeConfig, SchedulerConfig,
                              TPFLStrategy, executors)
from repro.launch.mesh import make_clients_mesh

n = int(sys.argv[1])
rounds = 3
cfg = tm.TMConfig(n_classes=4, n_clauses=16, n_features=32, n_states=63,
                  s=5.0, T=20)
x, y, _ = synthetic.make_dataset("synthmnist", 400, jax.random.PRNGKey(0),
                                 side=8)
data = partition.partition(x[:, :32], y % 4, 4, n_clients=n, experiment=5,
                           key=jax.random.PRNGKey(1), n_train=16,
                           n_test=8, n_conf=8)
key = jax.random.PRNGKey(7)


def run(backend, tm_backend, mesh=None):
    rt = RuntimeConfig(rounds=rounds, scheduler=SchedulerConfig(),
                       backend=backend, tm_backend=tm_backend)
    eng = Engine(TPFLStrategy(cfg, local_epochs=2), data, rt, mesh=mesh)
    k_init, k_rounds = jax.random.split(key)
    state = eng.init(k_init)
    placed = {
        "ta": state.client_state.ta_state.sharding,
        "x_train": eng.data.x_train.sharding,
        "server": state.server.slots.sharding}
    accs = []
    before = executors._fused_program._cache_size()
    for r in range(rounds):
        state, rep = eng.run_round(state, jax.random.fold_in(k_rounds, r))
        accs.append(np.asarray(rep.per_client_accuracy).tolist())
    compiles = executors._fused_program._cache_size() - before
    return state, accs, placed, compiles


mesh = make_clients_mesh(4)
sm, sm_acc, placed, compiles = run("shardmap", "pallas", mesh)
ip, ip_acc, _, _ = run("inprocess", "ref")
even = n % 4 == 0
want = NamedSharding(mesh, P("clients") if even else P())
print(json.dumps({
    "ta_equal": bool((np.asarray(sm.client_state.ta_state)
                      == np.asarray(ip.client_state.ta_state)).all()),
    "w_equal": bool((np.asarray(sm.client_state.weights)
                     == np.asarray(ip.client_state.weights)).all()),
    "server_equal": bool((np.asarray(sm.server.slots)
                          == np.asarray(ip.server.slots)).all()),
    "acc_equal": sm_acc == ip_acc,
    "placed": {k: v == (NamedSharding(mesh, P()) if k == "server"
                        else want) for k, v in placed.items()},
    "compiles": compiles}))
"""


SIZES = {"N8": 8, "N6_uneven": 6}


@pytest.fixture(scope="module")
def mesh_runs():
    """Both sizes, each in a child of its own, side by side."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    children = {name: subprocess.Popen(
        [sys.executable, "-c", CHILD, str(n)], cwd=ROOT, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for name, n in SIZES.items()}
    out = {}
    for name, child in children.items():
        stdout, stderr = child.communicate(timeout=240)
        assert child.returncode == 0, stderr[-4000:]
        out[name] = json.loads(stdout.strip().splitlines()[-1])
    return out


@pytest.mark.parametrize("size", sorted(SIZES))
def test_sharded_pallas_round_is_bit_identical_to_inprocess_ref(mesh_runs,
                                                                size):
    got = mesh_runs[size]
    assert got["ta_equal"] and got["w_equal"]
    assert got["server_equal"] and got["acc_equal"]


@pytest.mark.parametrize("size", sorted(SIZES))
def test_population_and_server_are_placed_on_the_mesh_at_init(mesh_runs,
                                                              size):
    assert mesh_runs[size]["placed"] == {"ta": True, "x_train": True,
                                         "server": True}


@pytest.mark.parametrize("size", sorted(SIZES))
def test_fused_program_compiles_once_across_rounds(mesh_runs, size):
    assert mesh_runs[size]["compiles"] == 1
