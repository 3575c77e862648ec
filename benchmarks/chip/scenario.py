"""Inputs and the system under test, built from a configuration file
and the seed: the client data (the offline ``mnist`` mirror, written
into the run's scratch directory, then the paper's non-IID split) and
the strategy the engine or the serving plane drives."""
from __future__ import annotations

import dataclasses
import shutil

import harness


def client_data(ctx: harness.Context):
    """The population's ``ClientData`` on the device, from the seed."""
    import jax
    import jax.numpy as jnp
    from repro.data.ingest import natural, registry as datasets

    cfg = ctx.config
    data_dir = ctx.work_dir / "data"
    shutil.rmtree(data_dir, ignore_errors=True)
    pool = datasets.load(cfg["dataset"], data_dir=str(data_dir),
                         n_samples=cfg["pool_samples"],
                         seed=ctx.seed % (2 ** 31))
    key = jax.random.fold_in(jnp.asarray(harness.key_data(ctx.seed)), 1)
    data = natural.partition_pool(
        pool, n_clients=cfg["population"], n_train=cfg["n_train"],
        n_test=cfg["n_test"], n_conf=cfg["n_conf"], key=key,
        experiment=cfg["experiment"])
    shutil.rmtree(data_dir, ignore_errors=True)
    return jax.block_until_ready(data)


def tm_config(cfg: dict, use_kernel: bool = False):
    from repro.core import tm
    return tm.TMConfig(n_classes=cfg["n_classes"],
                       n_clauses=cfg["n_clauses"],
                       n_features=cfg["n_features"],
                       n_states=cfg["n_states"], s=float(cfg["s"]),
                       T=cfg["T"], weighted=cfg["weighted"],
                       use_kernel=use_kernel)


def strategy(cfg: dict):
    """The configuration's strategy, as the engine receives it (the
    engine itself turns on the TM kernels for ``tm_backend="pallas"``)."""
    from repro.fl.runtime.strategy import FedAvgStrategy, TPFLStrategy
    if cfg["model"] == "tsetlin_machine":
        return TPFLStrategy(tm_config(cfg), local_epochs=cfg["local_epochs"])
    if cfg["model"] == "mlp":
        return FedAvgStrategy(n_features=cfg["n_features"],
                              n_hidden=cfg["n_hidden"],
                              n_classes=cfg["n_classes"],
                              local_epochs=cfg["local_epochs"],
                              batch=cfg["batch"], lr=float(cfg["lr"]))
    raise ValueError(f"unknown model {cfg['model']!r}")


def serving_strategy(cfg: dict):
    """The TPFL strategy with the configuration's TM backend switched on,
    as ``Engine`` would hand it to a serving plane."""
    from repro.fl.runtime.strategy import TPFLStrategy
    s = strategy(cfg)
    if not isinstance(s, TPFLStrategy):
        raise ValueError("the serving driver serves Tsetlin machines")
    return dataclasses.replace(s, tm_cfg=tm_config(
        cfg, use_kernel=cfg.get("tm_backend") == "pallas"))
