"""Entry-point behaviour that does not depend on the model: the chip
smoke refuses to report without an accelerator, and the compilation
cache is placed from outside or at the fixed in-checkout path."""
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_without_tpu_exits_nonzero(tmp_path, where):
    """On the CPU backend — or copied away from the program — the smoke
    exits nonzero and never prints its ``"ok": true`` line."""
    script = REPO / "chip_smoke.py"
    if where == "alone":
        script = pathlib.Path(shutil.copy(script, tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(script), "--out", str(tmp_path / "out")],
        capture_output=True, text=True, env=env, cwd=tmp_path,
        timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert not (tmp_path / "out").exists()


def test_compile_cache_defers_to_environment(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", "/elsewhere/sentinel")
        monkeypatch.setenv(compile_cache.ENV, "/set/from/outside")
        assert compile_cache.configure() == "/set/from/outside"
        assert jax.config.jax_compilation_cache_dir == "/elsewhere/sentinel"

        monkeypatch.delenv(compile_cache.ENV)
        assert compile_cache.configure() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            REPO / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_fed_train_leaves_no_cache_in_checkout():
    cache = compile_cache.DEFAULT_DIR
    listing = lambda: sorted(os.listdir(cache)) if cache.exists() else None
    before = listing()
    from repro.launch import fed_train
    out = fed_train.main(["--clients", "4", "--rounds", "1", "--clauses",
                          "8", "--local-epochs", "1"])
    assert len(out["acc_per_round"]) == 1
    assert listing() == before
