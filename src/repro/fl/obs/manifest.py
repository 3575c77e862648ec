"""Run manifests: the provenance record every telemetry run carries.

One ``manifest.json`` per run directory, written before the first
round: the full resolved configuration (``RuntimeConfig`` and friends,
dataclasses flattened), the seed, the mesh shape and device inventory,
the git sha the run was built from, and the jax version — everything a
reader needs to interpret (or re-run) the ``events.jsonl`` next to it.
The same dict rides along with engine checkpoints
(:func:`repro.fl.runtime.checkpointing.save` accepts it), so a resumed
run's provenance survives the interruption.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import platform
import subprocess
import sys
from typing import Any

import jax

from repro.fl.obs.events import to_jsonable

MANIFEST_NAME = "manifest.json"
EVENTS_NAME = "events.jsonl"


def git_sha(cwd: str | pathlib.Path | None = None) -> str | None:
    """Best-effort ``git rev-parse HEAD`` — None outside a checkout."""
    try:
        res = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=str(cwd) if cwd else None,
            capture_output=True, text=True, timeout=10)
    except (OSError, subprocess.TimeoutExpired):
        return None
    sha = res.stdout.strip()
    return sha if res.returncode == 0 and sha else None


def _flatten_config(obj: Any) -> Any:
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {f.name: _flatten_config(getattr(obj, f.name))
                for f in dataclasses.fields(obj)}
    return obj


def build_manifest(config: Any = None, seed: int | None = None,
                   mesh=None, extra: dict | None = None) -> dict:
    """Assemble the provenance dict.

    ``config`` is any dataclass (nested dataclasses are flattened —
    ``RuntimeConfig`` carries its scheduler and codec along); ``mesh``
    a jax Mesh or None (in-process); ``extra`` free-form caller fields
    (CLI argv, dataset name, strategy...)."""
    devices = jax.devices()
    manifest = {
        "config": _flatten_config(config),
        "seed": seed,
        "mesh": ({str(k): int(v) for k, v in dict(mesh.shape).items()}
                 if mesh is not None else None),
        "devices": {
            "count": len(devices),
            "platform": devices[0].platform if devices else None,
            "device_kind": devices[0].device_kind if devices else None,
        },
        "git_sha": git_sha(pathlib.Path(__file__).resolve().parents[4]),
        "jax_version": jax.__version__,
        "python_version": sys.version.split()[0],
        "host_platform": platform.platform(),
    }
    if extra:
        manifest.update(extra)
    return to_jsonable(manifest)


def write_manifest(run_dir: str | pathlib.Path,
                   manifest: dict) -> pathlib.Path:
    run_dir = pathlib.Path(run_dir)
    run_dir.mkdir(parents=True, exist_ok=True)
    path = run_dir / MANIFEST_NAME
    path.write_text(json.dumps(to_jsonable(manifest), indent=2,
                               sort_keys=True) + "\n")
    return path


def read_manifest(run_dir: str | pathlib.Path) -> dict | None:
    path = pathlib.Path(run_dir) / MANIFEST_NAME
    if not path.is_file():
        return None
    return json.loads(path.read_text())
