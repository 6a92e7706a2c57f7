"""The shared run manifest.

The manifest is the provenance block stamped into every artifact a run
emits — ``trace.json`` (``otherData.manifest``) and each ``BENCH_*.json``
(``manifest`` key, via ``benchmarks/common.py``) — so any two artifacts
can be matched to the same code + backend + device state after the fact.
"""
from __future__ import annotations

import platform
import subprocess
import sys
import time

MANIFEST_KEYS = (
    "git_sha",
    "jax_version",
    "backend",
    "device_kind",
    "device_count",
    "python",
    "platform",
    "timestamp",
)


def _git_sha() -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=5,
        )
        return out.stdout.strip() if out.returncode == 0 else "unknown"
    except (OSError, subprocess.TimeoutExpired):
        return "unknown"


def run_manifest() -> dict:
    """Provenance of the current run.  Importing jax here is fine — every
    caller already has it resident; failures degrade to "unknown" rather
    than taking the run down."""
    try:
        import jax

        backend = jax.default_backend()
        devices = jax.devices()
        device_kind = devices[0].device_kind if devices else "unknown"
        device_count = len(devices)
        jax_version = jax.__version__
    except Exception:  # manifest must never be the thing that crashes a run
        backend = device_kind = jax_version = "unknown"
        device_count = 0
    return {
        "git_sha": _git_sha(),
        "jax_version": jax_version,
        "backend": backend,
        "device_kind": device_kind,
        "device_count": device_count,
        "python": sys.version.split()[0],
        "platform": platform.platform(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
    }
