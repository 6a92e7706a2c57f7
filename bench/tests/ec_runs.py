"""Start ``ec_child.py`` on four forced CPU devices and collect its runs."""
import json
import os
import subprocess
import sys
from pathlib import Path

CHILD = Path(__file__).resolve().parent / "ec_child.py"


def child_runs(names) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4").strip())
    proc = subprocess.run([sys.executable, str(CHILD), *names], env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    rows = [json.loads(line) for line in proc.stdout.splitlines() if line.startswith("{")]
    return {r["run"]: r for r in rows}
