"""Multi-device scale-out sweep (DESIGN.md §7): ``run_sharded`` on a
(chain,) mesh at every device count in {1, 2, 4, 8}, raw vs int8-compressed
center exchange.

Each device count runs in its OWN subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the flag must be
set before jax picks a backend, and this parent has usually already locked
one) — ``repro.launch.mesh.forced_device_env`` builds the environment, the
same fallback the multidevice test harness uses.  That environment pins
``JAX_PLATFORMS=cpu``, so the children run on forced CPU devices even on an
accelerator host; every row records the backend and device kind its child
actually ran on.

Recorded per (device count, mode): steps/s of the compiled sharded program
and the per-device sync wire payload of one s-periodic center exchange
(``sync_wire_bytes``) — the compressed path's ~4x smaller operand is the
point of the packed int8 all_gather.  CPU-forced devices share one socket,
so QUICK steps/s across device counts measures overhead, not speedup; the
wire-bytes column is the hardware-independent signal.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
from pathlib import Path

from common import QUICK, emit, record

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.distributed import sync_wire_bytes  # noqa: E402
from repro.launch.mesh import forced_device_env  # noqa: E402

K = 8
D = 16_384 if QUICK else 262_144
STEPS = 256 if QUICK else 2_048
SYNC = 4
DEVICE_COUNTS = (1, 2, 4, 8)

_CHILD = textwrap.dedent(
    """
    import sys
    import jax, jax.numpy as jnp, numpy as np
    n, D, steps, sync = map(int, sys.argv[1:5])
    assert jax.device_count() >= n, (jax.device_count(), n)
    from repro import core
    from repro.distributed import int8_codec
    from repro.run import ChainExecutor

    K = 8
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:n]), ("chain",))
    mu = jnp.zeros((D,), jnp.float32)
    params0 = 0.1 * jax.random.normal(jax.random.PRNGKey(0), (K, D), jnp.float32)
    for mode in ("raw", "compressed"):
        sampler = core.ec_sghmc(
            step_size=1e-3, alpha=1.0, sync_every=sync, noise_convention="eq6",
            chain_axis="chain", per_chain_noise=True,
            compression=int8_codec() if mode == "compressed" else None)
        ex = ChainExecutor(sampler=sampler, grad_fn=lambda t, _b: t - mu,
                           chunk_steps=steps, key_mode="fold")
        # first call compiles; the second re-runs the cached executable so
        # steps_per_s measures compute
        ex.run_sharded(params0 + 0.0, sampler.init(params0), num_steps=steps,
                       key=jax.random.key(0), mesh=mesh)
        res = ex.run_sharded(params0 + 0.0, sampler.init(params0), num_steps=steps,
                             key=jax.random.key(0), mesh=mesh)
        ok = bool(np.all(np.isfinite(np.asarray(res.params))))
        kind = jax.devices()[0].device_kind.replace(" ", "_")
        print(f"RESULT devices={n} mode={mode} steps_per_s={res.steps_per_s:.2f} "
              f"ok={ok} backend={jax.default_backend()} device_kind={kind}", flush=True)
    """
)


def _child_env(n: int) -> dict:
    env = forced_device_env(n)
    env["PYTHONPATH"] = os.pathsep.join(
        p
        for p in (str(Path(__file__).resolve().parent.parent / "src"), env.get("PYTHONPATH"))
        if p
    )
    return env


def run():
    rows = []
    for n in DEVICE_COUNTS:
        out = subprocess.run(
            [sys.executable, "-c", _CHILD, str(n), str(D), str(STEPS), str(SYNC)],
            env=_child_env(n),
            capture_output=True,
            text=True,
            timeout=1200,
        )
        if out.returncode != 0:
            raise RuntimeError(f"shard child (n={n}) failed:\n{out.stderr[-3000:]}")
        for line in out.stdout.splitlines():
            if not line.startswith("RESULT"):
                continue
            kv = dict(p.split("=") for p in line.split()[1:])
            assert kv["ok"] == "True", line
            mode = kv["mode"]
            sps = float(kv["steps_per_s"])
            wire = sync_wire_bytes(D, compressed=(mode == "compressed"))
            emit(f"shard_{mode}_dev{n}", 1e6 / max(sps, 1e-9), f"{sps:.1f} steps/s")
            rows.append(
                {
                    "devices": n,
                    "mode": mode,
                    "backend": kv["backend"],
                    "device_kind": kv["device_kind"],
                    "steps_per_s": round(sps, 2),
                    "sync_wire_bytes_per_device": wire,
                    "syncs_per_run": STEPS // SYNC,
                }
            )
    raw = sync_wire_bytes(D, compressed=False)
    comp = sync_wire_bytes(D, compressed=True)
    record(
        "shard_sweep",
        {
            "num_chains": K,
            "num_params": D,
            "steps": STEPS,
            "sync_every": SYNC,
            "device_counts": list(DEVICE_COUNTS),
            "wire_compression_ratio": round(comp / raw, 4),
            "rows": rows,
        },
    )
    return {"wire_ratio": round(comp / raw, 4), "device_counts": len(DEVICE_COUNTS)}
