#!/usr/bin/env python3
"""Readings that the limits of a cell's correctness check are set from,
made on the chip at the cell's own size, in one process:

* the system under test on each of ``--seeds`` (the lower readings);
* the control, the reference computed in float8 and put in the program's
  place, on ``--control-seeds`` (the upper readings);
* each fault the cell can have, planted, on ``--control-seeds``, and a
  program that drops its norm gains (the weights draw them away from 1 so
  that the check can see this).

  python3 bench/calibrate.py --workload sample.sghmc1 --seeds 1,2,3 \\
      --control-seeds 4,5,6 --out readings.json

Serving windows last ``--seconds`` at the cell's load; a sampling cell's
readings need no window.  Each reading is printed as it is made and all
are written to ``--out`` as JSON.  The benchmark's runs never run this.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(1, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np  # noqa: E402

import harness  # noqa: E402


def serve_readings(cell, seed, seconds, device, fault=None):
    """(program gap, control gap) of one seed's window."""
    import serve
    import traffic

    cfg, mix = cell["cfg"], cell["mix"]
    with dropped_norm_gains(fault == "norm_gain_dropped"):
        engine = serve.build(cfg, mix, seed, device)
        if fault == "token_altered":
            plant_altered_token(engine, cfg["vocab_size"])
        timing = serve.warm_up(engine, mix, cfg, device)
    rows = traffic.serve_requests(mix, seed, cfg["vocab_size"],
                                  until_tick=serve.planned_ticks(mix, timing, seconds))
    report = engine.run(serve._requests(rows), max_steps=serve.planned_ticks(mix, timing, seconds))
    sample = serve.check_sample(report, mix, seed)
    del engine
    gc.collect()
    gaps = serve.reference_gaps(cfg, seed, sample, {r[0]: r[1] for r in rows}, mix["max_seq"],
                                device, precisions=("f32", "fp8"))
    return {"gap": gaps["f32"], "control_gap": gaps["fp8"],
            "sample_tokens": sum(r.num_tokens for r in sample)}


def plant_altered_token(engine, vocab):
    """The fault: a selected token is altered where the engine produces it
    (every token id divisible by 7 is served as the next id)."""
    import jax.numpy as jnp

    select = engine._mix_select

    def altered(logits, key):
        tok, logp = select(logits, key)
        return jnp.where(tok % 7 == 0, (tok + 1) % vocab, tok), logp

    engine._mix_select = altered


class dropped_norm_gains:
    """Within ``with``, the program's RMSNorm ignores its gain (every
    program traced there keeps the fault)."""

    def __init__(self, on: bool):
        self.on = on

    def __enter__(self):
        if self.on:
            import jax.numpy as jnp

            from repro.models import layers

            self.orig = orig = layers.rms_norm
            layers.rms_norm = lambda x, w, eps, offset=0.0: orig(x, jnp.ones_like(w), eps, offset)
        return self

    def __exit__(self, *exc):
        if self.on:
            from repro.models import layers

            layers.rms_norm = self.orig
        return False


def sample_readings(cell, seed, mesh, variant=None):
    """Readings of the program (variant None, or "norm_gain_dropped" for
    the program with that fault), or of the control ("control") or a
    planted fault of the reference put in the program's place."""
    import sample

    cfg, mix = cell["cfg"], cell["mix"]
    if variant in (None, "norm_gain_dropped"):
        with dropped_norm_gains(variant is not None):
            chains = sample.Chains(cfg, mix, seed, mesh)
            seen = sample.first_steps(chains)
        del chains
    else:
        precision = "fp8" if variant == "control" else "f32"
        fault = None if variant == "control" else variant
        seen = sample.follow(cfg, mix, seed, mesh, None, precision=precision, fault=fault,
                             record=True)["own"]
    gc.collect()
    r = sample.follow(cfg, mix, seed, mesh, seen)
    return sample.readings(r)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]

    import jax
    from jax.sharding import Mesh

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("calibrate: needs the cell's TPU chips", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    devices = devices[: cell["chips"]]
    out = {"workload": args.workload, "device": devices[0].device_kind, "runs": []}
    kind = cell["mix"]["kind"]
    if kind == "serve":
        plan = [(s, None) for s in seeds] + [(s, f) for f in ("token_altered", "norm_gain_dropped")
                                             for s in control_seeds]
    else:
        faults = ["unchanged", "half_batch", "norm_gain_dropped"]
        plan = ([(s, None) for s in seeds] + [(s, "control") for s in control_seeds]
                + [(s, f) for f in faults for s in control_seeds])
    mesh = Mesh(np.asarray(devices), ("chain",))
    for seed, variant in plan:
        t0 = time.perf_counter()
        if kind == "serve":
            got = serve_readings(cell, seed, args.seconds, devices[0], variant)
        else:
            got = sample_readings(cell, seed, mesh, variant)
        row = {"seed": seed, "variant": variant or "program", **got,
               "seconds": time.perf_counter() - t0}
        print(json.dumps(row), flush=True)
        out["runs"].append(row)
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
