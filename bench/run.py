#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

  python3 bench/run.py --workload serve.chat --seed 7 --seconds 30 --trace 0

``--trace 0`` measures the cell's end-to-end metrics; ``--trace 1`` runs a
profiled window (a sampling cell's is shorter) and reports its per-layer
metrics.  The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), with
``checks`` last: each number compared with the reference beside its limit.
The cell, its configuration and its traffic mix are found by name from
``BENCHMARK.json``.  Without a TPU, or with fewer chips than the cell asks
for, it exits 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(1, str(Path(__file__).resolve().parents[1] / "src"))

import harness  # noqa: E402


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, devices, t_start: float) -> dict:
    """One run of a cell on ``devices``; the result line as a dict."""
    kind = cell["mix"]["kind"]
    if kind != cell["cfg"]["deployment"]["kind"]:
        raise harness.CellError(f"traffic of kind {kind} for a {cell['cfg']['deployment']['kind']} "
                                "configuration")
    compiles = harness.CompileLog()
    if kind == "serve":
        import serve

        return serve.run(cell, seed, seconds, trace, devices, t_start, compiles)
    import sample

    return sample.run(cell, seed, seconds, trace, devices, t_start, compiles)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU; JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips; found {len(devices)}",
              file=sys.stderr)
        return 2
    harness.peak(devices[0].device_kind)  # an unknown chip is an error before any work
    harness.note(f"device {devices[0].device_kind} x{len(devices)}; compile cache "
                 f"{harness.enable_compile_cache()}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices[: cell["chips"]], T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
