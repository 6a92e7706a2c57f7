#!/usr/bin/env python3
"""Sweep a serving cell's arrival rate on the engine's tick clock, to find
the knee: the highest rate at which the queue stays bounded.

With greedy selection and no EOS every request holds its slot for exactly
its output length, and admission depends only on free slots and free
pages, so the schedule in ticks is the same at any model width.  This runs
the cell's own engine, mix, slots and page count with a model a few
numbers wide, on the CPU, for a long horizon:

  JAX_PLATFORMS=cpu python3 bench/knee.py --workload serve.chat \\
      --rates 0.08,0.09,0.10 --ticks 3000 [--slots 20]

Each rate prints one JSON line: mean active slots, requests still queued
at the cut, and the mean admission delay (ticks) of the requests arriving
in each third of the horizon.  A delay that grows from third to third
means the rate is past the knee.  The benchmark's runs never run this.
"""
import argparse
import json
import sys
from pathlib import Path

sys.path.insert(1, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np  # noqa: E402

import harness  # noqa: E402
import serve  # noqa: E402
import traffic  # noqa: E402
import weights  # noqa: E402

# the widths of the stand-in model; depth, widths and vocabulary do not
# enter the schedule
NARROW = dict(hidden_size=16, num_attention_heads=2, num_key_value_heads=1, head_dim=8,
              intermediate_size=16, num_hidden_layers=1, vocab_size=64)


def sweep(cell: dict, rates, ticks: int, slots: int | None = None):
    from repro.models import get_model
    from repro.obs import trace as obs_trace
    from repro.serve.engine import ServeEngine

    cfg, dep = cell["cfg"], cell["cfg"]["deployment"]
    blocks = serve.pool_blocks(cfg, dep)  # the cell's page count, at its real widths
    narrow = dict(cfg, **NARROW)
    mcfg = serve.model_config(narrow)
    members = weights.make(narrow, 1, dep["members"])
    for rate in rates:
        mix = json.loads(json.dumps(cell["mix"]))
        mix["arrival"]["rate_per_tick"] = rate
        if slots:
            mix["slots"] = slots
        engine = ServeEngine(mcfg, get_model(mcfg), members, num_slots=mix["slots"],
                             max_seq=mix["max_seq"], bma=dep["bma"], eos_id=dep["eos_id"],
                             paged=True, block_size=dep["block_size"], num_blocks=blocks,
                             fused_select=False)
        rows = traffic.serve_requests(mix, 5, narrow["vocab_size"], until_tick=ticks)
        tracer = obs_trace.enable(capacity=1 << 20)
        report = engine.run(serve._requests(rows), max_steps=ticks)
        active = [a["active"] for ph, n, _c, _t, _d, a in tracer.events()
                  if ph == "X" and n == "serve.decode_tick"]
        obs_trace.disable()
        served = {r.rid for r in report.results}
        arrival = np.asarray([rows[r.rid][3] for r in report.results])
        delay = np.asarray([r.admitted_step - rows[r.rid][3] for r in report.results])
        thirds = [float(np.mean(delay[(arrival >= ticks * i / 3) & (arrival < ticks * (i + 1) / 3)]))
                  for i in range(3)]
        yield {"rate_per_tick": rate, "slots": mix["slots"], "blocks": blocks, "sent": len(rows),
               "queued_at_cut": sum(r[0] not in served for r in rows),
               "mean_active": float(np.mean(active)), "delay_by_third": thirds}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--ticks", type=int, default=3000)
    ap.add_argument("--slots", type=int, default=None)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    for row in sweep(cell, [float(r) for r in args.rates.split(",")], args.ticks, args.slots):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
