"""The control, the reference computed in float8 and put in the program's
place, reads far above the program on the numbers the check compares.
Here at a small size on the CPU, against the program's own readings at the
same size; PERF.md gives both on the chip at the cells' sizes, where the
limits were set between them (``bench/calibrate.py``)."""
import jax
import numpy as np
import pytest
from jax.sharding import Mesh

import sample
import serve
import tiny
import traffic

SEPARATION = 3.0  # an upper reading counts only at three times the lower


def test_serve_control_reads_far_above_the_program():
    cell = tiny.serve_cell()
    cfg, mix = cell["cfg"], cell["mix"]
    device = jax.devices()[0]
    engine = serve.build(cfg, mix, 5, device)
    rows = traffic.serve_requests(mix, 5, cfg["vocab_size"], until_tick=60)
    report = engine.run(serve._requests(rows))
    gaps = serve.reference_gaps(cfg, 5, serve.check_sample(report, mix, 5),
                                {r[0]: r[1] for r in rows}, mix["max_seq"], device,
                                precisions=("f32", "fp8"))
    assert gaps["fp8"] > SEPARATION * gaps["f32"] and gaps["fp8"] > 0, gaps


@pytest.mark.parametrize("seed", [3, 4])
def test_sghmc_control_reads_far_above_the_program(seed):
    cell = tiny.sample_cell("sghmc-1chain")
    cfg, mix = cell["cfg"], cell["mix"]
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("chain",))
    chains = sample.Chains(cfg, mix, seed, mesh)
    seen = sample.first_steps(chains)
    del chains
    prog = sample.readings(sample.follow(cfg, mix, seed, mesh, seen))
    own = sample.follow(cfg, mix, seed, mesh, None, precision="fp8", record=True)["own"]
    ctrl = sample.readings(sample.follow(cfg, mix, seed, mesh, own))
    assert any(ctrl[k] > SEPARATION * prog[k] for k in sample.CHECKS), (prog, ctrl)
