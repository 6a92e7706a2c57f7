"""Runs of the coupled cell (``ec-4chain``) at a small size on four CPU
devices, each sound, with the timed path broken underneath in one way, or
with the control (the float8 reference) put in its place; one JSON line
per run.  The test files start it as a child process, since
the forced device count must be set before JAX starts:

  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
      python3 bench/tests/ec_child.py sound own_chain_mean
"""
import contextlib
import json
import sys
import time
from pathlib import Path
from unittest import mock

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent), str(HERE.parents[1] / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

import run  # noqa: E402
import sample  # noqa: E402
import tiny  # noqa: E402

SEED = 7_300_001_701  # over 32 bits, as the benchmark's seeds are


def own_chain_mean():
    """The exchange's mean taken over the chip's own chain, without the
    collective."""
    import importlib

    module = importlib.import_module("repro.core.ec_sghmc")
    local = module.tree_mean_axis0
    return mock.patch.object(module, "tree_mean_axis0", lambda a, axis_name=None: local(a))


def every_step_exchange():
    """Both branches of every cond computed and one selected: the exchange
    runs, unseen in the trajectory, on every step outside the sync branch."""
    def cond(pred, true_fn, false_fn, *operands):
        return jax.tree.map(lambda a, b: jnp.where(pred, a, b), true_fn(*operands),
                            false_fn(*operands))

    return mock.patch.object(jax.lax, "cond", cond)


def unchanged():
    """A step that returns its state unchanged."""
    make = sample.make_sampler

    def frozen(dep, mix):
        s = make(dep, mix)
        zeros = lambda p: jax.tree.map(jnp.zeros_like, p)
        return s._replace(update=lambda g, state, params, rng: (zeros(params), state))

    return mock.patch.object(sample, "make_sampler", frozen)


def centre_frozen():
    """The replicated centre's update and its noise draw skipped: the
    centre's position and momentum stay as they were."""
    make = sample.make_sampler

    def frozen(dep, mix):
        s = make(dep, mix)

        def update(g, state, params, rng):
            upd, new = s.update(g, state, params, rng)
            return upd, new._replace(center=state.center, center_momentum=state.center_momentum)

        return s._replace(update=update)

    return mock.patch.object(sample, "make_sampler", frozen)


def control_in_place():
    """The control in the program's place: what the check reads of the
    first steps is the float8 reference's (the window still runs the
    program from where its own first steps left it)."""
    cfg = tiny.sample_cell("ec-4chain")["cfg"]
    first = sample.first_steps

    def fp8(chains):
        first(chains)
        return sample.follow(cfg, chains.mix, SEED, chains.mesh, None, precision="fp8",
                             record=True)["own"]

    return mock.patch.object(sample, "first_steps", fp8)


def half_batch():
    """The gradient over the first half of each chain's rows."""
    import repro.train.step as step

    make = step.make_grad_fn

    def halved(*a, **k):
        fn = make(*a, **k)
        return lambda t, b: fn(t, jax.tree.map(lambda x: x[:, : x.shape[1] // 2], b))

    return mock.patch.object(step, "make_grad_fn", halved)


FAULTS = {"sound": contextlib.nullcontext, "own_chain_mean": own_chain_mean,
          "every_step_exchange": every_step_exchange, "unchanged": unchanged,
          "half_batch": half_batch, "centre_frozen": centre_frozen,
          "control_in_place": control_in_place}


def cell_run(name: str) -> dict:
    cell = tiny.sample_cell("ec-4chain")
    with FAULTS[name]():
        res = run.run_cell(cell, SEED, 0.5, False, jax.devices()[:4], time.perf_counter())
    return {"correct": res["correct"], "checks": res["checks"], "keys": list(res)}


def control() -> dict:
    """The program's readings and the control's (the float8 reference put
    in the program's place), and how the program's momentum is laid out."""
    cell = tiny.sample_cell("ec-4chain")
    cfg, mix = cell["cfg"], cell["mix"]
    mesh = Mesh(np.asarray(jax.devices()[:4]), (sample.CHAIN,))
    chains = sample.Chains(cfg, mix, SEED, mesh)
    # chains a chip in each momentum leaf as the sampler's state is made
    rows = sorted({x.sharding.shard_shape(x.shape)[0] for x in jax.tree.leaves(chains.state.momentum)})
    seen = sample.first_steps(chains)
    del chains
    prog = sample.readings(sample.follow(cfg, mix, SEED, mesh, seen))
    own = sample.follow(cfg, mix, SEED, mesh, None, precision="fp8", record=True)["own"]
    ctrl = sample.readings(sample.follow(cfg, mix, SEED, mesh, own))
    return {"program": prog, "control": ctrl, "momentum_chains_a_chip": rows}


def main(names) -> int:
    if len(jax.devices()) < 4:
        print("ec_child: needs four devices", file=sys.stderr)
        return 2
    for name in names:
        out = control() if name == "control" else cell_run(name)
        print(json.dumps({"run": name, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
