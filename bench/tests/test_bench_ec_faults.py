"""The coupled cell at a small size on four CPU devices: a step that
returns its state unchanged, or a gradient over half of each chain's rows,
comes out not correct; so does the control, the float8 reference put in
the program's place, which reads far above the program; and the sampler's
momentum is made one chain a chip, as the sharded step takes it (made replicated, it
holds four chains' worth on each chip, more than a chip holds at the
cell's size)."""
import pytest

from ec_runs import child_runs

SEPARATION = 3.0  # an upper reading counts only at three times the lower


@pytest.fixture(scope="module")
def runs():
    return child_runs(["unchanged", "half_batch", "control", "control_in_place"])


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_fault_is_caught(runs, fault):
    assert not runs[fault]["correct"], runs[fault]["checks"]


def test_control_reads_far_above_the_program(runs):
    prog, ctrl = runs["control"]["program"], runs["control"]["control"]
    assert any(ctrl[k] > SEPARATION * prog[k] and ctrl[k] > 0
               for k in ("grad_gap", "change_gap")), (prog, ctrl)


def test_control_in_place_is_not_correct(runs):
    r = runs["control_in_place"]
    assert not r["correct"], r["checks"]


def test_momentum_lies_one_chain_a_chip(runs):
    assert runs["control"]["momentum_chains_a_chip"] == [1]
