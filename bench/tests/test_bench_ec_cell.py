"""The coupled cell's path at a small size on four CPU devices: a sound run
comes out correct, and a program whose exchange takes each chip's own chain
for the mean, or runs on every step outside the sync branch, or that skips
the replicated centre's update, does not."""
import pytest

import sample
from ec_runs import child_runs


@pytest.fixture(scope="module")
def runs():
    return child_runs(["sound", "own_chain_mean", "every_step_exchange", "centre_frozen"])


def test_sound_run_is_correct(runs):
    r = runs["sound"]
    assert r["correct"], r["checks"]
    assert tuple(r["checks"]) == sample.COUPLED_CHECKS
    assert r["keys"][-1] == "checks"


def test_own_chain_mean_is_caught(runs):
    r = runs["own_chain_mean"]
    assert not r["correct"], r["checks"]
    checks = r["checks"]
    # four independent draws: the own chain's norm is about twice the mean's
    assert checks["exchange_gap"]["value"] > 3 * checks["exchange_gap"]["limit"], checks
    assert checks["missing_exchange"]["value"] == 1, checks


def test_exchange_outside_the_sync_branch_is_caught(runs):
    r = runs["every_step_exchange"]
    assert not r["correct"], r["checks"]
    assert r["checks"]["stray_collectives"]["value"] >= 1, r["checks"]
    assert r["checks"]["missing_exchange"]["value"] == 1, r["checks"]


def test_frozen_centre_is_caught(runs):
    r = runs["centre_frozen"]
    assert not r["correct"], r["checks"]
    # the centre's momentum stays 0 where the reference's holds its noise
    assert r["checks"]["centre_gap"]["value"] > 0.9, r["checks"]
