"""A run of each cell at a small size, with the chip check skipped: sound,
it comes out correct; with the timed path broken underneath in each way
the cell can be broken, it comes out not correct."""
import time

import jax
import pytest

import run
import tiny


def _run(cell, seed=3, seconds=0.5):
    return run.run_cell(cell, seed, seconds, False, jax.devices()[: cell["chips"]],
                        time.perf_counter())


# a serving window's ticks follow from the tick time the warm-up measures;
# two seconds leave even a loaded machine some hundred served tokens to check
SERVE_SECONDS = 2.0


def test_serve_sound_run_is_correct():
    res = _run(tiny.serve_cell(), seconds=SERVE_SECONDS)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and list(res)[-1] == "checks"


def test_serve_altered_token_is_caught(monkeypatch):
    from repro.serve.engine.engine import ServeEngine

    select = ServeEngine._mix_select

    def altered(self, logits, key):
        tok, logp = select(self, logits, key)
        return jax.numpy.where(tok % 7 == 0, (tok + 1) % logits.shape[-1], tok), logp

    monkeypatch.setattr(ServeEngine, "_mix_select", altered)
    res = _run(tiny.serve_cell(), seconds=SERVE_SECONDS)
    assert not res["correct"], res["checks"]


def test_sghmc_sound_run_is_correct():
    res = _run(tiny.sample_cell("sghmc-1chain"))
    assert res["correct"], res["checks"]


def _unchanged_sampler(monkeypatch):
    import sample

    make = sample.make_sampler

    def frozen(dep, mix):
        s = make(dep, mix)
        zeros = lambda p: jax.tree.map(jax.numpy.zeros_like, p)
        return s._replace(update=lambda g, state, params, rng: (zeros(params), state))

    monkeypatch.setattr(sample, "make_sampler", frozen)


def _half_batch(monkeypatch):
    import repro.train.step as step

    make = step.make_grad_fn

    def halved(*a, **k):
        fn = make(*a, **k)
        return lambda t, b: fn(t, jax.tree.map(lambda x: x[:, : x.shape[1] // 2], b))

    monkeypatch.setattr(step, "make_grad_fn", halved)


def _dropped_norm_gain(monkeypatch):
    from repro.models import layers

    norm = layers.rms_norm
    monkeypatch.setattr(layers, "rms_norm", lambda x, w, eps, offset=0.0:
                        norm(x, jax.numpy.ones_like(w), eps, offset))


@pytest.mark.parametrize("fault", [_unchanged_sampler, _half_batch, _dropped_norm_gain])
def test_sghmc_faults_are_caught(monkeypatch, fault):
    fault(monkeypatch)
    res = _run(tiny.sample_cell("sghmc-1chain"))
    assert not res["correct"], res["checks"]

