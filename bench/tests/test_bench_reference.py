"""The plain reference against the program at a small size on the CPU,
both in float32: the same weights give the same logits, loss, gradient and
BMA mixture."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import serve
import weights
from reference import qwen3 as ref

SMALL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             intermediate_size=128, num_hidden_layers=2, vocab_size=512)


@pytest.fixture(scope="module")
def setup():
    cfg = json.loads((Path(__file__).resolve().parents[1] / "configs" /
                      "qwen3-0.6b.posterior.json").read_text())
    cfg.update(SMALL)
    from repro.models import get_model

    mcfg = serve.model_config(cfg).replace(compute_dtype=jnp.float32)
    model = get_model(mcfg)
    weights.check_layout(cfg, model.param_specs(mcfg))
    params = jax.tree.map(lambda x: x[0], weights.make(cfg, 5, 1))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, cfg["vocab_size"], jnp.int32)
    return cfg, mcfg, model, params, {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def test_logits_match_the_program(setup):
    cfg, mcfg, model, params, batch = setup
    prog, _ = model.prefill(mcfg, params, {"tokens": batch["tokens"][:1]}, 64)
    with jax.default_matmul_precision("highest"):
        h = ref.hidden(cfg, params, batch["tokens"][0])
        want = ref.logits(cfg, params, h[-1:])
    np.testing.assert_allclose(np.asarray(prog[0, 0]), np.asarray(want[0]), rtol=1e-4, atol=1e-5)


def test_loss_and_gradient_match_the_program(setup):
    cfg, mcfg, model, params, batch = setup
    from repro.train.step import make_grad_fn

    grad_fn = make_grad_fn(mcfg, model, n_data=1000.0, weight_decay=1e-5)
    with jax.default_matmul_precision("highest"):
        g_prog, m = grad_fn(jax.tree.map(lambda x: x[None], params),
                            jax.tree.map(lambda x: x[None], batch))
    _, loss, g_ref = ref.potential_and_grad(cfg, params, batch, n_data=1000.0, weight_decay=1e-5)
    assert abs(float(m["nll_per_token"]) - float(loss)) < 1e-5 * float(loss)
    for a, b in zip(jax.tree.leaves(g_prog), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b), rtol=2e-3,
                                   atol=2e-4 * float(jnp.max(jnp.abs(b))))


def test_mixture_matches_the_program():
    from repro.serve.engine.bma import mixture_logprobs

    lg = 3.0 * jax.random.normal(jax.random.PRNGKey(2), (2, 5, 97))
    np.testing.assert_allclose(np.asarray(mixture_logprobs(lg, "probs")),
                               np.asarray(ref.mixture_logprobs(lg)), atol=1e-5)


def test_fp8_control_is_coarser_than_f32(setup):
    cfg, _mcfg, _model, params, batch = setup
    h32 = ref.hidden(cfg, params, batch["tokens"][0])
    h8 = ref.hidden(cfg, params, batch["tokens"][0], "fp8")
    rel = float(jnp.linalg.norm(h8 - h32) / jnp.linalg.norm(h32))
    assert 1e-3 < rel < 0.5
