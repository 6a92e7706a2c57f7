"""The configuration file chooses its architecture module
(``bench/arch/<model_type>.py``) and its reference
(``bench/reference/<reference>.py``).  The qwen3 weights and FLOP counts
are pinned to the numbers they had before the seam; a file that names no
module is refused; and an architecture defined here alone, with a leaf
layout of its own, runs through the serving and sampling cells' own
calls against its own reference."""
import hashlib
import json
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import flops
import harness
import sample
import serve
import tiny
import traffic
import weights

# sha256 of the float32 bytes of every leaf, in the layout's sorted order, of
# weights.make(tiny cell's configuration, PIN_SEED, copies), as computed
# before the layout moved into bench/arch/qwen3.py
PIN_SEED = 7_300_001_601
WEIGHT_DIGESTS = {
    "serve": (2, "c57a2fbe6f459e94bcedf5856b525f378312a2601e7e897c8202695b9e464eeb"),
    "sample": (1, "68a3b84d1eb366096ecd844c3165588c91731162b7be8f6d20e024d3a7a7b7fc"),
}


def _digest(cfg, seed, copies):
    tree = weights.make(cfg, seed, copies)
    h = hashlib.sha256()
    for path, _shape in weights._leaves(harness.arch(cfg).shapes(cfg)):
        leaf = tree
        for k in path:
            leaf = leaf[k]
        h.update(np.asarray(leaf, np.float32).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("kind", sorted(WEIGHT_DIGESTS))
def test_qwen3_weights_are_bit_for_bit_the_same(kind):
    cfg = (tiny.serve_cell() if kind == "serve" else tiny.sample_cell("sghmc-1chain"))["cfg"]
    copies, want = WEIGHT_DIGESTS[kind]
    assert _digest(cfg, PIN_SEED, copies) == want


# both qwen3-0.6b files, counted before the FLOP terms moved into
# bench/arch/qwen3.py (the two files share every width)
QWEN3_FLOPS = {
    "matmul_params": 595_984_384,
    "attention_flops_100": 22_937_600.0,
    "prefill_flops_512": 481_406_222_336.0,
    "decode_flops_2560": 1_779_171_328.0,
    "request_flops_512_128": 649_565_569_024.0,
    "train_flops_per_token_512": 3_752_411_136.0,
}


@pytest.mark.parametrize("name", ["qwen3-0.6b.ensemble-k2", "qwen3-0.6b.posterior"])
def test_qwen3_flops_are_the_same(name):
    cfg = json.loads((tiny.BENCH / "configs" / f"{name}.json").read_text())
    got = {
        "matmul_params": flops.matmul_params(cfg),
        "attention_flops_100": flops.attention_flops(cfg, 100),
        "prefill_flops_512": flops.prefill_flops(cfg, 512),
        "decode_flops_2560": flops.decode_flops(cfg, 2560),
        "request_flops_512_128": flops.request_flops(cfg, 512, 128),
        "train_flops_per_token_512": flops.train_flops_per_token(cfg, 512),
    }
    assert got == QWEN3_FLOPS


def test_qwen3_pool_is_the_same():
    cfg = tiny._load("configs/qwen3-0.6b.ensemble-k2.json")
    assert serve.pool_blocks(cfg, cfg["deployment"]) == 1171


def test_untied_qwen3_is_refused():
    cfg = dict(tiny.serve_cell()["cfg"], tie_word_embeddings=False)
    with pytest.raises(harness.CellError, match="tied-embedding"):
        serve.model_config(cfg)


@pytest.mark.parametrize("key,lookup,path", [
    ("model_type", harness.arch, "bench/arch/no_such_model.py"),
    ("reference", harness.reference, "bench/reference/no_such_model.py"),
])
def test_a_file_naming_no_module_is_refused(key, lookup, path):
    cfg = dict(tiny.serve_cell()["cfg"], **{key: "no_such_model"})
    with pytest.raises(harness.CellError, match=path):
        lookup(cfg)


@pytest.mark.parametrize("key", ["model_type", "reference"])
def test_load_cell_refuses_a_file_naming_no_module(tmp_path, key):
    cfg = dict(tiny._load("configs/qwen3-0.6b.posterior.json"), **{key: "no_such_model"})
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    bench = harness.load_benchmark()
    bench = dict(bench, configs=[dict(c, file=str(tmp_path / "cfg.json"))
                                 for c in bench["configs"]])
    with pytest.raises(harness.CellError, match="no_such_model.py is missing"):
        harness.load_cell("sample.sghmc1", bench)


# ---------------------------------------------------------------------------
# A stand-in architecture: qwen3's decoder without the per-head q/k norms and
# with an untied vocabulary head, so its leaf layout differs from qwen3's in
# both directions.  It and its reference live in this file alone.

STANDIN = "untied_standin"


def _standin_arch():
    from arch import qwen3

    mod = types.ModuleType(f"arch.{STANDIN}")

    def model_config(cfg):
        from repro.models.common import LayerKind, ModelConfig

        return ModelConfig(
            name=STANDIN, family="dense", vocab_size=cfg["vocab_size"],
            d_model=cfg["hidden_size"], num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], d_ff=cfg["intermediate_size"],
            pattern=(LayerKind("attn"),), act="silu", qk_norm=False,
            rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
            tie_embeddings=False, param_dtype=jnp.float32, compute_dtype=jnp.bfloat16)

    def shapes(cfg):
        out = qwen3.shapes(cfg)
        attn = out["layers"]["0"]["attn"]
        del attn["q_norm"], attn["k_norm"]
        out["embed"]["unembed"] = (cfg["hidden_size"], cfg["vocab_size"])
        return out

    mod.model_config = model_config
    mod.shapes = shapes
    mod.NORM_GAINS = ("ln1", "ln2", "final_norm")
    mod.std = qwen3.std
    # norms take part in no matmul, and qwen3's count has a D x V head
    mod.matmul_params = qwen3.matmul_params
    mod.attention_flops = qwen3.attention_flops
    mod.kv_bytes_per_token = qwen3.kv_bytes_per_token
    return mod


def _standin_reference():
    from reference import qwen3 as q

    mod = types.ModuleType(f"reference.{STANDIN}")

    def layer(cfg, p, x, precision):
        eps, dh = cfg["rms_norm_eps"], cfg["head_dim"]
        group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
        pos = jnp.arange(x.shape[0])
        a, m = p["attn"], p["mlp"]
        h = q.rms_norm(x, p["ln1"], eps)
        qh = q.rope(q.mm("sd,dhk->shk", h, a["wq"], precision), pos, cfg["rope_theta"])
        kh = q.rope(q.mm("sd,dhk->shk", h, a["wk"], precision), pos, cfg["rope_theta"])
        vh = q.mm("sd,dhk->shk", h, a["wv"], precision)
        kh, vh = jnp.repeat(kh, group, axis=1), jnp.repeat(vh, group, axis=1)
        s = q.mm("qhk,thk->hqt", qh, kh, precision) / jnp.sqrt(jnp.float32(dh))
        s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
        o = q.mm("hqt,thk->qhk", jax.nn.softmax(s, axis=-1), vh, precision)
        x = x + q.mm("qhk,hkd->qd", o, a["wo"], precision)
        h = q.rms_norm(x, p["ln2"], eps)
        g = jax.nn.silu(q.mm("sd,df->sf", h, m["w_gate"], precision))
        return x + q.mm("sf,fd->sd", g * q.mm("sd,df->sf", h, m["w_up"], precision),
                        m["w_down"], precision)

    def hidden(cfg, params, tokens, precision="f32", remat=False):
        body = lambda x, p: (layer(cfg, p, x, precision), None)
        x, _ = jax.lax.scan(jax.checkpoint(body) if remat else body,
                            params["embed"]["table"][tokens], params["layers"]["0"])
        return q.rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])

    def logits(cfg, params, h, precision="f32"):
        return q.mm("sd,dv->sv", h, params["embed"]["unembed"], precision)

    def potential_and_grad(cfg, params, batch, *, n_data, weight_decay, precision="f32",
                           rows=None):
        toks, labels = batch["tokens"][:rows], batch["labels"][:rows]

        def u(p):
            lg = jax.vmap(lambda t: logits(cfg, p, hidden(cfg, p, t, precision), precision))(toks)
            nll = jnp.sum(jax.nn.logsumexp(lg, axis=-1)
                          - jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0])
            prior = weight_decay * sum(jnp.sum(x * x) for x in jax.tree.leaves(p))
            return n_data / toks.size * nll + prior, nll / toks.size

        (val, loss), g = jax.value_and_grad(u, has_aux=True)(params)
        return val, loss, g

    mod.hidden, mod.logits, mod.potential_and_grad = hidden, logits, potential_and_grad
    mod.mixture_logprobs = q.mixture_logprobs
    return mod


@pytest.fixture
def standin(monkeypatch):
    """The stand-in registered through the seam, and a configuration file
    that names it."""
    monkeypatch.setitem(sys.modules, f"arch.{STANDIN}", _standin_arch())
    monkeypatch.setitem(sys.modules, f"reference.{STANDIN}", _standin_reference())

    def cell(c):
        c["cfg"] = dict(c["cfg"], model_type=STANDIN, reference=STANDIN,
                        tie_word_embeddings=False)
        return c

    return cell


def test_standin_layout_differs_from_qwen3(standin):
    cfg = standin(tiny.serve_cell())["cfg"]
    ours = {p for p, _ in weights._leaves(harness.arch(cfg).shapes(cfg))}
    qwen = {p for p, _ in weights._leaves(harness.arch(dict(cfg, model_type="qwen3")).shapes(cfg))}
    assert ("embed", "unembed") in ours - qwen
    assert ("layers", "0", "attn", "q_norm") in qwen - ours


def test_standin_serves_what_its_reference_serves(standin):
    cell = standin(tiny.serve_cell())
    cfg, mix = cell["cfg"], cell["mix"]
    device = jax.devices()[0]
    engine = serve.build(cfg, mix, 5, device)  # check_layout: the program holds `unembed`
    rows = traffic.serve_requests(mix, 5, cfg["vocab_size"], until_tick=60)
    report = engine.run(serve._requests(rows))
    sample_ = serve.check_sample(report, mix, 5)
    assert sum(r.num_tokens for r in sample_) > 0
    gaps = serve.reference_gaps(cfg, 5, sample_, {r[0]: r[1] for r in rows}, mix["max_seq"],
                                device)
    assert gaps["f32"] <= mix["check"]["limits"]["widest_gap_nats"], gaps


def test_standin_samples_what_its_reference_follows(standin):
    cell = standin(tiny.sample_cell("sghmc-1chain"))
    cfg, mix = cell["cfg"], cell["mix"]
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("chain",))
    chains = sample.Chains(cfg, mix, 3, mesh)
    seen = sample.first_steps(chains)
    del chains
    got = sample.readings(sample.follow(cfg, mix, 3, mesh, seen))
    for name in sample.CHECKS:
        assert got[name] <= mix["check"]["limits"][name], got
