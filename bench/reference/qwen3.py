"""Plain reference of the qwen3 decoder: forward pass, next-token loss,
its gradient and the K-member Bayesian model average, in float32 with
every matmul at ``highest`` precision.

It follows the published architecture (Hugging Face ``Qwen3ForCausalLM``):
pre-norm blocks with RMSNorm, grouped-query attention with a per-head
RMSNorm on queries and keys before rotary embeddings (half-split rotation,
base ``rope_theta``), causal softmax attention scaled by 1/sqrt(head_dim),
a SiLU-gated MLP, a final RMSNorm and a vocabulary head tied to the
embedding.  There is no cache, no kernel and no batching across requests:
each sequence runs whole.

``precision="fp8"`` is the control: every matmul operand is scaled per
tensor into float8 e4m3 (accumulating in float32), the step below the
bfloat16 the deployments state.  Everything else is the same.

Weights use the layout of ``bench/arch/qwen3.py``'s ``shapes``.  This
module imports nothing of the program under test.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _fp8(x):
    """x rounded to float8 e4m3 under a per-tensor scale, back in float32
    (products of such values are exact in float32, so a float32 matmul of
    them is an fp8 matmul that accumulates in float32)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def mm(eq: str, a, b, precision: str = "f32"):
    """einsum of two float32 operands at the given precision."""
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif precision != "f32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.einsum(eq, a, b, precision=HIGHEST, preferred_element_type=jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x (S, H, dh); rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs  # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer(cfg: dict, p, x, precision: str = "f32"):
    """One decoder block over a whole sequence x (S, D)."""
    eps = cfg["rms_norm_eps"]
    Hq, Hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    S = x.shape[0]
    pos = jnp.arange(S)
    a = p["attn"]
    h = rms_norm(x, p["ln1"], eps)
    q = rms_norm(mm("sd,dhk->shk", h, a["wq"], precision), a["q_norm"], eps)
    k = rms_norm(mm("sd,dhk->shk", h, a["wk"], precision), a["k_norm"], eps)
    v = mm("sd,dhk->shk", h, a["wv"], precision)
    q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    # query head j reads key/value head j // (Hq / Hkv)
    k = jnp.repeat(k, Hq // Hkv, axis=1)
    v = jnp.repeat(v, Hq // Hkv, axis=1)
    s = mm("qhk,thk->hqt", q, k, precision) / jnp.sqrt(jnp.float32(dh))
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = mm("hqt,thk->qhk", w, v, precision)
    x = x + mm("qhk,hkd->qd", o, a["wo"], precision)
    m = p["mlp"]
    h = rms_norm(x, p["ln2"], eps)
    g = jax.nn.silu(mm("sd,df->sf", h, m["w_gate"], precision))
    u = mm("sd,df->sf", h, m["w_up"], precision)
    return x + mm("sf,fd->sd", g * u, m["w_down"], precision)


def hidden(cfg: dict, params, tokens, precision: str = "f32", remat: bool = False):
    """Final-normed hidden states (S, D) of one sequence."""
    x = params["embed"]["table"][tokens]
    body = lambda x, p: (layer(cfg, p, x, precision), None)
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"]["0"])
    return rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])


def logits(cfg: dict, params, h, precision: str = "f32"):
    """Vocabulary logits (S, V) of hidden states h (S, D)."""
    return mm("sd,vd->sv", h, params["embed"]["table"], precision)


def mixture_logprobs(member_logits):
    """(K, ..., V) member logits -> log of the mean of the members'
    next-token distributions (the arithmetic BMA)."""
    lp = jax.nn.log_softmax(member_logits, axis=-1)
    return jax.nn.logsumexp(lp, axis=0) - jnp.log(jnp.float32(lp.shape[0]))


def sum_nll(cfg: dict, params, batch, precision: str = "f32", rows=None):
    """Summed next-token NLL and token count over a batch of rows
    (tokens/labels (B, S)); ``rows`` limits the batch to its first rows."""
    toks, labels = batch["tokens"], batch["labels"]
    if rows is not None:
        toks, labels = toks[:rows], labels[:rows]

    @jax.checkpoint
    def one(t, y):
        lg = logits(cfg, params, hidden(cfg, params, t, precision, remat=True), precision)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(lg, y[:, None], axis=-1)[:, 0])

    # row by row, recomputed in the backward pass: one (S, V) block of
    # logits lives at a time
    per_row = jax.lax.map(lambda ty: one(*ty), (toks, labels))
    return jnp.sum(per_row), jnp.float32(toks.size)


def potential_and_grad(cfg: dict, params, batch, *, n_data: float, weight_decay: float,
                       precision: str = "f32", rows=None):
    """U = N/|B| * NLL + weight_decay * ||theta||^2 and its gradient;
    also the NLL per token (the loss the sampler reports)."""

    def u(p):
        s, c = sum_nll(cfg, p, batch, precision, rows)
        prior = weight_decay * sum(jnp.sum(x * x) for x in jax.tree.leaves(p))
        return n_data / c * s + prior, s / c

    (val, loss), g = jax.value_and_grad(u, has_aux=True)(params)
    return val, loss, g
