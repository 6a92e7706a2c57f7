"""Work from shapes: the FLOPs a decoder needs per token, and the FLOPs
and bytes of the BMA mixture-and-select step.

The terms that depend on the architecture (the matmul weights a token
passes through, attention over a context) are the configuration's
architecture module's (``bench/arch/<model_type>.py``), kept with the
benchmark so that no change to the program can move the yardstick.
Recomputation (activation checkpointing) is not counted: a utilisation is
the work the algorithm needs over the time it took.

``cfg`` is a configuration file's dict (Hugging Face key names).
"""
from __future__ import annotations

import harness


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matmul per token, the vocabulary head
    included."""
    return harness.arch(cfg).matmul_params(cfg)


def attention_flops(cfg: dict, context: float) -> float:
    """QK^T and AV for one query token attending ``context`` positions,
    over all layers."""
    return harness.arch(cfg).attention_flops(cfg, context)


def forward_flops_per_token(cfg: dict, context: float) -> float:
    """Forward FLOPs of one token that attends ``context`` positions."""
    return 2.0 * matmul_params(cfg) + attention_flops(cfg, context)


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """One causal prefill of ``prompt_len`` tokens.  The program computes
    the vocabulary head for the last position only, so the head counts
    once."""
    head = cfg["hidden_size"] * cfg["vocab_size"]  # one D x V product per token
    body = 2.0 * (matmul_params(cfg) - head) * prompt_len
    attn = attention_flops(cfg, (prompt_len + 1) / 2.0) * prompt_len
    return body + attn + 2.0 * head


def decode_flops(cfg: dict, context: int) -> float:
    """One decode token whose query attends ``context`` positions (the
    prompt, the tokens before it, and itself)."""
    return forward_flops_per_token(cfg, context)


def request_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """A served request: its prefill (which yields the first token), then
    ``new_tokens - 1`` decode steps, the i-th attending prompt_len + i
    positions."""
    total = prefill_flops(cfg, prompt_len)
    for i in range(1, max(int(new_tokens), 1)):
        total += decode_flops(cfg, prompt_len + i)
    return total


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward of one training token in a causal sequence of
    ``seq``: the backward pass costs twice the forward."""
    return 3.0 * forward_flops_per_token(cfg, (seq + 1) / 2.0)


def bma_select_work(members: int, slots: int, vocab: int) -> tuple[float, float]:
    """(FLOPs, bytes) of mixing ``members`` logit rows per slot into the
    BMA log-probs and selecting a token.  Bytes: every f32 member logit
    read once, the f32 mixture row written once, one int32 token per slot.
    FLOPs: per member element a log-softmax (max, subtract, exp, sum, log
    and subtract: 5) and the member mixture (max, subtract, exp, sum, log:
    5).  The step is bound by bytes by two orders of magnitude."""
    elems = float(members) * slots * vocab
    flops = 10.0 * elems
    nbytes = 4.0 * elems + 4.0 * slots * vocab + 4.0 * slots
    return flops, nbytes
