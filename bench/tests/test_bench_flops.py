"""Work from shapes, pinned against counts made by hand."""
import json
from pathlib import Path

import pytest

import flops

CFG = json.loads((Path(__file__).resolve().parents[1] / "configs" /
                  "qwen3-0.6b.ensemble-k2.json").read_text())
TINY = {"model_type": "qwen3",
        "hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2,
        "intermediate_size": 16, "num_hidden_layers": 3, "vocab_size": 10}


def test_qwen3_matmul_params_by_hand():
    # per layer: q,k,v 1024*128*(16+8+8) + o 16*128*1024 + MLP 3*1024*3072
    per_layer = 1024 * 128 * 32 + 2048 * 1024 + 3 * 1024 * 3072
    assert per_layer == 15_728_640
    assert flops.matmul_params(CFG) == 28 * per_layer + 1024 * 151_936 == 595_984_384


@pytest.mark.parametrize("context", [1, 7, 2560])
def test_decode_flops_tiny_by_hand(context):
    # tiny: layer matmuls 8*2*(4+4) + 4*2*8 + 3*8*16 = 128+64+384 = 576; x3 layers
    # + head 8*10 = 80 -> 1808 weights, 3616 FLOPs; attention 4*3*4*2*ctx = 96 ctx
    assert flops.matmul_params(TINY) == 1808
    assert flops.decode_flops(TINY, context) == 3616 + 96 * context


def test_prefill_counts_the_head_once():
    # 5 tokens: body 2*(1808-80)*5, attention 96 * (5+1)/2 per token * 5, head 2*80
    assert flops.prefill_flops(TINY, 5) == 2 * 1728 * 5 + 96 * 3 * 5 + 160


def test_request_is_prefill_then_decodes():
    want = flops.prefill_flops(TINY, 6) + flops.decode_flops(TINY, 7) + flops.decode_flops(TINY, 8)
    assert flops.request_flops(TINY, 6, 3) == want
    assert flops.request_flops(TINY, 6, 1) == flops.prefill_flops(TINY, 6)


def test_train_is_three_forwards_at_mean_causal_context():
    assert flops.train_flops_per_token(TINY, 9) == 3 * (3616 + 96 * 5)


def test_bma_select_work_by_hand():
    # K=2 members, 32 slots, V=151936: read 2*32*V f32, write 32*V f32 and 32 int32
    fl, nbytes = flops.bma_select_work(2, 32, 151_936)
    assert nbytes == 4 * 2 * 32 * 151_936 + 4 * 32 * 151_936 + 4 * 32 == 58_343_552
    assert fl == 10 * 2 * 32 * 151_936
