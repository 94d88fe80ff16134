"""The operation and byte counters against hand counts at smoke size."""

import flops

# L=2, D=128, N=4, K=2, Dh=32, F=256, V=515
MODEL = {"num_hidden_layers": 2, "hidden_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 256,
         "vocab_size": 515}


def test_layer_params():
    # q 128*128 + k,v 2*128*64 + o 128*128 + mlp 3*128*256
    assert flops.layer_matmul_params(MODEL) == 16384 + 16384 + 16384 + 98304


def test_train_step():
    # forward over 4 x 32 tokens: 2 * 147456 * 128 per layer, attention
    # 4 * 4 * 32 * (4 * 528) per layer, head 2 * 128 * 515 * 128
    fwd = 2 * (2 * 147456 * 128 + 4 * 4 * 32 * 4 * 528) + 2 * 128 * 515 * 128
    assert flops.train_step_flops(MODEL, 4, 32) == 3 * fwd


def test_prefill_and_decode():
    prefill = 2 * (2 * 147456 * 64 + 512 * 2 * 528) + 2 * 128 * 515 * 2
    assert flops.prefill_flops(MODEL, 2, 32) == prefill
    decode = 2 * (2 * 147456 * 2 + 512 * 2 * 40) + 2 * 128 * 515 * 2
    assert flops.decode_flops(MODEL, 2, 40) == decode


def test_decode_bytes():
    weights = 2 * (147456 + 256) + 128 * 515 + 128
    cache = 2 * 2 * 2 * 40 * 2 * 32          # k and v, L, B, held, K, Dh
    assert flops.decode_bytes(MODEL, 2, 40) == 2 * (weights + 2 * 128) + 2 * cache


def test_causal_pairs():
    assert flops.causal_pairs(1) == 1 and flops.causal_pairs(4) == 10
