"""The operation and byte counts the benchmark divides by, against sums
worked out by hand."""
import benchpath  # noqa: F401
from work import dense_gqa, paged_decode_attention as pda

SMOL = {"hidden_size": 2048, "intermediate_size": 8192,
        "num_hidden_layers": 24, "num_attention_heads": 32,
        "num_key_value_heads": 32, "head_dim": 64, "vocab_size": 49152}
GRANITE = {"hidden_size": 4096, "intermediate_size": 12800,
           "num_hidden_layers": 10, "num_attention_heads": 32,
           "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 49155}


def test_weights_per_layer_by_hand():
    # q, k, v, o: 4 * 2048 * 2048; gate, up, down: 3 * 2048 * 8192
    assert dense_gqa.matmul_weights_per_layer(SMOL) == \
        4 * 2048 * 2048 + 3 * 2048 * 8192 == 67_108_864
    # q and o 4096 x 4096 each, k and v 4096 x 1024 each, MLP 3 x 4096 x 12800
    assert dense_gqa.matmul_weights_per_layer(GRANITE) == \
        2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 12800


def test_decode_token_flops_by_hand():
    n_valid = 300
    by_hand = (24 * (2 * 67_108_864 + 4 * 32 * 64 * 300)
               + 2 * 2048 * 49152)
    assert dense_gqa.decode_flops(SMOL, n_valid) == by_hand


def test_prefill_flops_counts_each_position_once():
    # a 3-token tail after 64 resident tokens attends to 65 + 66 + 67
    attended = 65 + 66 + 67
    by_hand = (24 * (2 * 67_108_864 * 3 + 4 * 32 * 64 * attended)
               + 2 * 2048 * 49152)
    assert dense_gqa.prefill_flops(SMOL, 3, 64) == by_hand
    # and a whole prompt equals its tokens decoded one by one, less the
    # logits of every position but the last
    whole = dense_gqa.prefill_flops(SMOL, 5, 0)
    one_by_one = sum(dense_gqa.token_flops(SMOL, p + 1, False)
                     for p in range(5)) + 2 * 2048 * 49152
    assert whole == one_by_one


def test_paged_decode_call_work_by_hand():
    # two rows with 100 and 300 valid tokens, smollm2 widths, bf16
    flops, nbytes = pda.call_work([100, 300], 32, 32, 64)
    assert flops == 4 * 32 * 64 * 400
    q_out = 2 * (2 * 32 * 64 * 2)                 # Q in and out, 2 rows
    kv = 2 * 400 * 32 * 64 * 2                    # K and V of 400 tokens
    assert nbytes == q_out + kv
    # grouped heads read a quarter of the KV bytes for the same FLOPs
    flops_g, bytes_g = pda.call_work([100, 300], 32, 8, 64)
    assert flops_g == flops and bytes_g == q_out + kv // 4


def test_roofline_picks_the_binding_peak():
    t, bound = pda.roofline_seconds(197e12, 819e9, 197e12, 819e9)
    assert (t, bound) == (1.0, "compute")
    t, bound = pda.roofline_seconds(1e9, 819e9, 197e12, 819e9)
    assert (t, bound) == (1.0, "memory")
