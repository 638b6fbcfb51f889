"""Operation and byte counts against hand counts at a small shape, and
the table of peaks."""
import pytest

from bench import flops, peaks

# d 8, d_ff 16, 2 query heads and 1 KV head of 4, 3 layers, vocab 32
S = {"d": 8, "dff": 16, "hq": 2, "hkv": 1, "hd": 4, "layers": 3,
     "vocab": 32}


def test_layer_counts_by_hand():
    # wq 8x8 + wo 8x8 + wk 8x4 + wv 8x4 + wi, wg 8x16 + wo 16x8
    assert flops.layer_matmul_params(S) == 64 + 64 + 32 + 32 + 3 * 128
    # + bq 8, bk 4, bv 4, ln1 8, ln2 8 -> 608 values of 2 bytes
    assert flops.layer_bytes(S) == 2 * 608
    assert flops.stack_bytes(S) == 3 * 1216


def test_prefill_and_decode_by_hand():
    # prompt of 3: matmuls 2*576*3 layers*3 tokens = 10368; attention
    # 4*2*4*(1+2+3) per layer = 192, x3 layers = 576; head 2*8*32 = 512
    assert flops.prefill_flops(S, 3) == 10368 + 576 + 512
    # a token attending 5 keys: 2*576*3 + 4*2*4*5*3 + 512
    assert flops.decode_flops(S, 5) == 3456 + 480 + 512


def test_paged_attention_call_by_hand():
    ops, nbytes = flops.paged_attention_call(S, [2, 0])
    # scores + weighted sum: 4 * hq * hd * keys, keys = pooled + 1
    assert ops == 4 * 2 * 4 * 3 + 4 * 2 * 4 * 1
    # per slot: q and out 2*8 values; K and V of (pooled + 1) keys 2*4
    assert nbytes == 2 * ((16 + 8 * 3) + (16 + 8 * 1))


def test_v5e_peaks_and_unknown_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
