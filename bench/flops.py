"""Operations and bytes of the dense GQA decoder's work, from shapes.

``s`` is the size dict of :func:`bench.refs.dense_gqa.sizes`.  Weights and
KV are bfloat16 (2 bytes).  A multiply-add counts as two operations.
"""
from __future__ import annotations

BF16 = 2


def layer_matmul_params(s: dict) -> int:
    """Weights one token meets in one layer's matmuls."""
    d, dff, hq, hkv, hd = s["d"], s["dff"], s["hq"], s["hkv"], s["hd"]
    return 2 * d * hq * hd + 2 * d * hkv * hd + 3 * d * dff


def layer_bytes(s: dict) -> int:
    """Bytes of one layer's weights: matrices, biases and norm scales."""
    d, hq, hkv, hd = s["d"], s["hq"], s["hkv"], s["hd"]
    return BF16 * (layer_matmul_params(s) + hq * hd + 2 * hkv * hd + 2 * d)


def stack_bytes(s: dict) -> int:
    return s["layers"] * layer_bytes(s)


def head_flops(s: dict) -> int:
    return 2 * s["d"] * s["vocab"]


def attention_flops(s: dict, context: int) -> int:
    """One query token attending ``context`` keys (itself included), in
    every layer: scores and the weighted sum of values."""
    return 4 * s["hq"] * s["hd"] * context * s["layers"]


def prefill_flops(s: dict, prompt: int) -> int:
    """A prompt of ``prompt`` tokens, causal, with the head applied to
    the last position only (the first output token)."""
    tri = prompt * (prompt + 1) // 2
    return (2 * layer_matmul_params(s) * s["layers"] * prompt
            + 4 * s["hq"] * s["hd"] * tri * s["layers"] + head_flops(s))


def decode_flops(s: dict, context: int) -> int:
    """One decoded token whose query attends ``context`` keys."""
    return (2 * layer_matmul_params(s) * s["layers"]
            + attention_flops(s, context) + head_flops(s))


def paged_attention_call(s: dict, pooled: list[int]) -> tuple[int, int]:
    """(operations, bytes) of one paged-attention kernel call (one layer,
    one decode step) for slots whose pools hold ``pooled`` past
    positions each: the query, the pooled K and V it must read, the
    current token's K and V, and the output.  Page tables and lengths
    are a few bytes and left out."""
    hq, hkv, hd = s["hq"], s["hkv"], s["hd"]
    ops = sum(4 * hq * hd * (n + 1) for n in pooled)
    nbytes = BF16 * sum(2 * hq * hd + 2 * hkv * hd * (n + 1) for n in pooled)
    return ops, nbytes
