"""Operations and bytes a dense GQA decoder needs, from its shapes alone.

These count what the algorithm needs, never what the code happens to do:
no padded rows, no positions past a request's real context, no idle slot,
the head only where a token is chosen. A program that does less than its
padding can therefore move a share up, and none can push it past what the
chip did.

A multiply-add is two operations. Weights are bfloat16 (2 bytes), as the
configurations state.
"""
from __future__ import annotations

BYTES = 2


def _m(model: dict):
    d, L = int(model["d_model"]), int(model["num_layers"])
    H, KV = int(model["num_heads"]), int(model["num_kv_heads"])
    hd, ff = int(model["head_dim"]), int(model["d_ff"])
    return d, L, H, KV, hd, ff, int(model["vocab_size"])


def layer_weights(model: dict) -> int:
    """Weight-matrix elements of one layer (q, k, v, o and the gated MLP)."""
    d, _, H, KV, hd, ff, _ = _m(model)
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff


def attention_flops(model: dict, context: float) -> float:
    """Scores and the weighted sum for one query over ``context`` keys,
    summed over layers."""
    _, L, H, _, hd, _, _ = _m(model)
    return 4.0 * L * H * hd * context


def head_flops(model: dict) -> float:
    d, *_, vocab = _m(model)
    return 2.0 * d * vocab


def token_flops(model: dict, context: float) -> float:
    """One token through every layer, attending ``context`` positions
    (itself included); without the head."""
    _, L, *_ = _m(model)
    return 2.0 * L * layer_weights(model) + attention_flops(model, context)


def prefill_flops(model: dict, prompt: int) -> float:
    """A whole prompt, causal, and the head at its last position."""
    return (prompt * token_flops(model, 0.0)
            + attention_flops(model, prompt * (prompt + 1) / 2.0)
            + head_flops(model))


def decode_flops(model: dict, context: float) -> float:
    """One generated token at ``context`` positions, with its head."""
    return token_flops(model, context) + head_flops(model)


def param_bytes(model: dict) -> int:
    """Every weight one decode step reads: the layers, their norms and
    biases, the final norm and the (tied) embedding as the head."""
    d, L, H, KV, hd, _, vocab = _m(model)
    per_layer = layer_weights(model) + 2 * d
    if model.get("qkv_bias"):
        per_layer += (H + 2 * KV) * hd
    return BYTES * (L * per_layer + d + vocab * d)


def kv_bytes_per_position(model: dict) -> int:
    """Keys and values of one cached position, over all layers."""
    _, L, _, KV, hd, _, _ = _m(model)
    return BYTES * 2 * L * KV * hd
