"""Work counts of a Llama-style decoder's serving steps, from its shapes.

A decode step over ``B`` active slots needs every weight matrix once
(the layers, the final norm and the LM head; the embedding gather reads
``B`` rows), and each active slot's keys and values over its live length
(the positions its new token attends to), plus the new token's own keys
and values written.  Operations are ``2`` per weight per token, plus
``4 * heads * head_dim`` per attended position per layer (scores and the
weighted sum).
"""

from __future__ import annotations

_ITEMSIZE = {"float32": 4, "bfloat16": 2}


def _dims(cfg: dict):
    return (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_hidden_layers"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["vocab_size"])


def layer_matmul_params(cfg: dict) -> int:
    d, ff, _, nh, nkv, hd, _ = _dims(cfg)
    return d * nh * hd + 2 * d * nkv * hd + nh * hd * d + 3 * d * ff


def decode_weight_params(cfg: dict) -> int:
    """Parameters a decode step reads: layers, norms, LM head."""
    d, _, L, _, _, _, v = _dims(cfg)
    return L * (layer_matmul_params(cfg) + 2 * d) + d + d * v


def kv_bytes_per_token(cfg: dict) -> int:
    _, _, L, _, nkv, hd, _ = _dims(cfg)
    return 2 * L * nkv * hd * _ITEMSIZE[cfg["dtype"]]


def decode_step_bytes(cfg: dict, live_lens) -> float:
    """Least HBM bytes of one decode step; ``live_lens`` holds, for each
    active slot, the positions its new token attends to (itself included)."""
    isz = _ITEMSIZE[cfg["dtype"]]
    d = cfg["hidden_size"]
    b = len(live_lens)
    kv = kv_bytes_per_token(cfg)
    return (decode_weight_params(cfg) * isz + b * d * isz
            + sum(live_lens) * kv + b * kv)


def decode_step_flops(cfg: dict, live_lens) -> float:
    d, _, L, nh, _, hd, v = _dims(cfg)
    b = len(live_lens)
    matmul = 2 * b * (L * layer_matmul_params(cfg) + d * v)
    return matmul + 4 * nh * hd * L * sum(live_lens)
