"""Operations and bytes of a Qwen2 decoder (dense GQA, SwiGLU, QKV bias)
from its published sizes, for the layers a chip holds and the head."""
from __future__ import annotations


def layer_params(c: dict) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    f = c["intermediate_size"]
    matmul = d * q + 2 * d * kv + q * d + 3 * d * f
    bias = q + 2 * kv
    norms = 2 * d
    return matmul + bias + norms


def layer_matmul_params(c: dict) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d + 3 * d * c["intermediate_size"]


def weight_bytes(c: dict, bytes_per_param: int = 2) -> int:
    """Weights held on the chip: its layers, the embedding, the final norm
    and the untied head."""
    d, v = c["hidden_size"], c["vocab_size"]
    emb = d * v * (1 if c["tie_word_embeddings"] else 2)
    return (c["num_hidden_layers"] * layer_params(c) + emb + d) \
        * bytes_per_param


def flops(c: dict, tokens: int, context_sum: int) -> float:
    """Forward operations for ``tokens`` tokens through the held layers and
    the head, where ``context_sum`` is the sum over those tokens of the
    positions each attends to (itself included)."""
    L = c["num_hidden_layers"]
    q = c["num_attention_heads"] * c["head_dim"]
    per_token = 2 * (L * layer_matmul_params(c)
                     + c["hidden_size"] * c["vocab_size"])
    attention = 4 * L * q * context_sum        # QK^T and PV
    return per_token * tokens + attention


def kv_bytes_per_token(c: dict, bytes_per_elem: int = 2) -> int:
    """K and V of one token in every held layer."""
    return (2 * c["num_hidden_layers"] * c["num_key_value_heads"]
            * c["head_dim"] * bytes_per_elem)
