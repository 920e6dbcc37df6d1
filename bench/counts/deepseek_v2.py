"""Parameters and operations of a DeepSeek-V2 decoder from its published
sizes, for the layers and the share of routed experts a chip holds, and
the head.  ``n_routed_experts`` is the count held here; the router scores
``published["n_routed_experts"]``."""
from __future__ import annotations


def mla_params(c: dict) -> int:
    d, H = c["hidden_size"], c["num_attention_heads"]
    rq, r = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, v = c["qk_nope_head_dim"], c["qk_rope_head_dim"], \
        c["v_head_dim"]
    return (d * rq + rq + rq * H * (nope + rope) + d * (r + rope) + r
            + r * H * (nope + v) + H * v * d)


def _swiglu(d: int, f: int) -> int:
    return 3 * d * f


def routed_experts(c: dict) -> int:
    return c["published"]["n_routed_experts"]


def dense_layer_params(c: dict) -> int:
    d = c["hidden_size"]
    return mla_params(c) + _swiglu(d, c["intermediate_size"]) + 2 * d


def moe_layer_params(c: dict) -> int:
    """Attention, the held experts, the shared experts and the router."""
    d, fe = c["hidden_size"], c["moe_intermediate_size"]
    return (mla_params(c) + c["n_routed_experts"] * _swiglu(d, fe)
            + _swiglu(d, c["n_shared_experts"] * fe)
            + d * routed_experts(c) + 2 * d)


def held_params(c: dict) -> int:
    """Everything the chip holds: its layers, the embedding, the final norm
    and the untied head."""
    d, v = c["hidden_size"], c["vocab_size"]
    k, L = c["first_k_dense_replace"], c["num_hidden_layers"]
    emb = d * v * (1 if c["tie_word_embeddings"] else 2)
    return k * dense_layer_params(c) + (L - k) * moe_layer_params(c) + emb + d


def expert_evaluations(c: dict) -> float:
    """Routed experts held here that one token is expected to reach in one
    MoE layer: ``num_experts_per_tok`` times the held share."""
    return c["num_experts_per_tok"] * c["n_routed_experts"] / routed_experts(c)


def flops(c: dict, tokens: int, context_sum: int) -> float:
    """Forward operations for ``tokens`` tokens through the held layers and
    the head, where ``context_sum`` is the sum over those tokens of the
    positions each attends to (itself included).  MLA counts its matmuls
    once a token (absorbed or not, the same weights) and its attention
    over the latent rows; a MoE layer counts the router, the shared
    experts and :func:`expert_evaluations` routed experts."""
    d = c["hidden_size"]
    k, L = c["first_k_dense_replace"], c["num_hidden_layers"]
    fe = c["moe_intermediate_size"]
    mla = mla_params(c) - c["q_lora_rank"] - c["kv_lora_rank"]   # no norms
    dense = mla + _swiglu(d, c["intermediate_size"])
    moe = (mla + d * routed_experts(c)
           + _swiglu(d, c["n_shared_experts"] * fe)
           + expert_evaluations(c) * _swiglu(d, fe))
    per_token = 2 * (k * dense + (L - k) * moe + d * c["vocab_size"])
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    attention = 2 * L * H * (r + c["qk_rope_head_dim"] + r) * context_sum
    return per_token * tokens + attention
