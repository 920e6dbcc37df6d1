"""Tiny configuration of the ``serve-batch-dsv2`` cell for CPU tests: the
cell's system module, generators and reference at sizes a test run can
hold, with every mechanism kept (MLA with YaRN, a leading dense layer,
group-limited routing over more experts than are held, shared experts).

Served in float32 by default: at these widths (16 experts, hidden 64) a
routing near-tie that bfloat16 activations decide the other way moves a
logit by up to about 1, so the served-token gap of a bfloat16 run says
more about the tiny widths than about the path under test."""


def deepseek_v2_config(dtype="float32", held=4, first=4):
    return {"hidden_size": 64, "intermediate_size": 128,
            "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
            "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
            "moe_intermediate_size": 32, "n_routed_experts": held,
            "n_shared_experts": 2, "n_group": 4, "topk_group": 2,
            "num_experts_per_tok": 3, "num_hidden_layers": 3,
            "first_k_dense_replace": 1, "vocab_size": 512,
            "published": {"num_hidden_layers": 3, "n_routed_experts": 16},
            "program": {"arch": "deepseek-v2", "num_layers": 3, "d_model": 64,
                        "num_heads": 4, "num_kv_heads": 4, "head_dim": 16,
                        "d_ff": 128, "vocab_size": 512, "q_lora_rank": 32,
                        "kv_lora_rank": 32, "qk_nope_head_dim": 16,
                        "qk_rope_head_dim": 8, "v_head_dim": 16,
                        "moe_d_ff": 32, "num_experts": 16, "n_group": 4,
                        "topk_group": 2, "experts_per_token": 3,
                        "experts_held": held, "first_expert": first,
                        "dtype": dtype, "param_dtype": dtype},
            "engine": {"batch_slots": 4, "max_len": 128, "page_size": 16}}
