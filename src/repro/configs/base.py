"""Configuration dataclasses for the repro framework.

Every assigned architecture is expressed as a :class:`ModelConfig`; every
assigned input shape as a :class:`ShapeConfig`.  Configs are frozen
dataclasses so they hash and can key compilation caches.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------

FAMILIES = ("dense", "moe", "ssm", "hybrid", "encdec", "vlm")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture hyper-parameters.

    Field groups further down only apply to the family named in the comment;
    they default to inert values for other families.
    """

    name: str
    family: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int

    # -- attention ----------------------------------------------------------
    head_dim: Optional[int] = None          # default: d_model // num_heads
    qkv_bias: bool = False
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-6
    tie_embeddings: bool = False

    # -- moe -----------------------------------------------------------------
    num_experts: int = 0
    experts_per_token: int = 0
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    # DeepSeekMoE: routing groups (experts are scored, the best
    # ``topk_group`` of ``n_group`` groups kept, then the top
    # ``experts_per_token`` of those), the routed weights' scale and
    # normalisation, always-on shared experts, the expert width when it is
    # not ``d_ff``, and the leading layers that keep a dense MLP
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    n_shared_experts: int = 0
    moe_d_ff: int = 0                       # default: d_ff
    first_k_dense: int = 0
    # the share of the routed experts this device holds: experts
    # ``first_expert .. first_expert + experts_held - 1`` (0: all of them).
    # The router still scores all ``num_experts``; the layer returns the part
    # of the output that its own experts give (expert parallelism, one shard)
    experts_held: int = 0
    first_expert: int = 0

    # -- latent attention (MLA, DeepSeek-V2) ------------------------------------
    # queries and keys/values pass through low-rank latents; the decode cache
    # holds one ``kv_lora_rank + qk_rope_head_dim`` row per token and layer
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN rope scaling (factor 0: plain rope)
    yarn_factor: float = 0.0
    yarn_original_max_pos: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    # -- ssm (mamba2 / SSD) --------------------------------------------------
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    conv_width: int = 4
    ssd_chunk: int = 256

    # -- hybrid (RG-LRU + local attention) ------------------------------------
    lru_width: int = 0
    local_window: int = 0
    # pattern of one block group, e.g. ("rec", "rec", "attn"); repeated over depth
    block_pattern: Tuple[str, ...] = ()

    # -- encoder/decoder ------------------------------------------------------
    encoder_layers: int = 0
    decoder_layers: int = 0

    # -- vlm (M-RoPE) ----------------------------------------------------------
    mrope_sections: Tuple[int, ...] = ()

    # -- frontend stubs --------------------------------------------------------
    # When True, ``input_specs`` provides precomputed frame/patch embeddings for
    # the (audio/vision) frontend instead of token ids (backbone-only mandate).
    embeds_input: bool = False

    # -- numerics ---------------------------------------------------------------
    dtype: str = "bfloat16"        # activation/compute dtype
    param_dtype: str = "bfloat16"  # stored parameter dtype

    # -- sharding overrides (hillclimbing hooks) --------------------------------
    # attention TP strategy: "head" (shard q+kv heads), "kv_repl" (shard q heads,
    # replicate kv), "uneven" (shard both, GSPMD pads), "seq" (shard q sequence).
    attn_shard: str = "auto"
    # q-head padding: attention heads are zero-padded (with masked outputs,
    # mathematically exact — see models/attention.py) up to a multiple of
    # this so head-TP shards evenly on the 16-way model axis (40 q heads on
    # 16 devices would otherwise replicate attention entirely)
    head_pad_multiple: int = 16
    # remat policy: "full" (recompute everything; the 16 GB/chip HBM
    # budget at 4k x 256 batch demands it), "dots", "none"
    remat: str = "full"

    # -------------------------------------------------------------------------
    def __post_init__(self):
        if self.family not in FAMILIES:
            raise ValueError(f"unknown family {self.family!r}")

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim is not None:
            return self.head_dim
        return self.d_model // max(self.num_heads, 1)

    @property
    def padded_heads(self) -> int:
        m = max(self.head_pad_multiple, 1)
        return ((self.num_heads + m - 1) // m) * m

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to a 256 multiple so the vocab dim shards over
        any reasonable TP degree (pad logits are masked in the loss)."""
        return ((self.vocab_size + 255) // 256) * 256

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.resolved_head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.resolved_head_dim

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def latent_width(self) -> int:
        """Width of one MLA cache row: the normed KV latent and the shared
        rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def held_experts(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic archs run the long_500k shape; full-attention skip it."""
        return self.family in ("ssm", "hybrid")

    @property
    def d_inner(self) -> int:
        """SSM inner width."""
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim if self.ssm_state else 0

    # -- parameter counting ----------------------------------------------------
    def _attn_params(self) -> int:
        if self.is_mla:
            H, qk = self.num_heads, self.qk_nope_head_dim + self.qk_rope_head_dim
            p = self.d_model * self.q_lora_rank + self.q_lora_rank     # q_a, norm
            p += self.q_lora_rank * H * qk                              # q_b
            p += self.d_model * self.latent_width + self.kv_lora_rank  # kv_a, norm
            p += self.kv_lora_rank * H * (self.qk_nope_head_dim + self.v_head_dim)
            p += H * self.v_head_dim * self.d_model                     # out proj
            return p
        hd = self.resolved_head_dim
        p = self.d_model * (self.q_dim + 2 * self.kv_dim)          # qkv
        p += self.q_dim * self.d_model                              # out proj
        if self.qkv_bias:
            p += self.q_dim + 2 * self.kv_dim
        return p

    def _mlp_params(self, d_ff: int) -> int:
        # SwiGLU: gate + up + down
        return 3 * self.d_model * d_ff

    def _ssm_params(self) -> int:
        di, ns, nh = self.d_inner, self.ssm_state, self.ssm_heads
        p = self.d_model * (2 * di + 2 * ns + nh)   # in_proj (z,x,B,C,dt)
        p += self.conv_width * (di + 2 * ns)          # conv over x,B,C
        p += nh * 2                                    # A_log, D
        p += di * self.d_model                         # out proj
        p += di                                        # gate norm
        return p

    def _rglru_params(self) -> int:
        w = self.lru_width
        p = self.d_model * 2 * w                       # in proj (x, gate branch)
        p += self.conv_width * w                       # temporal conv
        # RG-LRU gates: input gate + recurrence gate (diagonal) + a_param
        p += 2 * w + w
        p += w * self.d_model                          # out proj
        return p

    def num_params(self) -> int:
        """Total parameter count N (embedding included once, lm head extra
        unless tied)."""
        d, L = self.d_model, self.num_layers
        emb = self.vocab_size * d
        head = 0 if self.tie_embeddings else self.vocab_size * d
        if self.embeds_input:
            pass  # frontend stubbed; token path kept for decoder text side
        per_layer = 0
        if self.family == "ssm":
            per_layer = self._ssm_params() + d          # + norm
            return emb + head + L * per_layer
        if self.family == "hybrid":
            pat = self.block_pattern or ("rec", "rec", "attn")
            groups, rem = divmod(L, len(pat))
            counts = {k: groups * pat.count(k) for k in ("rec", "attn")}
            for k in pat[:rem]:
                counts[k] += 1
            total = counts["rec"] * (self._rglru_params() + self._mlp_params(self.d_ff) + 2 * d)
            total += counts["attn"] * (self._attn_params() + self._mlp_params(self.d_ff) + 2 * d)
            return emb + head + total
        if self.family == "encdec":
            enc = self.encoder_layers * (self._attn_params() + self._mlp_params(self.d_ff) + 2 * d)
            dec = self.decoder_layers * (2 * self._attn_params() + self._mlp_params(self.d_ff) + 3 * d)
            return emb + head + enc + dec
        # dense / moe / vlm share a decoder-only skeleton
        attn = self._attn_params()
        dense = attn + self._mlp_params(self.d_ff) + 2 * d
        if not self.is_moe:
            return emb + head + L * dense
        moe = attn + self.held_experts * self._mlp_params(self.expert_d_ff) \
            + self._shared_params() + d * self.num_experts + 2 * d
        k = self.first_k_dense
        return emb + head + k * dense + (L - k) * moe

    def _shared_params(self) -> int:
        return self._mlp_params(self.n_shared_experts * self.expert_d_ff)

    def active_params(self) -> int:
        """Parameters touched per token (MoE: only routed experts)."""
        if not self.is_moe:
            return self.num_params()
        d, L = self.d_model, self.num_layers
        emb = self.vocab_size * d
        head = 0 if self.tie_embeddings else self.vocab_size * d
        attn = self._attn_params()
        dense = attn + self._mlp_params(self.d_ff) + 2 * d
        mlp = self.experts_per_token * self._mlp_params(self.expert_d_ff) \
            + self._shared_params() + d * self.num_experts
        k = self.first_k_dense
        return emb + head + k * dense + (L - k) * (attn + mlp + 2 * d)

    # -- smoke-test reduction ----------------------------------------------------
    def reduced(self) -> "ModelConfig":
        """A tiny config of the same family for CPU smoke tests."""
        kv = max(1, min(self.num_kv_heads, 2))
        heads = max(kv, min(self.num_heads, 4))
        updates = dict(
            name=self.name + "-smoke",
            head_pad_multiple=1,
            num_layers=min(self.num_layers, 2),
            d_model=64,
            num_heads=heads,
            num_kv_heads=kv,
            head_dim=16,
            d_ff=128,
            vocab_size=256,
            dtype="float32",
            param_dtype="float32",
        )
        if self.is_moe:
            updates.update(num_experts=4, experts_per_token=2)
        if self.n_group > 1:
            # 8 experts in 4 groups, the best 2 groups, top-2 of them
            updates.update(num_experts=8, n_group=4, topk_group=2,
                           experts_held=0, first_expert=0)
        if self.moe_d_ff:
            updates.update(moe_d_ff=32)
        if self.first_k_dense:
            updates.update(num_layers=3, first_k_dense=1)
        if self.is_mla:
            updates.update(q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
                           qk_rope_head_dim=8, v_head_dim=16, num_kv_heads=heads)
        if self.family == "ssm":
            updates.update(ssm_state=16, ssm_head_dim=16, ssd_chunk=8)
        if self.family == "hybrid":
            updates.update(lru_width=64, local_window=16, num_layers=3)
        if self.family == "encdec":
            updates.update(encoder_layers=1, decoder_layers=1)
        if self.family == "vlm":
            updates.update(mrope_sections=(4, 6, 6))
        return dataclasses.replace(self, **updates)


# ---------------------------------------------------------------------------
# Input shapes
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned (seq_len x global_batch) input-shape cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode" | "long_decode"

    @property
    def is_decode(self) -> bool:
        return self.kind in ("decode", "long_decode")

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch

    def reduced(self) -> "ShapeConfig":
        return dataclasses.replace(
            self, name=self.name + "-smoke", seq_len=min(self.seq_len, 32),
            global_batch=min(self.global_batch, 2))


def applicable(model: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether this (arch x shape) cell is runnable; returns (ok, reason)."""
    if shape.kind == "long_decode" and not model.supports_long_context:
        return False, "long_500k skipped: pure full-attention arch (quadratic)"
    return True, ""
