"""Composable model configuration covering every assigned architecture.

One ``ModelConfig`` describes dense / MoE / SSM / hybrid / enc-dec stacks.
Exact full-size configs live in ``repro/configs/<arch>.py``; reduced smoke
configs are derived with ``.reduced()``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

__all__ = ["MoEConfig", "MLAConfig", "SSMConfig", "ModelConfig"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    # routing rule: "softmax_topk" (top-k logits, softmax over the chosen) or
    # "sigmoid_noaux" (DeepSeek-V3 noaux_tc: sigmoid scores, a correction
    # bias that chooses but does not weight, optional normalization, scaled)
    router: str = "softmax_topk"
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    n_shared_experts: int = 0         # always-on experts, one MLP of n x d_ff
    # (start, count) of the routed experts this device holds; None = all.
    # The router still scores all ``num_experts``; tokens routed elsewhere
    # add nothing here (expert parallelism without its exchange)
    experts_held: Optional[Tuple[int, int]] = None
    dispatch: str = "capacity"        # capacity (drops past the capacity) |
                                      # grouped (dropless sort + ragged_dot)

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    def capacity(self, seq_len: int) -> int:
        """Per-batch-row expert capacity (cumsum/positions are computed per
        row so token dispatch never serializes across the data axis)."""
        c = math.ceil(seq_len * self.top_k / self.num_experts * self.capacity_factor)
        return max(1, c)


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2/V3) without q-LoRA: keys and
    values are expanded from a normalized latent of ``kv_lora_rank`` plus one
    shared roped key head of ``qk_rope_head_dim``."""
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    head_dim: int = 64
    conv_width: int = 4
    expand: int = 2
    n_groups: int = 1
    chunk: int = 128

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    n_layers: int = 2
    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 256
    vocab_size: int = 512
    head_dim: int = 0                 # 0 => d_model // n_heads
    activation: str = "silu"          # relu | silu | gelu
    gated_mlp: bool = True
    qk_norm: bool = False
    use_bias: bool = False            # biases on mlp / attn out
    attn_qkv_bias: bool = False       # qwen2-style qkv bias
    pos_emb: str = "rope"             # rope | learned | none
    rope_theta: float = 10000.0
    norm: str = "rmsnorm"             # rmsnorm | layernorm
    norm_eps: Optional[float] = None  # None: 1e-6 (rmsnorm), 1e-5 (layernorm)
    tie_embeddings: bool = False
    max_seq_len: int = 8192           # for learned positions / cache default

    block_pattern: str = "dense"      # dense | moe | ssm | hybrid
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None   # latent attention in place of GQA
    first_k_dense: int = 0            # moe: leading dense-FFN blocks (one
    dense_d_ff: int = 0               # stack of them, FFN width dense_d_ff)
    ssm: Optional[SSMConfig] = None
    hybrid_period: int = 6            # hybrid: every Nth block = shared attn+mlp

    encoder_layers: int = 0           # >0 => encoder-decoder
    frontend: str = "none"            # none | vision | audio (stub embeddings)
    frontend_len: int = 0             # patches / frames prepended (vlm) or enc len

    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    remat: bool = False
    attn_chunk: int = 1024            # kv-chunk for blocked online-softmax attention
    vocab_pad_multiple: int = 256
    # Dry-run Δ-trick only: fully unroll layer/inner scans so XLA cost
    # analysis counts every iteration (while bodies are otherwise counted
    # once). Never set for real execution.
    unroll_layers: bool = False
    unroll_inner: bool = False
    # ---- perf-hillclimb knobs (EXPERIMENTS.md §Perf) ----
    remat_policy: str = "full"        # full | dots | none  (train remat)
    attn_softmax_dtype: str = "float32"   # float32 | bfloat16 score pipeline
    gqa_repeat_kv: bool = False       # repeat KV to q-heads pre-attention so
                                      # scores stay head-sharded under TP
    kv_cache_dtype: str = "compute"   # compute | int8 (absmax-scaled KV cache)
    use_flash_decode: bool = False    # route 1-token decode attention through
                                      # the fused Pallas kernel (TPU; interpret
                                      # mode elsewhere)

    # ----- derived -----
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // max(self.n_heads, 1)

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_multiple
        return ((self.vocab_size + m - 1) // m) * m

    @property
    def is_enc_dec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def attn_free(self) -> bool:
        return self.block_pattern == "ssm"

    def hybrid_layout(self) -> Tuple[int, int]:
        """(n_mamba_blocks, n_attn_applications) for hybrid stacks.

        Block i in [0, n_layers) is a shared attention block iff
        i % period == period - 1.
        """
        n_attn = self.n_layers // self.hybrid_period
        return self.n_layers - n_attn, n_attn

    def _attn_params(self) -> int:
        d = self.d_model
        if self.mla is not None:
            m, h = self.mla, self.n_heads
            return (d * h * m.qk_head_dim
                    + d * (m.kv_lora_rank + m.qk_rope_head_dim) + m.kv_lora_rank
                    + m.kv_lora_rank * h * (m.qk_nope_head_dim + m.v_head_dim)
                    + h * m.v_head_dim * d)
        hd = self.resolved_head_dim
        return (d * (self.n_heads * hd) + 2 * d * (self.n_kv_heads * hd)
                + (self.n_heads * hd) * d)

    def _mlp_params(self, f: int) -> int:
        d = self.d_model
        return d * f + f * d + (d * f if self.gated_mlp else 0)

    def _moe_block_params(self, experts: int) -> int:
        """One MoE block with ``experts`` routed experts counted."""
        d, m = self.d_model, self.moe
        router = d * m.num_experts + (m.num_experts if m.router == "sigmoid_noaux"
                                      else 0)
        return (self._attn_params() + experts * self._mlp_params(self.d_ff)
                + self._mlp_params(m.n_shared_experts * self.d_ff)
                + router + 2 * d)

    def _dense_lead_params(self) -> int:
        return self.first_k_dense * (self._attn_params()
                                     + self._mlp_params(self.dense_d_ff)
                                     + 2 * self.d_model)

    def param_count(self) -> int:
        """Analytic parameter count (embedding included once if tied)."""
        d, f, v = self.d_model, self.d_ff, self.padded_vocab
        attn = self._attn_params()
        mlp = self._mlp_params(f)
        per_dense = attn + mlp + 2 * d
        n = 0
        if self.block_pattern == "dense":
            n += self.n_layers * per_dense
        elif self.block_pattern == "moe":
            n += self._dense_lead_params()
            n += (self.n_layers - self.first_k_dense) * self._moe_block_params(
                self.moe.held[1])
        elif self.block_pattern == "ssm":
            n += self.n_layers * self._ssm_block_params()
        elif self.block_pattern == "hybrid":
            n_m, _ = self.hybrid_layout()
            n += n_m * self._ssm_block_params() + per_dense  # one shared attn+mlp block
        if self.is_enc_dec:
            enc_attn = 4 * d * d
            n += self.encoder_layers * (enc_attn + 2 * d * f + 2 * d)
            n += self.n_layers * (attn + 2 * d)  # cross-attention blocks
        n += v * d * (1 if self.tie_embeddings else 2)
        if self.pos_emb == "learned":
            n += self.max_seq_len * d
        return n

    def active_param_count(self) -> int:
        """Activated params per token (= param_count for non-MoE)."""
        if self.block_pattern != "moe":
            return self.param_count()
        act = self._dense_lead_params()
        act += (self.n_layers - self.first_k_dense) * self._moe_block_params(
            self.moe.top_k)
        act += self.padded_vocab * self.d_model * (1 if self.tie_embeddings else 2)
        return act

    def _ssm_block_params(self) -> int:
        d = self.d_model
        s = self.ssm
        di = s.d_inner(d)
        h = s.n_heads(d)
        g = s.n_groups
        in_proj = d * (2 * di + 2 * g * s.d_state + h)
        conv = s.conv_width * (di + 2 * g * s.d_state)
        out_proj = di * d
        return in_proj + conv + out_proj + 3 * h + di + d

    def reduced(self, **overrides) -> "ModelConfig":
        """Tiny same-family config for CPU smoke tests."""
        base = dict(
            n_layers=min(self.n_layers,
                         2 if self.block_pattern != "hybrid"
                         else self.hybrid_period + 1),
            d_model=64,
            n_heads=min(self.n_heads, 4) if self.n_heads else 0,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads else 0,
            d_ff=128 if self.d_ff else 0,
            vocab_size=512,
            head_dim=16 if self.head_dim else 0,
            max_seq_len=256,
            frontend_len=8 if self.frontend != "none" else 0,
            encoder_layers=min(self.encoder_layers, 2),
            moe=(dataclasses.replace(self.moe, num_experts=4, top_k=2,
                                     experts_held=None) if self.moe else None),
            mla=(MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                           qk_rope_head_dim=8, v_head_dim=16)
                 if self.mla else None),
            first_k_dense=min(self.first_k_dense, 1),
            dense_d_ff=256 if self.dense_d_ff else 0,
            ssm=(dataclasses.replace(self.ssm, d_state=16, head_dim=16,
                                     chunk=32) if self.ssm else None),
            remat=False,
            attn_chunk=64,
            param_dtype="float32",
            compute_dtype="float32",
        )
        base.update(overrides)
        return dataclasses.replace(self, **base)
