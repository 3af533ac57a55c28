"""Model operations of a DeepSeek-V3 stack's calibration forward (Moonlight),
computed from shapes and from the routing of the batch (the yardstick's own
counts; ``counts.py`` does the same for OPT).

A multiply and an add are two operations. The routed experts count the token
slots that the reference's float32 routing of the calibration batch sends to
the experts held here, so work the grouped dispatch wastes on padding rows
never raises a utilization.
"""
from __future__ import annotations


def token_matmul_params(m: dict) -> int:
    """Weights every token multiplies by, over all layers: MLA's four
    projections, the dense FFN, the router and shared experts of each MoE
    layer, and the LM head (the routed experts are counted per slot)."""
    D, H = m["hidden_size"], m["num_attention_heads"]
    R, rope = m["kv_lora_rank"], m["qk_rope_head_dim"]
    qk = m["qk_nope_head_dim"] + rope
    L, n_lead = m["num_hidden_layers"], m["first_k_dense_replace"]
    attn = (D * H * qk + D * (R + rope)
            + R * H * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + H * m["v_head_dim"] * D)
    shared = 3 * D * m["n_shared_experts"] * m["moe_intermediate_size"]
    moe = D * m["router_experts"] + shared
    return (L * attn + n_lead * 3 * D * m["intermediate_size"]
            + (L - n_lead) * moe + D * m["vocab_size"])


def forward_flops(m: dict, batch: int, seq: int, held_slots: int) -> int:
    """One causal forward over ``batch`` sequences of ``seq`` tokens, where
    ``held_slots`` token slots (summed over the MoE layers) go to the
    experts held here: the matmuls, plus attention scores (q/k width
    nope + rope) and values over the causal half."""
    D = m["hidden_size"]
    H, L = m["num_attention_heads"], m["num_hidden_layers"]
    dense = 2 * token_matmul_params(m) * batch * seq
    routed = 2 * held_slots * 3 * D * m["moe_intermediate_size"]
    pairs = seq * (seq + 1) // 2
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    attn = L * batch * H * 2 * (qk + m["v_head_dim"]) * pairs
    return dense + routed + attn


def expected_held_slots(m: dict, batch: int, seq: int) -> float:
    """Slots the held experts get under uniform routing: every token sends
    ``num_experts_per_tok`` of ``router_experts`` slots, each MoE layer."""
    return ((m["num_hidden_layers"] - m["first_k_dense_replace"]) * batch * seq
            * m["num_experts_per_tok"] * m["n_routed_experts"]
            / m["router_experts"])
