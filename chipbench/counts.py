"""Operations and bytes that the measured work needs, computed from shapes.

These are the yardstick's own counts: model operations (a multiply and an add
are two), not what a kernel happens to execute, so work a program wastes
(padding, masked-out attention) never raises a utilization.
"""
from __future__ import annotations

from typing import Iterable

BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def matmul_params(m: dict) -> int:
    """Weights multiplied per token: q, k, v, o and the FFN in every layer,
    plus the LM head."""
    L, D, F = m["num_hidden_layers"], m["hidden_size"], m["ffn_dim"]
    return L * (4 * D * D + 2 * D * F) + D * m["vocab_size"]


def forward_flops(m: dict, batch: int, seq: int) -> int:
    """One causal forward over ``batch`` sequences of ``seq`` tokens: the
    matmuls, plus attention scores and values over the causal half
    (seq (seq + 1) / 2 query-key pairs per head)."""
    L, D = m["num_hidden_layers"], m["hidden_size"]
    dense = 2 * matmul_params(m) * batch * seq
    pairs = seq * (seq + 1) // 2
    attn = L * batch * 2 * 2 * D * pairs       # QK^T and PV, 2 flops a MAC
    return dense + attn


def decode_step_flops(m: dict, context_lens: Iterable[int]) -> int:
    """One decode step: one new token for each live sequence, attending over
    its ``context_len`` tokens (its own included)."""
    lens = list(context_lens)
    L, D = m["num_hidden_layers"], m["hidden_size"]
    return 2 * matmul_params(m) * len(lens) + L * 2 * 2 * D * sum(lens)


def paged_decode_bytes(m: dict, context_lens: Iterable[int], page_size: int
                       ) -> int:
    """Logical HBM bytes of one decode step's paged-attention calls, over all
    layers: each live sequence's live pages of K and V at the configuration's
    dtype (no lane padding), plus the queries read and the float32 outputs
    written."""
    lens = list(context_lens)
    L, D = m["num_hidden_layers"], m["hidden_size"]
    kv_heads = m.get("num_key_value_heads", m["num_attention_heads"])
    dh = D // m["num_attention_heads"]
    pages = sum(-(-n // page_size) for n in lens)
    item = BYTES[m["torch_dtype"]]
    kv = pages * page_size * kv_heads * dh * item * 2
    qo = len(lens) * D * (item + 4)
    return L * (kv + qo)
