"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.invariance import apply_rotation_cols, apply_rotation_rows
from repro.core.quant import (QuantConfig, compute_qparams, quantize_codes,
                              dequantize_codes, unpack_codes)

__all__ = ["quant_matmul_ref", "group_quant_ref", "dequant_ref",
           "flash_attention_ref", "flash_decode_ref", "paged_decode_ref",
           "transform_quant_ref"]


def flash_attention_ref(q, k, v, *, causal: bool):
    """Dense full-sequence attention oracle with the flash kernel's
    arithmetic: q·Dh^-0.5, k, v and the probabilities rounded to bfloat16
    (the one MXU pass of default precision), products summed in float32.
    The output is not rounded to bfloat16 as ``ops.flash_attention``'s is.
    q (B, S, Hq, Dh); k/v (B, S, Hkv, Dh) -> (B, S, Hq, Dh) in q's dtype."""
    def bf16(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    B, S, Hq, Dh = q.shape
    rep = Hq // k.shape[2]
    kf = jnp.repeat(bf16(k), rep, axis=2)
    vf = jnp.repeat(bf16(v), rep, axis=2)
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bqhd,bkhd->bhqk", bf16(q.astype(jnp.float32) * Dh ** -0.5),
                   kf, precision=hi)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", bf16(p), vf, precision=hi)
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def flash_decode_ref(q, k, v, k_scale=None, v_scale=None, kv_len=None):
    """Dense one-token attention oracle. q (B,H,Dh); k/v (B,S,H,Dh)."""
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale[..., None].astype(jnp.float32)
        vf = vf * v_scale[..., None].astype(jnp.float32)
    dh = q.shape[-1]
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32), kf) * dh ** -0.5
    if kv_len is not None:
        mask = jnp.arange(k.shape[1]) < kv_len
        s = jnp.where(mask[None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bshd->bhd", p, vf)


def paged_decode_ref(q, k_pages, v_pages, block_tables, seq_lens,
                     k_scale=None, v_scale=None, normalize=True):
    """Dense paged-attention oracle: gather pages, then plain softmax.

    q (B, H, Dh); k/v_pages (N, Hkv, page_size, Dh) [+ scales
    (N, Hkv, page_size)]; block_tables (B, P) int32; seq_lens (B,) int32.
    Returns (B, H, Dh), or the (acc, m, l) log-sum-exp partials when
    ``normalize=False`` (the dist merge contract). Contractions run at
    ``Precision.HIGHEST`` so the oracle is full f32 on every backend.

    The ONE oracle for both paged-decode grids: the per-query-head kernel
    and the fused-GQA (B, Hkv, P) variant compute the same math, so
    ``paged_decode_gqa_pallas`` parity is pinned against this function
    (``repeat``-ing KV to H heads here IS the unfused read pattern the
    fused grid eliminates).
    """
    B, H, Dh = q.shape
    _, Hkv, page_size, _ = k_pages.shape
    P = block_tables.shape[1]
    kf = k_pages[block_tables].astype(jnp.float32)     # (B, P, Hkv, psz, Dh)
    vf = v_pages[block_tables].astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale[block_tables][..., None].astype(jnp.float32)
        vf = vf * v_scale[block_tables][..., None].astype(jnp.float32)
    kf = kf.transpose(0, 1, 3, 2, 4).reshape(B, P * page_size, Hkv, Dh)
    vf = vf.transpose(0, 1, 3, 2, 4).reshape(B, P * page_size, Hkv, Dh)
    if Hkv < H:  # GQA: repeat KV heads to the query head count
        kf = jnp.repeat(kf, H // Hkv, axis=2)
        vf = jnp.repeat(vf, H // Hkv, axis=2)
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32), kf,
                   precision=hi) * Dh ** -0.5
    mask = jnp.arange(P * page_size)[None, :] < seq_lens[:, None]
    s = jnp.where(mask[:, None, :], s, -1e30)
    if not normalize:
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        acc = jnp.einsum("bhs,bshd->bhd", p, vf, precision=hi)
        return acc, m, l
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bshd->bhd", p, vf, precision=hi)


def dequant_ref(packed, scale, zero, bits: int, group_size: int, k: int):
    """packed (K_pad//vpw, N) uint32 -> dense weights (K, N) f32.

    Dequantizes at the PADDED length (scale/zero rows cover K_pad when
    lcm(group, vals_per_word) padding was applied, e.g. 3-bit), then slices.
    """
    cfg = QuantConfig(bits=bits, group_size=group_size)
    k_pad = packed.shape[0] * (32 // bits)
    codes = unpack_codes(packed, bits, k_pad)
    return dequantize_codes(codes, scale, zero, cfg)[:k]


def quant_matmul_ref(x, packed, scale, zero, bits: int, group_size: int):
    """x (M, K) @ dequant(packed) -> (M, N) f32."""
    k = x.shape[1]
    w = dequant_ref(packed, scale, zero, bits, group_size, k)
    return x.astype(jnp.float32) @ w


def group_quant_ref(w, bits: int, group_size: int):
    """Fused quant->dequant roundtrip; returns (fq, scale, zero)."""
    cfg = QuantConfig(bits=bits, group_size=group_size)
    scale, zero = compute_qparams(w.astype(jnp.float32), cfg)
    codes = quantize_codes(w.astype(jnp.float32), scale, zero, cfg)
    fq = dequantize_codes(codes, scale, zero, cfg, out_dtype=w.dtype)
    return fq, scale, zero


def transform_quant_ref(w, pi, s, phi, *, bits: int, group: int, mode: str):
    """Materialize-then-quantize composition of ``apply_transform_ffn``'s
    up/down branches with the group fake-quant roundtrip — the oracle for the
    fused ``transform_quant`` kernel. Returns (fq, scale, zero)."""
    w = w.astype(jnp.float32)
    if mode == "up":        # w (D, F): rotate -> x s -> permute on columns
        t = apply_rotation_cols(w, phi) * s[None, :]
        t = t[:, pi]
    elif mode == "down":    # w (F, D): rotate -> / s -> permute on rows
        t = apply_rotation_rows(w, phi) * (1.0 / s)[:, None]
        t = t[pi, :]
    else:
        raise ValueError(f"mode must be 'up' or 'down', got {mode!r}")
    return group_quant_ref(t, bits, group)
