"""Fused single-token decode attention over a (possibly int8) KV cache.

The §Perf hillclimb on yi-6b × decode_32k showed the decode memory term is
dominated by score/correction tensors and cache reads; this kernel is the
structural fix on real TPUs: stream the cache HBM→VMEM chunk by chunk,
dequantize int8 codes in-register, and keep the online-softmax state
(m, l, acc) entirely in VMEM across the sequence grid axis — zero HBM
traffic beyond the cache itself and the (B, H, Dh) output.

    out[b,h] = softmax(q[b,h]·K[b,:,h]ᵀ / sqrt(Dh)) · V[b,:,h]
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["flash_decode_pallas"]

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_ref, l_ref, *,
            chunk, kv_len, quantized):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0, :].astype(jnp.float32)                  # (Dh,)
    kb = k_ref[0, :, 0, :].astype(jnp.float32)              # (chunk, Dh)
    vb = v_ref[0, :, 0, :].astype(jnp.float32)
    if quantized:
        kb = kb * ks_ref[0, :, 0][:, None].astype(jnp.float32)
        vb = vb * vs_ref[0, :, 0][:, None].astype(jnp.float32)

    dh = q.shape[0]
    s = (kb @ q) * (dh ** -0.5)                             # (chunk,)
    pos = c * chunk + jax.lax.iota(jnp.int32, chunk)
    mask = pos < kv_len
    s = jnp.where(mask, s, NEG)

    m_prev = m_ref[0, 0]
    l_prev = l_ref[0, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)            # (chunk,)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p)
    o_ref[0, 0, :] = o_ref[0, 0, :] * corr + p @ vb
    m_ref[0, 0] = m_new
    l_ref[0, 0] = l_new

    # final normalization on the last chunk
    @pl.when(c == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0, 0, :] = o_ref[0, 0, :] / jnp.maximum(l_ref[0, 0], 1e-30)


def flash_decode_pallas(q, k, v, k_scale=None, v_scale=None, *, kv_len=None,
                        chunk: int = 512, interpret: bool = False):
    """q: (B, H, Dh); k/v: (B, S, H, Dh) bf16/f32 or int8 (+ scales (B, S, H)).

    Returns (B, H, Dh) f32. GQA callers repeat KV heads first (cheap in VMEM).
    """
    B, H, Dh = q.shape
    S = k.shape[1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    quantized = k_scale is not None
    if not quantized:  # uniform arity for the kernel
        k_scale = jnp.ones((B, S, H), jnp.float32)
        v_scale = jnp.ones((B, S, H), jnp.float32)
    if kv_len is None:
        kv_len = S
    grid = (B, H, S // chunk)
    out, m, l = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, kv_len=kv_len,
                          quantized=quantized),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Dh), lambda b, h, c: (b, h, 0)),
            pl.BlockSpec((1, chunk, 1, Dh), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1, Dh), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Dh), lambda b, h, c: (b, h, 0)),
            pl.BlockSpec((1, 1), lambda b, h, c: (b, h)),
            pl.BlockSpec((1, 1), lambda b, h, c: (b, h)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Dh), jnp.float32),
            jax.ShapeDtypeStruct((B, H), jnp.float32),
            jax.ShapeDtypeStruct((B, H), jnp.float32),
        ],
        interpret=interpret,
        name="flash_decode",
    )(q, k, v, k_scale, v_scale)
    return out
