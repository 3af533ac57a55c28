"""Fused full-sequence self-attention (causal or plain), scores kept in VMEM.

The no-cache forward (training, the search's calibration forwards) attends
whole sequences at once. Left to XLA, each (S, S) score, masked-score and
probability matrix is written to HBM and read back; at 16 x 32 heads x
512 x 512 that is three f32 passes over 537 MB per block. This kernel reads
q, k and v once, keeps every score tile in VMEM, and never computes a tile
that lies wholly above the causal diagonal.

Layout: q, k, v enter as the projections produce them, (B, S, H*Dh), so no
transpose is needed on either side. One grid step takes one batch row and
one 128-lane block of heads: two heads of 64, or one head of 128 (or of any
multiple of 128). Each load and store is lane-dense. Inside the step a
static loop walks q tiles of ``block_q`` rows (256, or 128 where S is not a
multiple of 256); tile ``i`` of a causal call attends to keys
``[0, (i + 1) * block_q)`` only, in one softmax (no running rescale), with
the mask built for that range. The heads of a lane block are
separated without lane slicing: head ``g``'s scores are
``(q masked to g's lanes) @ kᵀ``, and its output is read from g's lanes of
``p_g @ v``. At Dh = 64 the MXU is half filled either way, so the masks
cost no MXU passes.

Arithmetic: the operands arrive as bfloat16 (``q`` already scaled by
Dh^-0.5), the matmuls accumulate in float32, and max, exp and sum run in
float32; ``p`` is rounded to bfloat16 for the value matmul. That is what
XLA does with the float32 reference at JAX's default matmul precision on a
TPU (one bfloat16 pass). The normalized output is computed in float32 and
written in the operands' dtype.

GQA: with one head per lane block the kv block index is ``h // rep``; at
Dh < 128 a q head's kv head would sit in other lanes, so the wrapper
(``ops.flash_attention_refusal``) refuses GQA there.

Values may be narrower than queries and keys (latent attention: q/k heads
of 192, zero-padded to 256 by the wrapper, and v heads of 128). Then each
lane block holds one head, of ``head_dim`` lanes in q and k and of
``v_head_dim`` lanes in v and the output.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

NEG = -1e30
_NT = (((1,), (1,)), ((), ()))   # contract the lane axis of both: q @ kᵀ


def _kernel(q_ref, k_ref, v_ref, o_ref, *, causal, head_dim, block_q):
    S, W = q_ref.shape
    hp = W // head_dim                                  # heads per lane block
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    in_head = [(lane >= g * head_dim) & (lane < (g + 1) * head_dim)
               for g in range(hp)]
    for i in range(S // block_q):
        rows = pl.ds(i * block_q, block_q)
        kv_end = (i + 1) * block_q if causal else S     # skip tiles above diag
        q = q_ref[rows, :]
        k = k_ref[pl.ds(0, kv_end), :]
        v = v_ref[pl.ds(0, kv_end), :]
        if causal:
            row = jax.lax.broadcasted_iota(jnp.int32, (block_q, kv_end), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (block_q, kv_end), 1)
            mask = col <= row + i * block_q
        out = None
        for g in range(hp):
            qg = q if hp == 1 else jnp.where(in_head[g], q, jnp.zeros_like(q))
            s = jax.lax.dot_general(qg, k, _NT,
                                    preferred_element_type=jnp.float32)
            if causal:
                s = jnp.where(mask, s, NEG)
            m = jnp.max(s, axis=1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=1, keepdims=True)
            o = jnp.dot(p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32)
            o = o / jnp.maximum(l, 1e-30)
            out = o if out is None else jnp.where(in_head[g], o, out)
        o_ref[rows, :] = out.astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool, head_dim: int,
                           v_head_dim: int = None, interpret: bool = False):
    """q: (B, S, Hq*Dh) bf16, pre-scaled; k: (B, S, Hkv*Dh) bf16; v:
    (B, S, Hkv*Dv) bf16, ``Dv = v_head_dim`` (default ``head_dim``).

    Returns (B, S, Hq*Dv) in q's dtype. The lane block is
    ``W = max(128, head_dim)``; the caller guarantees that ``W`` divides both
    feature axes, that S is a multiple of 16 and of ``min(128, S)``, and
    (when Hkv < Hq) that ``W == head_dim``; where Dv differs from Dh, that
    both are multiples of 128. The q tile is 256 rows where S allows it:
    each MXU weight load then serves twice the rows, which beats the extra
    masked work (0.485 against 0.541 ms at 16 x 32 x 512 x 64 on a v5e).
    """
    B, S, Fq = q.shape
    Fk = k.shape[2]
    W = max(128, head_dim)
    Wv = W if v_head_dim is None else max(128, v_head_dim)
    rep = Fq // Fk                                      # GQA group size
    block_q = 256 if S % 256 == 0 else min(128, S)

    def q_map(b, h):
        return (b, 0, h)

    def kv_map(b, h):
        return (b, 0, h // rep)

    return pl.pallas_call(
        functools.partial(_kernel, causal=causal, head_dim=head_dim,
                          block_q=block_q),
        grid=(B, Fq // W),
        in_specs=[pl.BlockSpec((None, S, W), q_map),
                  pl.BlockSpec((None, S, W), kv_map),
                  pl.BlockSpec((None, S, Wv), kv_map)],
        out_specs=pl.BlockSpec((None, S, Wv), q_map),
        out_shape=jax.ShapeDtypeStruct((B, S, Fq // W * Wv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
