"""Fused group quant->dequant roundtrip Pallas TPU kernel.

This is the discrete search's inner primitive (Algorithm 1 evaluates
``fake_quant(T(θ))`` per proposal). Naively it is 4 HBM passes
(min/max reduce, scale/zero, round, dequant); fused it is ONE VMEM pass:
each (bg·G × bn) tile computes its group min/max with a lane-local VPU
reduction (groups are contiguous along the K axis and never straddle tiles),
derives scale/zero, rounds, clips and dequantizes in-register.

Outputs the roundtripped weights plus the per-group scale/zero (the packing
path reuses them without a second pass).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["group_quant_pallas"]


def _kernel(w_ref, fq_ref, scale_ref, zero_ref, *, bits, group, bg):
    q_max = float((1 << bits) - 1)
    w = w_ref[...].astype(jnp.float32)            # (bg*G, bn)
    bn = w.shape[1]
    wg = w.reshape(bg, group, bn)
    wmax = jnp.max(wg, axis=1)                    # (bg, bn)
    wmin = jnp.min(wg, axis=1)
    scale = jnp.maximum((wmax - wmin) / q_max, 1e-8)
    zero = jnp.clip(jnp.round(-wmin / scale), 0.0, q_max)
    q = jnp.clip(jnp.round(wg / scale[:, None]) + zero[:, None], 0.0, q_max)
    fq = (q - zero[:, None]) * scale[:, None]
    fq_ref[...] = fq.reshape(bg * group, bn).astype(fq_ref.dtype)
    scale_ref[...] = scale
    zero_ref[...] = zero


def group_quant_pallas(w, *, bits: int, group: int, bg: int = 4, bn: int = 256,
                       interpret: bool = False):
    """w: (K, N) -> (fq (K, N), scale (K//G, N), zero (K//G, N)).

    Tile = (bg·G, bn): bg groups per tile so the VMEM working set stays
    small while rows remain group-aligned.
    """
    K, N = w.shape
    n_groups = K // group
    bg = min(bg, n_groups)
    bn = min(bn, N)
    assert K % group == 0 and n_groups % bg == 0 and N % bn == 0
    grid = (n_groups // bg, N // bn)
    return pl.pallas_call(
        functools.partial(_kernel, bits=bits, group=group, bg=bg),
        grid=grid,
        in_specs=[pl.BlockSpec((bg * group, bn), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((bg * group, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bg, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bg, bn), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, N), w.dtype),
            jax.ShapeDtypeStruct((n_groups, N), jnp.float32),
            jax.ShapeDtypeStruct((n_groups, N), jnp.float32),
        ],
        interpret=interpret,
        name="group_quant",
    )(w)
