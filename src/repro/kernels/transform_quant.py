"""Fused invariant-transform + group fake-quant Pallas TPU kernel.

The search's inner primitive is ``fake_quant(T(θ))``: apply the candidate
(π, s, φ) transform to a unit's FFN weights, then group-quantize. Unfused
that is two full HBM round trips per proposal — materialize the transformed
fp32 weights, then re-read them to quantize. Fused it is ONE pass: each
weight strip is DMA'd to VMEM once, rotated (block-diagonal Givens pairs),
scaled, permuted and group-fake-quantized in-register, and only the
roundtripped weights (plus per-group scale/zero, reusable by the packing
path) go back to HBM.

Two layouts, matching ``core.invariance.apply_transform_ffn``:

- ``mode="up"``   — w (D, F): transform acts on the F *columns* (rotate →
  ×s → permute), quant groups run along the D rows. Tile = (bg·G, F): a full
  F strip so the arbitrary column permutation resolves inside VMEM.
- ``mode="down"`` — w (F, D): transform acts on the F *rows* (rotate → ÷s →
  permute), quant groups run along the F rows — here the permutation
  reshuffles the group axis itself (group membership changes), which is why
  transform and quant cannot be split into independent passes. Tile =
  (F, bn): a full F strip per column block.

The permutation is an arbitrary gather, so the transformed (F) axis must be
VMEM-resident per tile; the wrapper in ``ops.py`` falls back to the jnp
reference when the strip would not fit. ``kernels/ref.py`` carries the
oracle (``transform_quant_ref``); interpret-mode parity is pinned in
``tests/test_kernels.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["transform_quant_pallas"]


def _rotate_scale_cols(w, phi, s):
    """(rows, F) -> rotated (pairs (2i, 2i+1) of columns) and scaled."""
    rows, f = w.shape
    wp = w.reshape(rows, f // 2, 2)
    c, sn = jnp.cos(phi), jnp.sin(phi)
    a, b = wp[:, :, 0], wp[:, :, 1]
    ra = c[None, :] * a - sn[None, :] * b
    rb = sn[None, :] * a + c[None, :] * b
    return jnp.stack([ra, rb], axis=2).reshape(rows, f) * s[None, :]


def _rotate_scale_rows(w, phi, s_inv):
    """(F, cols) -> rotated (pairs of rows) and scaled by 1/s."""
    f, cols = w.shape
    wp = w.reshape(f // 2, 2, cols)
    c, sn = jnp.cos(phi), jnp.sin(phi)
    a, b = wp[:, 0], wp[:, 1]
    ra = c[:, None] * a - sn[:, None] * b
    rb = sn[:, None] * a + c[:, None] * b
    return jnp.stack([ra, rb], axis=1).reshape(f, cols) * s_inv[:, None]


def _group_fq(t, bits, group):
    """(rows, cols) -> fake-quant roundtrip with groups along rows.

    Same closed forms as ``core.quant`` (q_min = 0), so the fused output is
    bit-compatible with ``fake_quant``.
    """
    q_max = float((1 << bits) - 1)
    rows, cols = t.shape
    tg = t.reshape(rows // group, group, cols)
    wmax = jnp.max(tg, axis=1)
    wmin = jnp.min(tg, axis=1)
    scale = jnp.maximum((wmax - wmin) / q_max, 1e-8)
    zero = jnp.clip(jnp.round(-wmin / scale), 0.0, q_max)
    q = jnp.clip(jnp.round(tg / scale[:, None]) + zero[:, None], 0.0, q_max)
    fq = (q - zero[:, None]) * scale[:, None]
    return fq.reshape(rows, cols), scale, zero


def _kernel_up(pi_ref, s_ref, phi_ref, w_ref, fq_ref, scale_ref, zero_ref, *,
               bits, group):
    w = w_ref[...].astype(jnp.float32)               # (bg*G, F)
    t = _rotate_scale_cols(w, phi_ref[0, :], s_ref[0, :])
    t = jnp.take(t, pi_ref[0, :], axis=1)            # column permutation
    fq, scale, zero = _group_fq(t, bits, group)
    fq_ref[...] = fq.astype(fq_ref.dtype)
    scale_ref[...] = scale
    zero_ref[...] = zero


def _kernel_down(pi_ref, s_ref, phi_ref, w_ref, fq_ref, scale_ref, zero_ref, *,
                 bits, group):
    w = w_ref[...].astype(jnp.float32)               # (F, bn)
    t = _rotate_scale_rows(w, phi_ref[0, :], 1.0 / s_ref[0, :])
    t = jnp.take(t, pi_ref[0, :], axis=0)            # row permutation
    fq, scale, zero = _group_fq(t, bits, group)
    fq_ref[...] = fq.astype(fq_ref.dtype)
    scale_ref[...] = scale
    zero_ref[...] = zero


def transform_quant_pallas(w, pi, s, phi, *, bits: int, group: int, mode: str,
                           bg: int = 4, bn: int = 128,
                           interpret: bool = False):
    """Fused (π, s, φ)-transform + group fake-quant.

    mode="up":   w (D, F) -> (fq (D, F), scale (D//G, F), zero (D//G, F))
    mode="down": w (F, D) -> (fq (F, D), scale (F//G, D), zero (F//G, D))
    pi (F,) int32; s (F,) f32; phi (F//2,) f32.
    """
    K, N = w.shape
    f = N if mode == "up" else K                     # transformed axis length
    assert pi.shape == (f,) and s.shape == (f,) and phi.shape == (f // 2,)
    assert K % group == 0
    n_groups = K // group
    pi2 = pi.astype(jnp.int32)[None, :]
    s2 = s.astype(jnp.float32)[None, :]
    phi2 = phi.astype(jnp.float32)[None, :]
    vec_specs = [
        pl.BlockSpec((1, f), lambda *idx: (0, 0)),
        pl.BlockSpec((1, f), lambda *idx: (0, 0)),
        pl.BlockSpec((1, f // 2), lambda *idx: (0, 0)),
    ]
    if mode == "up":
        bg = min(bg, n_groups)
        assert n_groups % bg == 0
        grid = (n_groups // bg,)
        kernel = functools.partial(_kernel_up, bits=bits, group=group)
        in_spec = pl.BlockSpec((bg * group, f), lambda i: (i, 0))
        out_specs = [
            pl.BlockSpec((bg * group, f), lambda i: (i, 0)),
            pl.BlockSpec((bg, f), lambda i: (i, 0)),
            pl.BlockSpec((bg, f), lambda i: (i, 0)),
        ]
    elif mode == "down":
        bn = min(bn, N)
        assert N % bn == 0
        grid = (N // bn,)
        kernel = functools.partial(_kernel_down, bits=bits, group=group)
        in_spec = pl.BlockSpec((K, bn), lambda j: (0, j))
        out_specs = [
            pl.BlockSpec((K, bn), lambda j: (0, j)),
            pl.BlockSpec((n_groups, bn), lambda j: (0, j)),
            pl.BlockSpec((n_groups, bn), lambda j: (0, j)),
        ]
    else:
        raise ValueError(f"mode must be 'up' or 'down', got {mode!r}")
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=vec_specs + [in_spec],
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((K, N), w.dtype),
            jax.ShapeDtypeStruct((n_groups, N), jnp.float32),
            jax.ShapeDtypeStruct((n_groups, N), jnp.float32),
        ],
        interpret=interpret,
        name="transform_quant",
    )(pi2, s2, phi2, w)
