"""Fused dequant-matmul Pallas TPU kernel: x @ dequant(packed W).

Weight-only ultra-low-bit serving is HBM-bandwidth-bound: at 2 bits + g128
the packed weights are ~7.5x smaller than bf16. The win only materialises if
dequantization happens AFTER the HBM->VMEM stream — so this kernel unpacks
(shift/mask in VREGs), dequantizes ((q - z) * s) and feeds the MXU per
(bm × bk) · (bk × bn) tile, accumulating over the K grid axis. Weight HBM
traffic drops by the packing factor vs. a dense bf16 matmul.

Tiling constraints (checked in ops.py):
  - bk % group_size == 0 and bk % vals_per_word == 0 (scale/zero and packed
    tiles stay row-aligned),
  - bm/bn multiples of 8/128 for MXU alignment.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["quant_matmul_pallas"]


def _kernel(x_ref, packed_ref, scale_ref, zero_ref, o_ref, *, bits, group, bk):
    vpw = 32 // bits
    mask = jnp.uint32((1 << bits) - 1)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    packed = packed_ref[...]                     # (bk//vpw, bn) uint32
    # unpack slot i -> original row w*vpw + i : stack along axis 1, reshape
    parts = [((packed >> jnp.uint32(i * bits)) & mask).astype(jnp.float32)
             for i in range(vpw)]
    codes = jnp.stack(parts, axis=1).reshape(bk, packed.shape[1])
    scale = scale_ref[...]                       # (bk//group, bn)
    zero = zero_ref[...]
    s = jnp.repeat(scale, group, axis=0)
    z = jnp.repeat(zero, group, axis=0)
    w = (codes - z) * s                          # dequantized (bk, bn) f32
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def quant_matmul_pallas(x, packed, scale, zero, *, bits: int, group: int,
                        bm: int = 128, bk: int = 512, bn: int = 256,
                        interpret: bool = False):
    """x: (M, K) f32/bf16; packed: (K//vpw, N) uint32; scale/zero: (K//G, N).

    Returns (M, N) f32. Shape constraints are validated by ops.quant_matmul
    (which also pads / falls back to the reference path).
    """
    M, K = x.shape
    N = packed.shape[1]
    vpw = 32 // bits
    bm = min(bm, M)
    bk = min(bk, K)
    bn = min(bn, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0
    assert bk % group == 0 and bk % vpw == 0
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        functools.partial(_kernel, bits=bits, group=group, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // vpw, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk // group, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk // group, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
        name="quant_matmul",
    )(x, packed, scale, zero)
