"""Jit'd public wrappers for the Pallas kernels.

The kernels compile for the device on every backend but the CPU, where they
execute in ``interpret=True`` mode (the test suite's correctness path). A
shape that violates a kernel's tiling constraints falls back to the pure-jnp
reference; on a TPU that fallback warns once per kernel and shape, with the
reason, so it cannot pass for the kernel.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.quant_matmul import quant_matmul_pallas
from repro.kernels.group_quant import group_quant_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.flash_decode import flash_decode_pallas
from repro.kernels.paged_decode import (paged_decode_gqa_pallas,
                                        paged_decode_pallas)
from repro.kernels.transform_quant import transform_quant_pallas

__all__ = ["quant_matmul", "group_quant", "flash_attention",
           "flash_attention_refusal", "one_device_program", "flash_decode",
           "paged_decode", "transform_quant", "tq_plan", "TQPlan", "on_tpu"]

# VMEM budget for one transform_quant full-F strip. The kernel holds an
# input strip AND a same-size fq output strip, and both revolve per grid
# step so Pallas double-buffers each: ~4x the strip bytes must fit in the
# ~16MB core VMEM. Past this the wrapper falls back to the jnp reference.
_TQ_STRIP_BYTES = 3 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class TQPlan:
    """Pure tiling/VMEM plan for one ``transform_quant`` call site.

    ``ok`` mirrors the wrapper's runtime guard exactly; ``reason`` names the
    first violated constraint when ``ok`` is False (consumed by the static
    Pallas-budget checker so lint reports say *why* a config falls back).
    """

    ok: bool
    strip_bytes: int
    bg: int          # group-block rows (mode="up"; 0 otherwise)
    bn: int          # N-block cols (mode="down"; 0 otherwise)
    n_groups: int
    f: int           # transformed-axis length (N for "up", K for "down")
    reason: str = ""


def tq_plan(K: int, N: int, *, group: int, mode: str) -> TQPlan:
    """Plan the fused transform+fake-quant kernel for a (K, N) fp32 weight.

    This is the single source of truth for the ``_TQ_STRIP_BYTES`` VMEM
    budget and the grid/block divisibility constraints: ``transform_quant``
    consults it at trace time to pick Pallas vs the jnp reference, and
    ``repro.analysis``'s pallas-budget checker replays it at lint time over
    every config in the zoo.
    """
    f = N if mode == "up" else K
    n_groups = K // group if K % group == 0 else 0
    if mode == "up":
        bg = 4 if n_groups % 4 == 0 else (2 if n_groups % 2 == 0 else 1)
        strip = bg * group * f * 4
        bn = 0
    else:
        bg = 0
        bn = 128 if N % 128 == 0 else (N if N <= 128 else 0)
        strip = K * max(bn, 1) * 4
    ok = (n_groups > 0 and f % 2 == 0 and strip <= _TQ_STRIP_BYTES
          and (mode == "up" or bn > 0))
    reason = ""
    if not ok:
        if n_groups <= 0:
            reason = f"K={K} not divisible by group={group}"
        elif f % 2 != 0:
            reason = f"transformed axis f={f} is odd"
        elif strip > _TQ_STRIP_BYTES:
            reason = (f"VMEM strip {strip}B > _TQ_STRIP_BYTES "
                      f"{_TQ_STRIP_BYTES}B")
        else:
            reason = f"mode=down N={N} has no 128-divisible block"
    return TQPlan(ok=ok, strip_bytes=strip, bg=bg, bn=bn,
                  n_groups=n_groups, f=f, reason=reason)


# Longest sequence the flash-attention kernel takes: one grid step holds a
# whole sequence of q, k, v and output for one 128-lane block of heads
# (about 2.5 MB of VMEM, double-buffered, at S = 1024), and its q-tile loop
# is unrolled. Longer sequences keep ``blocked_attention``.
_FA_MAX_SEQ = 1024


def _fa_refusal(q_shape, k_shape, v_shape=None) -> str:
    _, sq, hq, dh = q_shape
    sk, hkv = k_shape[1], k_shape[2]
    dv = dh if v_shape is None else v_shape[3]
    if sq != sk:
        return f"query length {sq} != key length {sk}"
    if dv != dh:
        if dv % 128:
            return f"value head_dim {dv} != {dh} and not a multiple of 128"
        dh = -(-dh // 128) * 128        # q/k heads are zero-padded to lanes
    if dh % 128 and 128 % dh:
        return f"head_dim {dh} neither divides nor is a multiple of 128"
    if (hq * dh) % max(128, dh):
        return f"{hq} heads of {dh} do not fill whole 128-lane blocks"
    if hq % hkv:
        return f"{hq} query heads are not a multiple of {hkv} kv heads"
    if hkv < hq and dh < 128:
        return (f"GQA at head_dim {dh} < 128: a lane block would mix the kv "
                f"heads of its query heads")
    if sq % 16:
        return f"sequence {sq} is not a multiple of 16"
    if sq % min(128, sq):
        return f"sequence {sq} is not a multiple of the 128-row q tile"
    if sq > _FA_MAX_SEQ:
        return f"sequence {sq} exceeds {_FA_MAX_SEQ}"
    return ""


def flash_attention_refusal(q_shape, k_shape, v_shape=None) -> str:
    """Why the flash-attention kernel cannot take q (B, S, Hq, Dh), k
    (B, S, Hkv, Dh) and v (B, S, Hkv, Dv; default Dv = Dh); "" where it can.
    Its lane block is ``max(128, Dh)`` wide: 128 / Dh heads where Dh divides
    128, one head where Dh is a multiple of it; where Dv differs, one head
    of q/k zero-padded to a multiple of 128 lanes and of Dv (a multiple of
    128) lanes of v; its q tile is 256 or 128 rows, or all of a shorter S.
    A refusal warns once per shape on a TPU, with the reason."""
    reason = _fa_refusal(q_shape, k_shape, v_shape)
    if reason:
        _fallback("flash_attention", q_shape, reason)
    return reason


def one_device_program() -> bool:
    """True where the program being traced runs on one device: the process
    sees one device, or the trace is inside a ``shard_map`` over every axis
    of its mesh. A Mosaic kernel cannot be partitioned by GSPMD, so a
    program that may span devices must not hold one."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty and all(t == jax.sharding.AxisType.Manual
                              for t in mesh.axis_types):
        return True
    return jax.device_count() == 1


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Pallas interpret mode: only where the backend is the CPU."""
    return jax.default_backend() == "cpu"


_FALLBACKS_WARNED: set = set()


def _fallback(kernel: str, shape, reason: str) -> None:
    """Record that ``kernel`` falls back to its jnp reference at ``shape``;
    on a TPU, warn once per (kernel, shape) with the reason."""
    key = (kernel, tuple(shape))
    if on_tpu() and key not in _FALLBACKS_WARNED:
        _FALLBACKS_WARNED.add(key)
        warnings.warn(f"{kernel}: shape {tuple(shape)} runs the jnp "
                      f"reference, not the Pallas kernel: {reason}",
                      stacklevel=3)


@functools.partial(jax.jit, static_argnames=("bits", "group", "use_pallas"))
def quant_matmul(x, packed, scale, zero, *, bits: int, group: int,
                 use_pallas: bool = True):
    """x (M, K) @ dequant(packed (K//vpw, N)) -> (M, N) f32.

    The serving path's hot matmul: weights stream packed (2-bit: 16 codes per
    uint32 word), dequantized tile-by-tile in VMEM.
    """
    M, K = x.shape
    N = packed.shape[1]
    vpw = 32 // bits
    ok = (K % group == 0 and K % vpw == 0 and M % 8 == 0 and N % 128 == 0)
    if use_pallas and not ok:
        _fallback("quant_matmul", (M, K, N),
                  f"needs K % group == 0, K % {vpw} == 0, M % 8 == 0 and "
                  f"N % 128 == 0 (group={group})")
    if not (use_pallas and ok):
        return ref.quant_matmul_ref(x, packed, scale, zero, bits, group)
    bk = K
    for cand in (512, 256, 128):
        if K % cand == 0 and cand % group == 0 and cand % vpw == 0:
            bk = cand
            break
    bm = 128 if M % 128 == 0 else 8
    bn = 256 if N % 256 == 0 else 128
    return quant_matmul_pallas(x, packed, scale, zero, bits=bits, group=group,
                               bm=bm, bk=bk, bn=bn, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("causal",))
def flash_attention(q, k, v, *, causal: bool):
    """Full-sequence self-attention, q (B, S, Hq, Dh), k/v (B, S, Hkv, Dh)
    -> (B, S, Hq, Dh): the no-cache forward's attention with its scores kept
    in VMEM (``kernels/flash_attention.py``). The arithmetic is the float32
    reference's at JAX's default matmul precision, one bfloat16 pass, up to
    the output, which is rounded to bfloat16 as the output projection's pass
    rounds it. Forward only; the caller checks ``flash_attention_refusal``
    first (``layers.flash_attention`` adds the gradient).

    Values narrower than queries (latent attention: Dh 192, Dv 128): q and k
    are zero-padded to whole 128-lane blocks, which leaves every score as it
    is, and the softmax scale stays Dh^-0.5 of the unpadded width."""
    reason = _fa_refusal(q.shape, k.shape, v.shape)
    if reason:
        raise ValueError(f"flash_attention: shape {q.shape}: {reason}")
    B, S, H, Dh = q.shape
    Dv = v.shape[3]
    # the MXU operands the float32 reference feeds at default precision; the
    # output is rounded to bfloat16 as the output projection's one pass
    # would round it, which halves its write and the projection's read
    qs = (q.astype(jnp.float32) * Dh ** -0.5).astype(jnp.bfloat16)
    if Dv == Dh:
        qs, ks = qs.reshape(B, S, -1), k.astype(jnp.bfloat16).reshape(B, S, -1)
    else:
        pad = ((0, 0),) * 3 + ((0, -Dh % 128),)
        qs = jnp.pad(qs, pad).reshape(B, S, -1)
        ks = jnp.pad(k.astype(jnp.bfloat16), pad).reshape(B, S, -1)
    out = flash_attention_pallas(
        qs, ks, v.astype(jnp.bfloat16).reshape(B, S, -1), causal=causal,
        head_dim=qs.shape[2] // H, v_head_dim=None if Dv == Dh else Dv,
        interpret=_interpret())
    return out.reshape(B, S, H, Dv).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("kv_len", "chunk", "use_pallas"))
def flash_decode(q, k, v, k_scale=None, v_scale=None, *, kv_len=None,
                 chunk: int = 512, use_pallas: bool = True):
    """Fused one-token decode attention over a bf16 or int8 KV cache."""
    S = k.shape[1]
    ok = S % min(chunk, S) == 0
    if use_pallas and not ok:
        _fallback("flash_decode", k.shape,
                  f"cache length {S} is not a multiple of chunk={chunk}")
    if not (use_pallas and ok):
        return ref.flash_decode_ref(q, k, v, k_scale, v_scale, kv_len)
    return flash_decode_pallas(q, k, v, k_scale, v_scale, kv_len=kv_len,
                               chunk=chunk, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("normalize", "use_pallas",
                                             "fused_gqa",
                                             "gqa_pages_per_block"))
def paged_decode(q, k_pages, v_pages, block_tables, seq_lens, k_scale=None,
                 v_scale=None, *, normalize: bool = True,
                 use_pallas: bool = True, fused_gqa: bool = True,
                 gqa_pages_per_block: int = 1):
    """Paged one-token decode attention over a block-table page pool.

    The continuous-batching hot path: q (B, H, Dh) attends over the pages
    named by ``block_tables`` (B, P) in the global head-major
    (N, Hkv, page_size, Dh) pool (int8 scales (N, Hkv, page_size)), masked
    to per-sequence ``seq_lens``. ``normalize=False`` returns the (acc, m, l)
    partials for the cross-shard LSE merge.

    With ``fused_gqa`` (the default) GQA shapes (H > Hkv) route to the
    (B, Hkv, P)-grid kernel that loads each KV head's page once for its
    whole query-head group — decode HBM reads drop by the GQA ratio. MHA
    shapes (H == Hkv) always use the per-query-head grid, so pre-GQA callers
    see bit-identical outputs. ``gqa_pages_per_block > 1`` further batches
    the fused kernel's online-softmax update over page blocks (the
    multi-page inner grid axis — DMA of the next pages overlaps one
    MXU-shaped (rep, MP*psz) matmul); the default 1 keeps the single-page
    grid bit-for-bit.
    """
    if not use_pallas:
        return ref.paged_decode_ref(q, k_pages, v_pages, block_tables,
                                    seq_lens, k_scale, v_scale,
                                    normalize=normalize)
    H, Hkv = q.shape[1], k_pages.shape[1]
    if fused_gqa and H > Hkv and H % Hkv == 0:
        return paged_decode_gqa_pallas(q, k_pages, v_pages, block_tables,
                                       seq_lens, k_scale, v_scale,
                                       normalize=normalize,
                                       interpret=_interpret(),
                                       pages_per_block=gqa_pages_per_block)
    return paged_decode_pallas(q, k_pages, v_pages, block_tables, seq_lens,
                               k_scale, v_scale, normalize=normalize,
                               interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("bits", "group", "mode", "use_pallas"))
def transform_quant(w, pi, s, phi, *, bits: int, group: int, mode: str,
                    use_pallas: bool = True):
    """Fused (π, s, φ) invariant transform + group fake-quant roundtrip.

    The search engine's fused hot path: one VMEM pass instead of
    materializing the transformed fp32 weights and re-reading them to
    quantize (two HBM round trips per proposal). ``mode="up"`` transforms
    columns of a (D, F) weight; ``mode="down"`` transforms rows of a (F, D)
    weight (there the permutation reshuffles the quant-group axis itself, so
    the passes genuinely cannot be split). Returns (fq, scale, zero).
    """
    K, N = w.shape
    plan = tq_plan(K, N, group=group, mode=mode)
    if use_pallas and not plan.ok:
        _fallback(f"transform_quant[{mode}]", (K, N), plan.reason)
    if not (use_pallas and plan.ok):
        return ref.transform_quant_ref(w, pi, s, phi, bits=bits, group=group,
                                       mode=mode)
    return transform_quant_pallas(w, pi, s, phi, bits=bits, group=group,
                                  mode=mode, bg=plan.bg or 4,
                                  bn=plan.bn or 128, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("bits", "group", "use_pallas"))
def group_quant(w, *, bits: int, group: int, use_pallas: bool = True):
    """Fused fake-quant roundtrip (the search inner primitive).

    Returns (fq (K, N), scale (K//G, N), zero (K//G, N)).
    """
    K, N = w.shape
    ok = (K % group == 0 and N % 128 == 0)
    if use_pallas and not ok:
        _fallback("group_quant", (K, N),
                  f"needs K % group == 0 and N % 128 == 0 (group={group})")
    if not (use_pallas and ok):
        return ref.group_quant_ref(w, bits, group)
    n_groups = K // group
    bg = 4 if n_groups % 4 == 0 else (2 if n_groups % 2 == 0 else 1)
    bn = 256 if N % 256 == 0 else 128
    return group_quant_pallas(w, bits=bits, group=group, bg=bg, bn=bn,
                              interpret=_interpret())
