"""Sequence-sharded decode attention via partial-softmax (log-sum-exp) merge.

The long_500k cell shards the KV cache along SEQUENCE (batch 1 cannot shard
over "data"). Each shard computes attention stats over its local KV slice:

    acc_i = sum_s exp(s - m_i) * v_s      (unnormalized output)
    m_i   = max_s(scores)                 (running max)
    l_i   = sum_s exp(s - m_i)            (normalizer mass)

and the merge recovers EXACT dense softmax attention:

    m*  = max_i m_i
    out = sum_i exp(m_i - m*) acc_i / sum_i exp(m_i - m*) l_i

— the same identity flash attention uses across KV blocks, applied across
devices. ``sharded_decode_attention`` does the merge with pmax/psum inside
shard_map; ``merge_partials`` is the collective-free oracle used in tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["partial_decode_attention", "merge_partials",
           "sharded_decode_attention", "sharded_paged_decode_attention"]

_MASKED = -1e30  # matches kernels/ref.py masking (finite: no NaN via inf-inf)


def partial_decode_attention(q, k, v, *, kv_len=None, start=0):
    """One-token attention stats over a local KV shard.

    q: (B, H, Dh); k/v: (B, S_shard, H, Dh). ``start`` is this shard's global
    sequence offset; positions >= ``kv_len`` are masked out. Returns
    (acc (B, H, Dh), m (B, H), l (B, H)) in float32.

    A fully-masked shard degrades safely: m == _MASKED makes its merge weight
    exp(m - m*) underflow to exactly 0.
    """
    dh = q.shape[-1]
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * dh ** -0.5
    if kv_len is not None:
        pos = start + jnp.arange(k.shape[1])
        s = jnp.where(pos[None, None, :] < kv_len, s, _MASKED)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhs,bshd->bhd", p, v.astype(jnp.float32))
    return acc, m, l


def merge_partials(acc, m, l):
    """Merge stacked shard stats -> dense softmax attention output.

    acc: (N, B, H, Dh); m, l: (N, B, H) — leading axis indexes shards.
    """
    m_star = jnp.max(m, axis=0)
    alpha = jnp.exp(m - m_star[None])           # (N, B, H)
    num = jnp.sum(alpha[..., None] * acc, axis=0)
    den = jnp.sum(alpha * l, axis=0)
    return num / den[..., None]


def sharded_paged_decode_attention(q, k_pages, v_pages, block_tables,
                                   seq_lens, axis_name, k_scale=None,
                                   v_scale=None, *, use_pallas=True):
    """Decode attention over a sequence-sharded PAGED cache (shard_map body).

    Each device owns a head-major page pool (N, Hkv, psz, Dh) holding its
    slice of every sequence plus
    the matching per-shard block tables (B, P_local) and LOCAL lengths (B,)
    — the paged analogue of ``sharded_decode_attention``. The Pallas kernel
    (``kernels.paged_decode`` with ``normalize=False``) emits the exact
    (acc, m, l) log-sum-exp partials this merge needs, so paging composes
    with sequence sharding at the cost of the same two O(B*H*Dh)
    collectives. Softmax weights depend only on scores, not positions, so
    local masking per shard merges exactly.
    """
    from repro.kernels import paged_decode  # deferred: dist stays importable
    acc, m, l = paged_decode(q, k_pages, v_pages, block_tables, seq_lens,
                             k_scale, v_scale, normalize=False,
                             use_pallas=use_pallas)
    m_star = jax.lax.pmax(m, axis_name)
    alpha = jnp.exp(m - m_star)
    num, den = jax.lax.psum((alpha[..., None] * acc, alpha * l), axis_name)
    return num / den[..., None]


def sharded_decode_attention(q, k, v, axis_name, *, shard_start=0, kv_len=None):
    """Decode attention over a sequence-sharded KV cache (shard_map context).

    q: (B, H, Dh) replicated; k/v: (B, S_local, H, Dh) — this device's
    sequence slice; ``shard_start`` is its global offset (typically
    ``jax.lax.axis_index(axis_name) * S_local``). Two collectives total
    (pmax + fused psum), both O(B*H*Dh), independent of sequence length.
    """
    acc, m, l = partial_decode_attention(q, k, v, kv_len=kv_len,
                                         start=shard_start)
    m_star = jax.lax.pmax(m, axis_name)
    alpha = jnp.exp(m - m_star)
    num, den = jax.lax.psum((alpha[..., None] * acc, alpha * l), axis_name)
    return num / den[..., None]
