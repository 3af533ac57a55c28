"""Chunked paged prefill: prompt K/V written straight into allocated pages.

The v1 admit path ran a CONTIGUOUS prefill over the whole right-padded prompt
(one ``(1, s_pad)`` cache buffer per admit) and then scatter-copied every
layer's K/V into the page pool (``PagedKVCache.write_prefill``). That is two
full passes over the prompt's KV bytes, one jit shape per padded prompt
length, and a transient contiguous allocation that defeats the point of
paging.

This module prefills *in page-aligned chunks*:

  - the prompt is split into chunks of ``chunk_pages * page_size`` tokens
    (the tail chunk padded up to a page multiple), so the jitted step sees at
    most ``chunk_pages`` distinct shapes TOTAL — not one per prompt length;
  - each chunk's K/V is written DIRECTLY into the sequence's allocated pages
    (a ``(C // page_size)``-page scatter inside the jitted step — no
    contiguous ``(1, s_pad)`` KV buffer ever exists);
  - chunk attention runs over the page pool itself through an online-softmax
    scan across the block table (``paged_prefill_attention``): one page is
    gathered per scan step, causally masked at absolute positions, so
    chunk c attends over chunks 0..c-1's pages plus its own freshly written
    pages without materialising a contiguous cache.

The jnp scan is the portable fallback the ISSUE allows; the page-gather
structure mirrors ``kernels/paged_decode.py``'s grid (one page per step,
online (m, l, acc) carry) so a Pallas lowering can swap in per page-block
without changing the batcher contract.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.models.model import embed_tokens, lm_head_logits
from repro.serving.decode import paged_block_body
from repro.serving.paged_cache import refuse_unpaged

__all__ = ["paged_prefill_attention", "make_paged_prefill_step",
           "run_prefill_chunks"]

NEG = -1e30


def paged_prefill_attention(q, pools, block_tables, offset):
    """Causal attention of a prefill chunk over the page pool.

    q: (B, C, H, Dh) chunk queries at absolute positions ``offset + i``;
    pools: one layer's slices {"k"/"v": (N, Hkv, psz, Dh)[, "k_scale"/...]};
    block_tables: (B, P) physical page ids; offset: scalar int32 (page
    aligned). Keys live in the pool ONLY — each scan step gathers a single
    (B, Hkv, psz, Dh) page, keeps the online-softmax (m, l, acc) carry, and
    masks by ``key_pos <= query_pos`` so dead/null/garbage page slots never
    contribute. Returns (B, C, H, Dh) f32.
    """
    B, C, H, Dh = q.shape
    kp, vp = pools["k"], pools["v"]
    Hkv, psz = kp.shape[1], kp.shape[2]
    rep = H // Hkv
    P = block_tables.shape[1]
    ks, vs = pools.get("k_scale"), pools.get("v_scale")

    qf = q.astype(jnp.float32) * Dh ** -0.5
    q_pos = offset + jnp.arange(C)                       # (C,) absolute

    def body(p, carry):
        m, l, acc = carry
        pg = block_tables[:, p]                          # (B,) physical page
        kb = kp[pg].astype(jnp.float32)                  # (B, Hkv, psz, Dh)
        vb = vp[pg].astype(jnp.float32)
        if ks is not None:
            kb = kb * ks[pg][..., None].astype(jnp.float32)
            vb = vb * vs[pg][..., None].astype(jnp.float32)
        if rep > 1:
            kb = jnp.repeat(kb, rep, axis=1)
            vb = jnp.repeat(vb, rep, axis=1)
        s = jnp.einsum("bchd,bhkd->bhck", qf, kb)        # (B, H, C, psz)
        k_pos = p * psz + jnp.arange(psz)
        mask = k_pos[None, :] <= q_pos[:, None]          # (C, psz) causal
        s = jnp.where(mask[None, None], s, NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        prob = jnp.where(mask[None, None], jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        acc = acc * corr[..., None] + jnp.einsum("bhck,bhkd->bhcd", prob, vb)
        l = l * corr + jnp.sum(prob, axis=-1)
        return (m_new, l, acc)

    m0 = jnp.full((B, H, C), NEG, jnp.float32)
    l0 = jnp.zeros((B, H, C), jnp.float32)
    a0 = jnp.zeros((B, H, C, Dh), jnp.float32)
    # causality bounds the reachable keys at offset + C, so only the first
    # ceil((offset + C) / psz) table entries can contribute — a fori_loop
    # with that (traced) bound keeps admit cost O(live pages), not
    # O(max_pages_per_seq), per chunk (C and offset are page multiples, so
    # the division is exact; the bound is clamped to the table width).
    n_reach = jnp.minimum((offset + C) // psz, P)
    m, l, acc = jax.lax.fori_loop(0, n_reach, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3)                     # (B, C, H, Dh)


def _write_chunk(pool, page_ids, val):
    """pool (N, Hkv, psz, ...) <- val (B=1, C, Hkv, ...) across the chunk's
    pages."""
    n = page_ids.shape[0]
    psz = pool.shape[2]
    src = jnp.swapaxes(val[0].reshape((n, psz) + val.shape[2:]), 1, 2)
    return pool.at[page_ids].set(src.astype(pool.dtype))


def paged_prefill_block(p, cfg: ModelConfig, x, pools, block_tables, offset):
    """One layer's attention sublayer for a prefill chunk (write + attend).

    x: (1, C, D) normed input, C a page multiple, ``offset`` page-aligned.
    Writes the chunk's K/V into pages ``block_tables[0, offset//psz : ... +
    C//psz]`` then attends over the pool. Returns (attn_out, new pools).
    """
    B, C, _ = x.shape
    psz = pools["k"].shape[2]
    positions = offset + jnp.arange(C)[None]             # (1, C)
    q, k, v = L.attn_qkv(p, cfg, x, positions)
    ids = jax.lax.dynamic_slice(block_tables[0], (offset // psz,),
                                (C // psz,))             # this chunk's pages
    new = dict(pools)
    if "k_scale" in pools:
        kq, vq, kscale, vscale = L.quantize_kv(k, v)
        new["k"] = _write_chunk(pools["k"], ids, kq)
        new["v"] = _write_chunk(pools["v"], ids, vq)
        new["k_scale"] = _write_chunk(pools["k_scale"], ids, kscale)
        new["v_scale"] = _write_chunk(pools["v_scale"], ids, vscale)
    else:
        new["k"] = _write_chunk(pools["k"], ids, k)
        new["v"] = _write_chunk(pools["v"], ids, v)
    out = paged_prefill_attention(q, new, block_tables, offset)
    return L.attn_out(p, out.astype(q.dtype), cfg), new


def run_prefill_chunks(chunk_fn, params_q, pools, full, block_table, *,
                       page_size: int, chunk_pages: int, start: int = 0):
    """Drive ``chunk_fn`` (a compiled ``make_paged_prefill_step``) over
    ``full[start:]`` in page-aligned chunks.

    ``start`` must be a ``page_size`` multiple strictly below ``len(full)`` —
    the admit path's prefix-cache hook: positions below ``start`` were
    aliased from already-populated pages and are skipped entirely (zero
    prefill for the cached run), so only the divergent tail is computed.
    Returns ``(last_logits_row, pools, n_chunks)`` where ``last_logits_row``
    is the (V,) logits of the final prompt position (the first-token input).
    """
    plen = len(full)
    if not 0 <= start < plen:
        raise ValueError(f"start={start} outside prompt of {plen} tokens")
    if start % page_size:
        raise ValueError(f"start={start} not page aligned (psz={page_size})")
    chunk_tokens = max(chunk_pages, 1) * page_size
    off, last_off = start, start
    logits = None
    n_chunks = 0
    while off < plen:
        n_tok = min(chunk_tokens, plen - off)
        c = -(-n_tok // page_size) * page_size  # pad tail to a page multiple
        toks = np.zeros((1, c), np.int32)
        toks[0, :n_tok] = full[off: off + n_tok]
        logits, pools = chunk_fn(params_q, jnp.asarray(toks), pools,
                                 block_table, jnp.int32(off))
        n_chunks += 1
        last_off, off = off, off + n_tok
    return logits[0, (plen - 1) - last_off], pools, n_chunks


def make_paged_prefill_step(cfg: ModelConfig):
    """(params_q, tokens (1, C), pools, block_tables (1, P), offset ())
    -> (logits (1, C, V) vocab-masked, updated pools).

    One prefill CHUNK: C must be a ``page_size`` multiple and ``offset`` a
    page-aligned scalar (traced — one compiled program per chunk length C,
    shared by every admit). The layer stack is scanned with the page pools as
    carried slices, exactly like ``make_paged_decode_step``; padded tail
    positions write garbage into the chunk's own allocated pages (masked at
    every later read by causality / per-sequence lengths).
    """
    refuse_unpaged(cfg, "paged prefill")
    if cfg.is_enc_dec:
        raise ValueError("paged prefill does not cover cross-attention caches")

    def chunk_step(params_q, tokens, pools, block_tables, offset):
        C = tokens.shape[1]
        positions = offset + jnp.arange(C)
        h = embed_tokens(params_q, cfg, tokens, positions)

        def attn(p, x, pool_slice):
            return paged_prefill_block(p, cfg, x, pool_slice, block_tables,
                                       offset)

        def body(carry, xs):
            pl, pool_slice = xs
            return paged_block_body(pl, cfg, carry, pool_slice, attn)

        h, new_pools = jax.lax.scan(body, h, (params_q["blocks"], pools),
                                    unroll=cfg.unroll_layers)
        logits = lm_head_logits(params_q, cfg, h, mask_vocab=True)
        return logits, new_pools

    return chunk_step
