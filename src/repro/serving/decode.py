"""Jit-able paged decode step: one token for every batch slot, KV in pages.

The step mirrors ``repro.models.model.decode_step``'s scanned layer stack but
replaces the contiguous-cache attention with the paged path:

  1. scatter-write this step's K/V (quantized to int8 when configured) into
     each sequence's current page at ``(block_table[b, pos // psz], pos % psz)``
  2. attend over the pool through ``kernels.paged_decode`` (block table +
     per-sequence lengths scalar-prefetched into the Pallas grid)

Unlike the dense step, positions are PER-SEQUENCE (``seq_lens`` (B,)) — the
whole point of continuous batching is that batch slots sit at unrelated
depths. Idle slots carry ``seq_len == 0`` and a null-page block table: their
write lands in the reserved page and their attention output is fully masked.

Token selection is greedy by default; ``temperature > 0`` switches the step
to temperature / top-k sampling with PER-SEQUENCE RNG keys threaded through
the jitted step (the key array is an extra step argument, so one compiled
program serves every step and re-seeding a sequence is just handing it a new
key row). Greedy steps keep the original 5-argument signature byte-for-byte.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import paged_decode
from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.models.model import dequant_tree, embed_tokens, lm_head_logits
from repro.serving.paged_cache import refuse_unpaged

__all__ = ["make_paged_decode_step", "make_paged_logits_step",
           "paged_attention_block",
           "paged_block_body", "sample_logits", "sample_logits_per_seq",
           "sample_step_keys", "request_key"]


def sample_step_keys(key, batch: int):
    """(B, 2) uint32 per-sequence keys for one sampling step."""
    return jax.random.split(key, batch)


def request_key(seed: int, token_index: int):
    """The RNG key for a request's ``token_index``-th generated token.

    Derived from (seed, token index) ALONE — not from how many decode steps
    actually ran — so a recompute-preempted request resumes its sample stream
    exactly where it left off: the re-admit's first sampled token uses the
    same key the uninterrupted decode step would have used.
    """
    return jax.random.fold_in(jax.random.PRNGKey(seed), token_index)


def sample_logits(logits, keys, *, temperature: float, top_k: int = 0):
    """Per-sequence temperature / top-k sampling.

    logits (B, V); keys (B, 2) uint32 (one key row per sequence, e.g. from
    ``sample_step_keys``). ``top_k > 0`` restricts sampling to the k highest
    logits; ``temperature <= 0`` degenerates to greedy argmax. Returns (B,)
    int32 — deterministic in (logits, keys).
    """
    logits = logits.astype(jnp.float32)
    if top_k and top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / temperature
    return jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)


def sample_logits_per_seq(logits, keys, temperature, top_k):
    """Per-SEQUENCE temperature / top-k sampling (params as (B,) arrays).

    The batcher's mixed-batch path: each slot carries its own ``temperature``
    (f32) and ``top_k`` (int32), so one compiled step serves any mix of
    greedy and sampled requests. Slots with ``temperature <= 0`` take the
    exact argmax (identical to the greedy step's selection); ``top_k == 0``
    means unrestricted. Per-row thresholds come from a full descending sort
    (k is per-row, so ``lax.top_k``'s static k does not apply); for a row
    with top_k == k the kept set matches ``sample_logits``'s
    ``lax.top_k``-derived threshold exactly.
    """
    logits = logits.astype(jnp.float32)
    B, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    srt = jnp.sort(logits, axis=-1)[:, ::-1]             # descending
    kth = jnp.take_along_axis(
        srt, jnp.clip(top_k - 1, 0, V - 1)[:, None], axis=-1)
    restricted = jnp.where(logits < kth, -jnp.inf, logits)
    eff = jnp.where((top_k > 0)[:, None], restricted, logits)
    safe_t = jnp.where(temperature > 0.0, temperature, 1.0)
    sampled = jax.vmap(jax.random.categorical)(keys, eff / safe_t[:, None])
    return jnp.where(temperature > 0.0, sampled.astype(jnp.int32), greedy)


def _write_token(pool, phys, slot, val):
    """pool (N, Hkv, psz, ...) <- val (B, Hkv, ...) at page ``phys[b]``, slot
    ``slot[b]`` of every head, per batch slot b."""
    return pool.at[phys, :, slot].set(val.astype(pool.dtype))


def paged_block_body(pl, cfg: ModelConfig, carry, pool_slice, attn_sublayer):
    """One dense/moe block over a paged pool slice, shared by BOTH paged
    serving stacks — ``attn_sublayer(attn_params, normed_x, pool_slice) ->
    (attn_out, new_pool)`` is the ONLY difference between the decode step
    (single-token scatter-write) and the prefill chunk step (chunk write).
    Keeping one body here is what guarantees their numerics cannot drift
    (the prefill<=1e-5 equivalence and preemption determinism depend on a
    re-admitted request resuming through the same block math)."""
    pl = dequant_tree(pl, jnp.dtype(cfg.compute_dtype))
    a_in = L.apply_norm(carry, pl["ln1"], cfg.norm)
    a, new_pool = attn_sublayer(pl["attn"], a_in, pool_slice)
    hh = carry + a
    m_in = L.apply_norm(hh, pl["ln2"], cfg.norm)
    if "moe" in pl:
        hh = hh + L.moe_ffn(pl["moe"], cfg, m_in)
    else:
        hh = hh + L.mlp(pl["mlp"], cfg, m_in)
    return hh, new_pool


def paged_attention_block(p, cfg: ModelConfig, x, pools, block_tables,
                          seq_lens, *, use_pallas: bool = True,
                          gqa_pages_per_block: int = 1):
    """Attention sublayer over the paged cache (one layer's pool slices).

    x: (B, 1, D) normed input; pools: {"k"/"v": (N, Hkv, psz, hd)[, scales
    (N, Hkv, psz)]}.
    Returns (attn_out (B, 1, D), updated pools). ``gqa_pages_per_block``
    batches the fused-GQA kernel's inner softmax over page blocks (1 keeps
    the single-page grid bit-for-bit).
    """
    positions = seq_lens[:, None]                       # (B, 1) write position
    q, k, v = L.attn_qkv(p, cfg, x, positions)
    psz = pools["k"].shape[2]
    phys = jnp.take_along_axis(block_tables, (seq_lens // psz)[:, None],
                               axis=1)[:, 0]            # (B,) physical page
    slot = seq_lens % psz
    new = dict(pools)
    if "k_scale" in pools:  # int8 pool: same convention as the dense cache
        kq, vq, ks, vs = L.quantize_kv(k, v)
        new["k"] = _write_token(pools["k"], phys, slot, kq[:, 0])
        new["v"] = _write_token(pools["v"], phys, slot, vq[:, 0])
        new["k_scale"] = _write_token(pools["k_scale"], phys, slot, ks[:, 0])
        new["v_scale"] = _write_token(pools["v_scale"], phys, slot, vs[:, 0])
    else:
        new["k"] = _write_token(pools["k"], phys, slot, k[:, 0])
        new["v"] = _write_token(pools["v"], phys, slot, v[:, 0])
    out = paged_decode(q[:, 0], new["k"], new["v"], block_tables, seq_lens + 1,
                       new.get("k_scale"), new.get("v_scale"),
                       use_pallas=use_pallas,
                       gqa_pages_per_block=gqa_pages_per_block)
    return L.attn_out(p, out[:, None].astype(q.dtype), cfg), new


def make_paged_logits_step(cfg: ModelConfig, *, use_pallas: bool = True,
                           gqa_pages_per_block: int = 1):
    """(params_q, tokens (B,1), pools, block_tables (B,P), seq_lens (B,))
    -> (logits (B, 1, V) vocab-masked, updated pools): the layer stack every
    decode step below runs before it selects a token."""
    refuse_unpaged(cfg, "paged decode")
    if cfg.is_enc_dec:
        raise ValueError("paged decode does not cover cross-attention caches")

    def logits_step(params_q, tokens, pools, block_tables, seq_lens):
        positions = seq_lens[:, None]
        h = embed_tokens(params_q, cfg, tokens, positions)

        def attn(p, x, pool_slice):
            return paged_attention_block(
                p, cfg, x, pool_slice, block_tables, seq_lens,
                use_pallas=use_pallas,
                gqa_pages_per_block=gqa_pages_per_block)

        def body(carry, xs):
            pl, pool_slice = xs
            return paged_block_body(pl, cfg, carry, pool_slice, attn)

        h, new_pools = jax.lax.scan(body, h, (params_q["blocks"], pools),
                                    unroll=cfg.unroll_layers)
        return lm_head_logits(params_q, cfg, h, mask_vocab=True), new_pools

    return logits_step


def make_paged_decode_step(cfg: ModelConfig, *, use_pallas: bool = True,
                           temperature: float = 0.0, top_k: int = 0,
                           per_request: bool = False,
                           gqa_pages_per_block: int = 1):
    """(params_q, tokens (B,1), pools, block_tables (B,P), seq_lens (B,))
    -> (next_token (B,1) int32, updated pools).

    ``pools`` leaves carry a leading n_layers axis and are scanned alongside
    the stacked layer params, exactly like the dense cache in
    ``model.decode_step``. Only attention-cache architectures page.

    With ``temperature > 0`` the returned step takes one extra trailing
    argument, ``sample_keys`` (B, 2) uint32 per-sequence keys, and samples
    through ``sample_logits`` (optionally top-k-restricted); the default
    greedy step keeps the original signature and argmax selection unchanged.

    ``per_request=True`` instead appends FOUR trailing arguments — ``seeds``
    (B,) int32, ``token_indices`` (B,) int32, ``temperatures`` (B,) f32 and
    ``top_ks`` (B,) int32. Keys are folded from (seed, token index) inside
    the compiled step (``request_key``) and selection routes through
    ``sample_logits_per_seq``, so a single program serves any per-slot mix
    of greedy and sampled requests (the continuous batcher's path).
    """
    logits_step = make_paged_logits_step(
        cfg, use_pallas=use_pallas, gqa_pages_per_block=gqa_pages_per_block)

    if per_request:
        def per_request_step(params_q, tokens, pools, block_tables, seq_lens,
                             seeds, token_indices, temperatures, top_ks):
            logits, new_pools = logits_step(params_q, tokens, pools,
                                            block_tables, seq_lens)
            # keys are derived INSIDE the compiled step from (seed, token
            # index) — the batcher ships two int vectors instead of running
            # B tiny key-fold programs (device round trips) per decode step
            keys = jax.vmap(request_key)(seeds, token_indices)
            next_tok = sample_logits_per_seq(logits[:, -1], keys,
                                             temperatures, top_ks)
            return next_tok[:, None], new_pools
        return per_request_step

    if temperature > 0.0:
        def sampled_step(params_q, tokens, pools, block_tables, seq_lens,
                         sample_keys):
            logits, new_pools = logits_step(params_q, tokens, pools,
                                            block_tables, seq_lens)
            next_tok = sample_logits(logits[:, -1], sample_keys,
                                     temperature=temperature, top_k=top_k)
            return next_tok[:, None], new_pools
        return sampled_step

    def step(params_q, tokens, pools, block_tables, seq_lens):
        logits, new_pools = logits_step(params_q, tokens, pools, block_tables,
                                        seq_lens)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        return next_tok, new_pools

    return step
