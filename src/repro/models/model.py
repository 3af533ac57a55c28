"""Model assembly: init / forward / decode for every block pattern.

Layer stacks are scanned (stacked params, one compiled body) so 40-80 layer
models lower to a small HLO. Quantized serving: any 2-D weight leaf may be a
``QTensor`` — it is dequantized *inside* the scan body, so only one layer's
weights are ever materialised (this is where 2-bit serving saves HBM).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.core.quant import QTensor
from repro.models.config import ModelConfig
from repro.models import layers as L
from repro.models import ssm as SSM

__all__ = [
    "init_params", "forward", "lm_loss", "init_cache", "decode_step",
    "prefill", "dequant_tree", "lm_head_logits", "quantizable_paths",
    "n_lead_blocks",
]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def dequant_tree(tree, dtype=None):
    """Materialise any QTensor leaves (called per scan-slice inside blocks)."""
    def deq(x):
        if isinstance(x, QTensor):
            w = x.dequantize(dtype or jnp.float32)
            return w
        return x
    return jax.tree.map(deq, tree, is_leaf=lambda x: isinstance(x, QTensor))


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_dense_block(key, cfg: ModelConfig, dt, cross=False, lead=False):
    """One attention + FFN block; ``lead``: a leading dense block of a MoE
    stack (FFN width ``dense_d_ff``)."""
    ks = jax.random.split(key, 4)
    p = {
        "ln1": L.init_norm(cfg.d_model, cfg.norm, dt),
        "attn": (L.init_mla(ks[0], cfg, dt) if cfg.mla is not None
                 else L.init_attn(ks[0], cfg, dt)),
        "ln2": L.init_norm(cfg.d_model, cfg.norm, dt),
    }
    if cfg.block_pattern == "moe" and not cross and not lead:
        p["moe"] = L.init_moe(ks[1], cfg, dt)
    else:
        p["mlp"] = L.init_mlp(ks[1], cfg, dt,
                              d_ff=cfg.dense_d_ff if lead else None)
    if cross:
        p["ln_x"] = L.init_norm(cfg.d_model, cfg.norm, dt)
        p["xattn"] = L.init_attn(ks[2], cfg, dt)
    return p


def _init_ssm_block(key, cfg: ModelConfig, dt):
    return {"ln1": L.init_norm(cfg.d_model, cfg.norm, dt), "ssm": SSM.init_ssm(key, cfg, dt)}


def _stack_init(init_fn, key, n):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def n_lead_blocks(cfg: ModelConfig) -> int:
    """Leading dense blocks of a MoE stack (``params["dense_blocks"]``),
    run before the scanned ``params["blocks"]``."""
    return cfg.first_k_dense if cfg.block_pattern == "moe" else 0


def init_params(key, cfg: ModelConfig):
    dt = _dtype(cfg)
    keys = jax.random.split(key, 8)
    V, D = cfg.padded_vocab, cfg.d_model
    params = {"embed": {"tok": jax.random.normal(keys[0], (V, D), dt) * 0.02}}
    if cfg.pos_emb == "learned":
        params["embed"]["pos"] = jax.random.normal(keys[1], (cfg.max_seq_len, D), dt) * 0.02

    if cfg.block_pattern in ("dense", "moe"):
        cross = cfg.is_enc_dec
        n_lead = n_lead_blocks(cfg)
        params["blocks"] = _stack_init(
            lambda k: _init_dense_block(k, cfg, dt, cross=cross), keys[2],
            cfg.n_layers - n_lead)
        if n_lead:
            params["dense_blocks"] = _stack_init(
                lambda k: _init_dense_block(k, cfg, dt, lead=True), keys[6],
                n_lead)
    elif cfg.block_pattern == "ssm":
        params["blocks"] = _stack_init(lambda k: _init_ssm_block(k, cfg, dt), keys[2], cfg.n_layers)
    elif cfg.block_pattern == "hybrid":
        n_m, n_a = cfg.hybrid_layout()
        params["blocks"] = _stack_init(lambda k: _init_ssm_block(k, cfg, dt), keys[2], n_m)
        params["shared"] = _init_dense_block(keys[3], cfg, dt)
    else:
        raise ValueError(cfg.block_pattern)

    if cfg.is_enc_dec:
        enc_cfg = cfg  # same dims; encoder blocks are non-causal dense
        params["enc_blocks"] = _stack_init(
            lambda k: _init_dense_block(k, enc_cfg, dt, cross=False), keys[4], cfg.encoder_layers)
        params["enc_norm"] = L.init_norm(D, cfg.norm, dt)

    params["final_norm"] = L.init_norm(D, cfg.norm, dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(keys[5], (D, V), dt) * D ** -0.5
    return params


# ---------------------------------------------------------------------------
# Block bodies
# ---------------------------------------------------------------------------

def _dense_body(pl, cfg: ModelConfig, h, positions, cache=None, cache_index=0,
                enc_h=None, causal=True):
    pl = dequant_tree(pl, jnp.dtype(cfg.compute_dtype))
    a_in = L.apply_norm(h, pl["ln1"], cfg.norm, cfg.norm_eps)
    attention = L.mla_attention if cfg.mla is not None else L.self_attention
    with jax.named_scope("attn"):
        a, new_cache = attention(pl["attn"], cfg, a_in, positions, causal=causal,
                                 cache=cache, cache_index=cache_index)
    h = h + a
    if "xattn" in pl and enc_h is not None:
        x_in = L.apply_norm(h, pl["ln_x"], cfg.norm)
        kv = L.cross_kv(pl["xattn"], cfg, enc_h)
        h = h + L.cross_attention(pl["xattn"], cfg, x_in, kv)
    m_in = L.apply_norm(h, pl["ln2"], cfg.norm, cfg.norm_eps)
    if "moe" in pl:   # scopes moe.router / .dispatch / .experts / .shared
        f = L.moe_ffn(pl["moe"], cfg, m_in)
    else:
        with jax.named_scope("ffn"):
            f = L.mlp(pl["mlp"], cfg, m_in)
    return h + f, new_cache


def _dense_body_cached_cross(pl, cfg, h, positions, cache, cache_index, cross_kv):
    """Decode body for enc-dec: cross-attn uses precomputed (k, v)."""
    pl = dequant_tree(pl, jnp.dtype(cfg.compute_dtype))
    a_in = L.apply_norm(h, pl["ln1"], cfg.norm)
    a, new_cache = L.self_attention(pl["attn"], cfg, a_in, positions, causal=True,
                                    cache=cache, cache_index=cache_index)
    h = h + a
    x_in = L.apply_norm(h, pl["ln_x"], cfg.norm)
    h = h + L.cross_attention(pl["xattn"], cfg, x_in, cross_kv)
    m_in = L.apply_norm(h, pl["ln2"], cfg.norm)
    h = h + L.mlp(pl["mlp"], cfg, m_in)
    return h, new_cache


def _ssm_body(pl, cfg: ModelConfig, h, state=None, decode=False):
    pl = dequant_tree(pl, jnp.dtype(cfg.compute_dtype))
    s_in = L.apply_norm(h, pl["ln1"], cfg.norm)
    if decode:
        out, new_state = SSM.ssm_decode_step(pl["ssm"], cfg, s_in, state)
        return h + out, new_state
    return h + SSM.ssm_forward(pl["ssm"], cfg, s_in), None


def _single_kv(cfg: ModelConfig, batch: int, max_len: int, dt):
    """One block's empty KV cache (matches init_cache leaf layout sans stack)."""
    hd = cfg.resolved_head_dim
    if cfg.kv_cache_dtype == "int8":
        return {"k": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), jnp.int8),
                "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), jnp.int8),
                "k_scale": jnp.zeros((batch, max_len, cfg.n_kv_heads), dt),
                "v_scale": jnp.zeros((batch, max_len, cfg.n_kv_heads), dt)}
    return {"k": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dt),
            "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dt)}


def _maybe_remat(fn, cfg: ModelConfig):
    if not cfg.remat or cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        # §Perf iteration 1 (refuted for MoE): batch-dim dots are NOT saved,
        # so MoE expert einsums / attention einsums recompute anyway.
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if cfg.remat_policy == "dots_all":
        # §Perf iteration 2: save EVERY dot output (incl. batched MoE/attn
        # einsums), recompute only the elementwise tail.
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_saveable)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# Forward (training / full-sequence)
# ---------------------------------------------------------------------------

@jax.named_scope("embed")
def embed_tokens(params, cfg: ModelConfig, tokens, positions):
    h = jnp.take(params["embed"]["tok"], tokens, axis=0)
    if cfg.pos_emb == "learned":
        h = h + jnp.take(params["embed"]["pos"], positions, axis=0)
    return h.astype(jnp.dtype(cfg.compute_dtype))


@jax.named_scope("lm_head")
def lm_head_logits(params, cfg: ModelConfig, h, *, mask_vocab: bool = False):
    """Final norm + (tied or dedicated, possibly QTensor) LM head.

    The one implementation every decode path shares — forward / decode_step /
    prefill here plus the paged serving steps in ``repro.serving``.
    ``mask_vocab=True`` sets padded-vocab columns to -inf (the serving steps'
    convention before argmax/sampling).
    """
    h = L.apply_norm(h, params["final_norm"], cfg.norm, cfg.norm_eps)
    head = params["embed"]["tok"].T if cfg.tie_embeddings else params["lm_head"]
    if isinstance(head, QTensor):
        head = head.dequantize(h.dtype)
    logits = h @ head.astype(h.dtype)
    if mask_vocab:
        V = logits.shape[-1]
        if V > cfg.vocab_size:
            logits = jnp.where(jnp.arange(V) < cfg.vocab_size, logits,
                               -jnp.inf)
    return logits


def _run_encoder(params, cfg: ModelConfig, enc_embeds):
    h = enc_embeds.astype(jnp.dtype(cfg.compute_dtype))
    positions = jnp.arange(h.shape[1])

    def body(carry, pl):
        out, _ = _dense_body(pl, cfg, carry, positions, causal=False)
        return out, None

    h, _ = jax.lax.scan(_maybe_remat(body, cfg), h, params["enc_blocks"], unroll=cfg.unroll_layers)
    return L.apply_norm(h, params["enc_norm"], cfg.norm)


def forward(params, cfg: ModelConfig, tokens, *, enc_embeds=None, vision_embeds=None,
            collect_hidden=False):
    """Full-sequence forward -> logits (B, S_total, V_padded).

    vision_embeds (B, P, D) are prepended (VLM); enc_embeds (B, S_enc, D) feed
    the encoder (enc-dec).
    """
    B, S = tokens.shape
    h = embed_tokens(params, cfg, tokens, jnp.arange(S))
    if vision_embeds is not None:
        h = jnp.concatenate([vision_embeds.astype(h.dtype), h], axis=1)
    positions = jnp.arange(h.shape[1])
    enc_h = _run_encoder(params, cfg, enc_embeds) if cfg.is_enc_dec else None

    # the scan writes each block's output into the stacked hidden taps at
    # this scope: ``blocks/while/body/dynamic_update_slice``
    with jax.named_scope("blocks"):
        if cfg.block_pattern in ("dense", "moe"):
            def body(carry, pl):
                out, _ = _dense_body(pl, cfg, carry, positions, enc_h=enc_h)
                return out, out if collect_hidden else None
            if n_lead_blocks(cfg):
                h, hidden0 = jax.lax.scan(_maybe_remat(body, cfg), h,
                                          params["dense_blocks"])
            h, hidden = jax.lax.scan(_maybe_remat(body, cfg), h, params["blocks"],
                                     unroll=cfg.unroll_layers)
            if n_lead_blocks(cfg) and collect_hidden:
                hidden = jnp.concatenate([hidden0, hidden])
        elif cfg.block_pattern == "ssm":
            def body(carry, pl):
                out, _ = _ssm_body(pl, cfg, carry)
                return out, out if collect_hidden else None
            h, hidden = jax.lax.scan(_maybe_remat(body, cfg), h, params["blocks"],
                                     unroll=cfg.unroll_layers)
        elif cfg.block_pattern == "hybrid":
            h, hidden = _hybrid_forward(params, cfg, h, positions, collect_hidden)
        else:
            raise ValueError(cfg.block_pattern)

    logits = lm_head_logits(params, cfg, h)
    if collect_hidden:
        return logits, hidden
    return logits


def _hybrid_forward(params, cfg: ModelConfig, h, positions, collect_hidden):
    """Zamba2-style: every ``period``-th block is a SHARED attn+mlp block."""
    period = cfg.hybrid_period
    n_m, n_a = cfg.hybrid_layout()
    per_group = period - 1
    n_group_m = n_a * per_group
    shared = params["shared"]

    grouped = jax.tree.map(lambda x: x[:n_group_m].reshape((n_a, per_group) + x.shape[1:]),
                           params["blocks"])
    tail = jax.tree.map(lambda x: x[n_group_m:], params["blocks"])

    def mamba_scan(h, stack):
        def body(carry, pl):
            out, _ = _ssm_body(pl, cfg, carry)
            return out, None
        h, _ = jax.lax.scan(_maybe_remat(body, cfg), h, stack, unroll=cfg.unroll_layers)
        return h

    def group_body(carry, group_params):
        h = mamba_scan(carry, group_params)
        h, _ = _dense_body(shared, cfg, h, positions)
        return h, h if collect_hidden else None

    h, hidden = jax.lax.scan(_maybe_remat(group_body, cfg), h, grouped, unroll=cfg.unroll_layers)
    if n_m - n_group_m > 0:
        h = mamba_scan(h, tail)
    return h, hidden


def lm_loss(logits, labels, vocab_size: int, ignore_id: int = -1):
    """Mean next-token CE; positions with label == ignore_id are masked;
    padded vocab ids are masked out of the softmax."""
    V = logits.shape[-1]
    if V > vocab_size:
        mask = jnp.arange(V) < vocab_size
        logits = jnp.where(mask[None, None, :], logits, L.NEG_INF)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    valid = labels != ignore_id
    safe = jnp.where(valid, labels, 0)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1)


# ---------------------------------------------------------------------------
# Decode (KV cache / SSM state)
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    dt = dtype or jnp.dtype(cfg.compute_dtype)
    hd = cfg.resolved_head_dim

    def kv(n_l):
        if cfg.mla is not None:   # MLA caches the latent and the roped key
            m = cfg.mla
            return {"c_kv": jnp.zeros((n_l, batch, max_len, m.kv_lora_rank), dt),
                    "k_pe": jnp.zeros((n_l, batch, max_len, m.qk_rope_head_dim),
                                      dt)}
        if cfg.kv_cache_dtype == "int8":
            return {
                "k": jnp.zeros((n_l, batch, max_len, cfg.n_kv_heads, hd), jnp.int8),
                "v": jnp.zeros((n_l, batch, max_len, cfg.n_kv_heads, hd), jnp.int8),
                "k_scale": jnp.zeros((n_l, batch, max_len, cfg.n_kv_heads), dt),
                "v_scale": jnp.zeros((n_l, batch, max_len, cfg.n_kv_heads), dt),
            }
        return {
            "k": jnp.zeros((n_l, batch, max_len, cfg.n_kv_heads, hd), dt),
            "v": jnp.zeros((n_l, batch, max_len, cfg.n_kv_heads, hd), dt),
        }

    if cfg.block_pattern in ("dense", "moe"):
        cache = kv(cfg.n_layers)
        if cfg.is_enc_dec:
            cache["cross"] = None  # filled at prefill from encoder output
        return cache
    if cfg.block_pattern == "ssm":
        return jax.vmap(lambda _: SSM.init_ssm_state(cfg, batch, dt))(jnp.arange(cfg.n_layers))
    if cfg.block_pattern == "hybrid":
        n_m, n_a = cfg.hybrid_layout()
        return {
            "ssm": jax.vmap(lambda _: SSM.init_ssm_state(cfg, batch, dt))(jnp.arange(n_m)),
            "attn": kv(n_a),
        }
    raise ValueError(cfg.block_pattern)


def decode_step(params, cfg: ModelConfig, tokens, cache, index):
    """One decode step. tokens: (B, 1) int32; index: scalar int32 (position).

    Returns (logits (B, 1, V), new_cache).
    """
    h = embed_tokens(params, cfg, tokens, index + jnp.arange(1))
    positions = index + jnp.arange(1)

    if cfg.block_pattern in ("dense", "moe"):
        cross = cache.get("cross") if isinstance(cache, dict) else None

        def body(carry, xs):
            if cross is not None:
                pl, c, xkv = xs
                out, nc = _dense_body_cached_cross(pl, cfg, carry, positions, c, index, xkv)
            else:
                pl, c = xs
                out, nc = _dense_body(pl, cfg, carry, positions, cache=c, cache_index=index)
            return out, nc

        kv_slices = {k: v for k, v in cache.items() if k != "cross"}
        if cross is not None:
            h, new_kv = jax.lax.scan(body, h, (params["blocks"], kv_slices, cross),
                                     unroll=cfg.unroll_layers)
            new_cache = {**new_kv, "cross": cross}
        else:
            h, new_cache = _scan_cached(body, h, params, kv_slices, cfg)
    elif cfg.block_pattern == "ssm":
        def body(carry, xs):
            pl, st = xs
            out, ns = _ssm_body(pl, cfg, carry, state=st, decode=True)
            return out, ns
        h, new_cache = jax.lax.scan(body, h, (params["blocks"], cache), unroll=cfg.unroll_layers)
    elif cfg.block_pattern == "hybrid":
        h, new_cache = _hybrid_decode(params, cfg, h, positions, cache, index)
    else:
        raise ValueError(cfg.block_pattern)

    return lm_head_logits(params, cfg, h), new_cache


def _scan_cached(body, h, params, cache, cfg: ModelConfig):
    """Scan ``body(carry, (block params, block cache))`` over the stack; a MoE
    stack's leading dense blocks take the first ``n_lead_blocks`` layers of
    every cache leaf, the scanned blocks the rest."""
    n = n_lead_blocks(cfg)
    if not n:
        return jax.lax.scan(body, h, (params["blocks"], cache),
                            unroll=cfg.unroll_layers)
    h, c0 = jax.lax.scan(body, h, (params["dense_blocks"],
                                   jax.tree.map(lambda x: x[:n], cache)))
    h, c1 = jax.lax.scan(body, h, (params["blocks"],
                                   jax.tree.map(lambda x: x[n:], cache)),
                         unroll=cfg.unroll_layers)
    return h, jax.tree.map(lambda a, b: jnp.concatenate([a, b]), c0, c1)


def _hybrid_decode(params, cfg: ModelConfig, h, positions, cache, index):
    period = cfg.hybrid_period
    n_m, n_a = cfg.hybrid_layout()
    per_group = period - 1
    n_group_m = n_a * per_group
    shared = params["shared"]

    grouped_p = jax.tree.map(lambda x: x[:n_group_m].reshape((n_a, per_group) + x.shape[1:]),
                             params["blocks"])
    tail_p = jax.tree.map(lambda x: x[n_group_m:], params["blocks"])
    grouped_s = jax.tree.map(lambda x: x[:n_group_m].reshape((n_a, per_group) + x.shape[1:]),
                             cache["ssm"])
    tail_s = jax.tree.map(lambda x: x[n_group_m:], cache["ssm"])

    def mamba_scan(h, stack, states):
        def body(carry, xs):
            pl, st = xs
            out, ns = _ssm_body(pl, cfg, carry, state=st, decode=True)
            return out, ns
        return jax.lax.scan(body, h, (stack, states), unroll=cfg.unroll_layers)

    def group_body(carry, xs):
        gp, gs, ac = xs
        h, new_gs = mamba_scan(carry, gp, gs)
        h, new_ac = _dense_body(shared, cfg, h, positions, cache=ac, cache_index=index)
        return h, (new_gs, new_ac)

    h, (new_grouped_s, new_attn) = jax.lax.scan(
        group_body, h, (grouped_p, grouped_s, cache["attn"]),
        unroll=cfg.unroll_layers)
    if n_m - n_group_m > 0:
        h, new_tail_s = mamba_scan(h, tail_p, tail_s)
    else:
        new_tail_s = tail_s
    new_ssm = jax.tree.map(
        lambda a, b: jnp.concatenate([a.reshape((n_group_m,) + a.shape[2:]), b], axis=0),
        new_grouped_s, new_tail_s)
    return h, {"ssm": new_ssm, "attn": new_attn}


def prefill(params, cfg: ModelConfig, tokens, max_len: int, *, enc_embeds=None,
            vision_embeds=None):
    """Process a prompt, building the cache. Returns (logits, cache).

    For simplicity the prefill recomputes per-layer K/V into a fresh cache via
    the same block bodies with cache writes at index 0.
    """
    B, S = tokens.shape
    cache = init_cache(cfg, B, max_len)
    h = embed_tokens(params, cfg, tokens, jnp.arange(S))
    if vision_embeds is not None:
        h = jnp.concatenate([vision_embeds.astype(h.dtype), h], axis=1)
    positions = jnp.arange(h.shape[1])

    if cfg.block_pattern in ("dense", "moe"):
        if cfg.is_enc_dec:
            enc_h = _run_encoder(params, cfg, enc_embeds)

            def xkv_of(pl):
                pl = dequant_tree(pl, jnp.dtype(cfg.compute_dtype))
                return L.cross_kv(pl["xattn"], cfg, enc_h)
            cross = jax.lax.map(xkv_of, params["blocks"])

            def body(carry, xs):
                pl, c, xkv = xs
                out, nc = _dense_body_cached_cross(pl, cfg, carry, positions, c, 0, xkv)
                return out, nc
            kv = {k: v for k, v in cache.items() if k != "cross"}
            h, new_kv = jax.lax.scan(body, h, (params["blocks"], kv, cross),
                                     unroll=cfg.unroll_layers)
            new_cache = {**new_kv, "cross": cross}
        else:
            def body(carry, xs):
                pl, c = xs
                out, nc = _dense_body(pl, cfg, carry, positions, cache=c, cache_index=0)
                return out, nc
            h, new_cache = _scan_cached(body, h, params, cache, cfg)
    elif cfg.block_pattern == "ssm":
        # full-sequence forward capturing each layer's final SSD + conv state
        def body(carry, xs):
            pl, st = xs
            pl = dequant_tree(pl, jnp.dtype(cfg.compute_dtype))
            s_in = L.apply_norm(carry, pl["ln1"], cfg.norm)
            out, fs = SSM.ssm_forward(pl["ssm"], cfg, s_in, return_state=True)
            return carry + out, fs
        h, new_cache = jax.lax.scan(body, h, (params["blocks"], cache), unroll=cfg.unroll_layers)
    elif cfg.block_pattern == "hybrid":
        h, new_cache = _hybrid_prefill(params, cfg, h, positions, max_len)
    else:
        raise ValueError(cfg.block_pattern)

    return lm_head_logits(params, cfg, h), new_cache


def _hybrid_prefill(params, cfg: ModelConfig, h, positions, max_len: int):
    """Full-sequence hybrid pass capturing SSM states + shared-attn KV cache."""
    period = cfg.hybrid_period
    n_m, n_a = cfg.hybrid_layout()
    per_group = period - 1
    n_group_m = n_a * per_group
    shared = params["shared"]
    B = h.shape[0]
    dt = jnp.dtype(cfg.compute_dtype)

    grouped_p = jax.tree.map(lambda x: x[:n_group_m].reshape((n_a, per_group) + x.shape[1:]),
                             params["blocks"])
    tail_p = jax.tree.map(lambda x: x[n_group_m:], params["blocks"])

    def mamba_scan_state(h, stack):
        def body(carry, pl):
            pl = dequant_tree(pl, dt)
            s_in = L.apply_norm(carry, pl["ln1"], cfg.norm)
            out, fs = SSM.ssm_forward(pl["ssm"], cfg, s_in, return_state=True)
            return carry + out, fs
        return jax.lax.scan(body, h, stack, unroll=cfg.unroll_layers)

    empty_kv = _single_kv(cfg, B, max_len, dt)

    def group_body(carry, gp):
        h = carry
        h, gs = mamba_scan_state(h, gp)
        h, nc = _dense_body(shared, cfg, h, positions, cache=empty_kv, cache_index=0)
        return h, (gs, nc)

    h, (grouped_states, attn_caches) = jax.lax.scan(group_body, h, grouped_p,
                                                    unroll=cfg.unroll_layers)
    if n_m - n_group_m > 0:
        h, tail_states = mamba_scan_state(h, tail_p)
        ssm_states = jax.tree.map(
            lambda a, b: jnp.concatenate(
                [a.reshape((n_group_m,) + a.shape[2:]), b], axis=0),
            grouped_states, tail_states)
    else:
        ssm_states = jax.tree.map(
            lambda a: a.reshape((n_group_m,) + a.shape[2:]), grouped_states)
    return h, {"ssm": ssm_states, "attn": attn_caches}


# ---------------------------------------------------------------------------
# Quantizable-leaf selection
# ---------------------------------------------------------------------------

_QUANT_KEYS = ("wq", "wk", "wv", "wo", "wkv_a", "wkv_b", "up", "gate", "down",
               "w_z", "w_x", "out_proj")
_SKIP_SUBSTR = ("embed", "ln", "norm", "router", "conv", "bias")


def quantizable_paths(params) -> list:
    """Paths (tuples of keys) of weight leaves the PTQ methods quantize."""
    out = []

    def walk(tree, path):
        if isinstance(tree, dict):
            for k, v in tree.items():
                walk(v, path + (k,))
            return
        key = path[-1]
        if key in _QUANT_KEYS and not any(s in str(p) for p in path for s in _SKIP_SUBSTR):
            if hasattr(tree, "ndim") and tree.ndim >= 2:
                out.append(path)

    walk(params, ())
    return out
