"""Shared neural building blocks: norms, RoPE, blocked attention, latent
attention (MLA), MLP, MoE."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.models.config import ModelConfig

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x, w, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def layernorm(x, w, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return (((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)) * w + b


def apply_norm(x, p, kind, eps=None):
    """``eps`` None keeps each kind's default (1e-5 layernorm, 1e-6 rms)."""
    if kind == "layernorm":
        return layernorm(x, p["w"], p["b"], 1e-5 if eps is None else eps)
    return rmsnorm(x, p["w"], 1e-6 if eps is None else eps)


def init_norm(d, kind, dtype):
    p = {"w": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["b"] = jnp.zeros((d,), dtype)
    return p


def activation_fn(name):
    return {"relu": jax.nn.relu, "silu": jax.nn.silu, "gelu": jax.nn.gelu}[name]


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_cos_sin(positions, head_dim, theta):
    """positions: (..., S) int -> cos/sin (..., S, head_dim//2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (B, S, H, Dh); cos/sin: (B, S, Dh//2) or (S, Dh//2)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    if cos.ndim == 2:
        cos = cos[None]
        sin = sin[None]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Blocked (online-softmax) attention — memory O(S·chunk), GQA-aware
# ---------------------------------------------------------------------------

def blocked_attention(q, k, v, *, causal: bool, q_offset=0, kv_len=None,
                      chunk: int = 1024, unroll: bool = False,
                      softmax_dtype=jnp.float32, repeat_kv: bool = False,
                      k_scale=None, v_scale=None):
    """q/k: (B, S, H, Dh); v: (B, Sk, Hkv, Dv). Returns (B, Sq, Hq, Dv).

    Streams KV in chunks with an online softmax (flash-attention recurrence),
    so the (Sq, Sk) logit matrix is never materialised — required for the
    32k/500k shapes. ``q_offset`` is the absolute position of q[0] (decode);
    ``kv_len`` masks cache positions >= kv_len.
    """
    B, Sq, Hq, Dh = q.shape
    Sk, Hkv, Dv = k.shape[1], k.shape[2], v.shape[3]
    if repeat_kv and Hkv < Hq:
        # §Perf: keep scores HEAD-SHARDED under TP — the (Hkv, rep) reshape of
        # a head-sharded q axis defeats GSPMD propagation; repeating KV to Hq
        # heads costs (rep/model_shards)x KV reads but shards all score math.
        rep0 = Hq // Hkv
        k = jnp.repeat(k, rep0, axis=2)
        v = jnp.repeat(v, rep0, axis=2)
        if k_scale is not None:
            k_scale = jnp.repeat(k_scale, rep0, axis=2)
            v_scale = jnp.repeat(v_scale, rep0, axis=2)
        Hkv = Hq
    rep = Hq // Hkv
    sdt = jnp.dtype(softmax_dtype)
    scale = Dh ** -0.5
    qg = q.reshape(B, Sq, Hkv, rep, Dh).astype(sdt) * jnp.asarray(scale, sdt)

    chunk = min(chunk, Sk)
    if Sk % chunk != 0:  # pad KV to a chunk multiple; padding is masked out
        pad = chunk - Sk % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if k_scale is not None:
            k_scale = jnp.pad(k_scale, ((0, 0), (0, pad), (0, 0)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, pad), (0, 0)))
        Sk_pad = Sk + pad
    else:
        Sk_pad = Sk
    n_chunks = Sk_pad // chunk
    if kv_len is None:
        kv_len = Sk
    kc = k.reshape(B, n_chunks, chunk, Hkv, Dh).swapaxes(0, 1)
    vc = v.reshape(B, n_chunks, chunk, Hkv, Dv).swapaxes(0, 1)
    scale_xs = None
    if k_scale is not None:  # int8 KV cache: per-(pos, head) absmax scales
        scale_xs = (k_scale.reshape(B, n_chunks, chunk, Hkv).swapaxes(0, 1),
                    v_scale.reshape(B, n_chunks, chunk, Hkv).swapaxes(0, 1))

    q_pos = q_offset + jnp.arange(Sq)
    neg = jnp.asarray(NEG_INF if sdt == jnp.float32 else -3e38, jnp.float32).astype(sdt)

    def body(carry, xs):
        m, l, acc = carry
        if scale_xs is not None:
            kb, vb, ksb, vsb, start = xs
            kb = kb.astype(sdt) * ksb[..., None].astype(sdt)
            vb = vb.astype(sdt) * vsb[..., None].astype(sdt)
        else:
            kb, vb, start = xs
        # logits: (B, Hkv, rep, Sq, chunk)
        s = jnp.einsum("bqhrd,bkhd->bhrqk", qg, kb.astype(sdt),
                       preferred_element_type=sdt)
        k_pos = start + jnp.arange(chunk)
        mask = (k_pos[None, :] < kv_len)
        if causal:
            mask = mask & (q_pos[:, None] >= k_pos[None, :])
        s = jnp.where(mask[None, None, None], s, neg)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1).astype(jnp.float32))
        p = jnp.exp(s - m_new[..., None].astype(sdt))  # (m is small: no Sk dim)
        p = jnp.where(mask[None, None, None], p, jnp.zeros((), sdt))
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1).astype(jnp.float32)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhrqk,bkhd->bhrqd", p, vb.astype(sdt),
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hkv, rep, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, rep, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, rep, Sq, Dv), jnp.float32)
    starts = jnp.arange(n_chunks) * chunk
    xs = (kc, vc) + (scale_xs if scale_xs is not None else ()) + (starts,)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), xs, unroll=unroll)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention block (GQA, optional qk-norm / rope / biases)
# ---------------------------------------------------------------------------

def init_attn(key, cfg: ModelConfig, dtype):
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    sd = d ** -0.5
    p = {
        "wq": jax.random.normal(ks[0], (d, hq * hd), dtype) * sd,
        "wk": jax.random.normal(ks[1], (d, hkv * hd), dtype) * sd,
        "wv": jax.random.normal(ks[2], (d, hkv * hd), dtype) * sd,
        "wo": jax.random.normal(ks[3], (hq * hd, d), dtype) * (hq * hd) ** -0.5,
    }
    if cfg.attn_qkv_bias or cfg.use_bias:
        p["bq"] = jnp.zeros((hq * hd,), dtype)
        p["bk"] = jnp.zeros((hkv * hd,), dtype)
        p["bv"] = jnp.zeros((hkv * hd,), dtype)
    if cfg.use_bias:
        p["bo"] = jnp.zeros((d,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def quantize_kv(k, v):
    """int8 KV quantize-on-write: absmax scale per (..., head) over head_dim.

    The single source of the cache quantization convention — the contiguous
    cache (``self_attention``) and the paged pool writer
    (``repro.serving.decode``) must stay bit-identical or their documented
    tolerances diverge. Returns (k_int8, v_int8, k_scale, v_scale).
    """
    ks = jnp.max(jnp.abs(k), axis=-1) / 127.0 + 1e-8
    vs = jnp.max(jnp.abs(v), axis=-1) / 127.0 + 1e-8
    kq = jnp.round(k / ks[..., None]).astype(jnp.int8)
    vq = jnp.round(v / vs[..., None]).astype(jnp.int8)
    return kq, vq, ks, vs


def attn_qkv(p, cfg: ModelConfig, x, positions):
    """Project + rope. Returns q, k, v as (B, S, H, Dh)."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    if cfg.pos_emb == "rope":
        cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def attn_out(p, x_attn, cfg: ModelConfig):
    B, S = x_attn.shape[:2]
    out = x_attn.reshape(B, S, -1) @ p["wo"]
    if "bo" in p:
        out = out + p["bo"]
    return out


def self_attention(p, cfg: ModelConfig, x, positions, *, causal=True, cache=None,
                   cache_index=None):
    """Full self-attention block body (no norm / residual).

    cache: optional dict {"k": (B, Smax, Hkv, Dh), "v": ..., } updated at
    ``cache_index`` (decode path).
    """
    q, k, v = attn_qkv(p, cfg, x, positions)
    sdt = jnp.dtype(cfg.attn_softmax_dtype)
    kw = dict(chunk=cfg.attn_chunk, unroll=cfg.unroll_inner,
              softmax_dtype=sdt, repeat_kv=cfg.gqa_repeat_kv)
    if cache is not None:
        def upd(buf, val):
            return jax.lax.dynamic_update_slice_in_dim(
                buf, val.astype(buf.dtype), cache_index, axis=1)

        if cfg.kv_cache_dtype == "int8":
            # quantize on write, dequant per chunk at read
            kq, vq, ks, vs = quantize_kv(k, v)
            new_cache = {"k": upd(cache["k"], kq), "v": upd(cache["v"], vq),
                         "k_scale": upd(cache["k_scale"], ks),
                         "v_scale": upd(cache["v_scale"], vs)}
            kw.update(k_scale=new_cache["k_scale"], v_scale=new_cache["v_scale"])
        else:
            new_cache = {"k": upd(cache["k"], k), "v": upd(cache["v"], v)}
        kv_len = cache_index + k.shape[1]
        if cfg.use_flash_decode and q.shape[1] == 1:
            out = _flash_decode_attention(cfg, q, new_cache, kv_len)
        else:
            out = blocked_attention(q, new_cache["k"], new_cache["v"],
                                    causal=causal, q_offset=cache_index,
                                    kv_len=kv_len, **kw)
        return attn_out(p, out, cfg), new_cache
    return attn_out(p, _full_attention(q, k, v, causal, kw), cfg), None


# Matmul precisions at which a TPU matmul of float32 inputs takes one
# bfloat16 pass: the arithmetic of the flash-attention kernel.
_ONE_BF16_PASS = (None, "default", "bfloat16", "BF16_BF16_F32")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def flash_attention(q, k, v, causal):
    """``blocked_attention`` of whole sequences (no offset, no cache) through
    the Pallas kernel (``ops.flash_attention``), differentiable: the
    backward is ``blocked_attention``'s VJP on the same inputs."""
    from repro.kernels import ops  # local import: kernels are optional
    return ops.flash_attention(q, k, v, causal=causal)


def _flash_attention_fwd(q, k, v, causal):
    return flash_attention(q, k, v, causal), (q, k, v)


def _flash_attention_bwd(causal, res, g):
    _, vjp = jax.vjp(
        lambda q, k, v: blocked_attention(q, k, v, causal=causal), *res)
    return vjp(g)


flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def _full_attention(q, k, v, causal, kw):
    """No-cache attention over whole sequences: ``flash_attention`` on a TPU
    where the kernel can take it, else ``blocked_attention``. The kernel's
    arithmetic is a float32 softmax over one bfloat16 pass per matmul, so it
    is taken only at that softmax dtype and matmul precision; a Mosaic
    kernel cannot be partitioned, so only in a program on one device
    (``ops.one_device_program``); and only for a shape it can tile (a shape
    it cannot warns once). ``attention_path_total{path}`` counts the choice
    once per trace."""
    from repro.kernels import ops  # local import: kernels are optional
    flash = (ops.on_tpu() and kw["softmax_dtype"] == jnp.float32
             and jax.config.jax_default_matmul_precision in _ONE_BF16_PASS
             and ops.one_device_program()
             and not ops.flash_attention_refusal(q.shape, k.shape, v.shape))
    obs.counter("attention_path_total",
                "Traced no-cache attention calls, by kernel path").inc(
                    path="flash" if flash else "blocked")
    if flash:
        return flash_attention(q, k, v, causal)
    return blocked_attention(q, k, v, causal=causal, **kw)


def _flash_decode_attention(cfg: ModelConfig, q, cache, kv_len):
    """Route a single decode token through the fused Pallas kernel
    (kernels/flash_decode.py) — GQA heads repeated into the kernel call,
    int8 caches dequantized in-register."""
    from repro.kernels import flash_decode  # local import: kernels are optional
    B, _, Hq, Dh = q.shape
    k, v = cache["k"], cache["v"]
    ks, vs = cache.get("k_scale"), cache.get("v_scale")
    rep = Hq // k.shape[2]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        if ks is not None:
            ks = jnp.repeat(ks, rep, axis=2)
            vs = jnp.repeat(vs, rep, axis=2)
    # kernel expects a static kv_len; decode at a traced index falls back to
    # full-length attention with zero-filled (masked-by-softmax-zero) slots:
    # unwritten cache rows are zeros -> exp(0-scores) contributes; so instead
    # mask via the scales path when quantized, else pass kv_len=None only if
    # the cache is fully written. We keep correctness by computing over the
    # whole buffer with -inf masking inside the kernel when kv_len is static.
    kv_len_static = int(kv_len) if not isinstance(kv_len, jax.core.Tracer) else None
    if kv_len_static is None:
        # dynamic position: use the jnp online-softmax path (kernel needs a
        # static mask bound) — still benefits from int8 dequant-in-chunk.
        return blocked_attention(q, cache["k"], cache["v"], causal=True,
                                 q_offset=kv_len - 1, kv_len=kv_len,
                                 chunk=cfg.attn_chunk,
                                 softmax_dtype=jnp.dtype(cfg.attn_softmax_dtype),
                                 k_scale=ks if rep == 1 else cache.get("k_scale"),
                                 v_scale=vs if rep == 1 else cache.get("v_scale"))
    out = flash_decode(q[:, 0], k, v, ks, vs, kv_len=kv_len_static,
                       chunk=min(512, k.shape[1]))
    return out[:, None].astype(q.dtype)


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V3, no q-LoRA)
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig, dtype):
    d, h, m = cfg.d_model, cfg.n_heads, cfg.mla
    r = m.kv_lora_rank
    ks = jax.random.split(key, 4)
    return {
        "wq": jax.random.normal(ks[0], (d, h * m.qk_head_dim), dtype) * d ** -0.5,
        "wkv_a": jax.random.normal(
            ks[1], (d, r + m.qk_rope_head_dim), dtype) * d ** -0.5,
        "kv_norm": {"w": jnp.ones((r,), dtype)},
        "wkv_b": jax.random.normal(
            ks[2], (r, h * (m.qk_nope_head_dim + m.v_head_dim)), dtype) * r ** -0.5,
        "wo": jax.random.normal(ks[3], (h * m.v_head_dim, d), dtype)
        * (h * m.v_head_dim) ** -0.5,
    }


def mla_latent(p, cfg: ModelConfig, x, positions):
    """What MLA caches per token: the normalized latent ``c_kv`` (B, S, R)
    and the one roped key head ``k_pe`` (B, S, Dr) all heads share."""
    m = cfg.mla
    r = m.kv_lora_rank
    kv_a = x @ p["wkv_a"]
    c_kv = apply_norm(kv_a[..., :r], p["kv_norm"], "rmsnorm", cfg.norm_eps)
    cos, sin = rope_cos_sin(positions, m.qk_rope_head_dim, cfg.rope_theta)
    k_pe = apply_rope(kv_a[..., None, r:], cos, sin)[:, :, 0]
    return c_kv, k_pe


def mla_attention(p, cfg: ModelConfig, x, positions, *, causal=True,
                  cache=None, cache_index=None):
    """MLA block body (no norm / residual): q = x W_q (heads of nope + rope),
    [c_kv, k_pe] = x W_kva, c_kv normalized, [k_nope, v] = c_kv W_kvb, rope
    on q_pe and on the shared k_pe, softmax scale (nope + rope)^-0.5.

    cache: optional {"c_kv": (B, Smax, R), "k_pe": (B, Smax, Dr)} written at
    ``cache_index``; keys and values are expanded from the whole latent
    cache through W_kvb."""
    B, S, _ = x.shape
    m, H = cfg.mla, cfg.n_heads
    nope, rope = m.qk_nope_head_dim, m.qk_rope_head_dim
    q = (x @ p["wq"]).reshape(B, S, H, m.qk_head_dim)
    cos, sin = rope_cos_sin(positions, rope, cfg.rope_theta)
    q = jnp.concatenate([q[..., :nope], apply_rope(q[..., nope:], cos, sin)],
                        axis=-1)
    c_kv, k_pe = mla_latent(p, cfg, x, positions)
    new_cache = None
    if cache is not None:
        def upd(buf, val):
            return jax.lax.dynamic_update_slice_in_dim(
                buf, val.astype(buf.dtype), cache_index, axis=1)
        new_cache = {"c_kv": upd(cache["c_kv"], c_kv),
                     "k_pe": upd(cache["k_pe"], k_pe)}
        c_kv, k_pe = new_cache["c_kv"], new_cache["k_pe"]
    Sk = c_kv.shape[1]
    kv = (c_kv @ p["wkv_b"]).reshape(B, Sk, H, nope + m.v_head_dim)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe[:, :, None], (B, Sk, H, rope))],
        axis=-1)
    v = kv[..., nope:]
    kw = dict(chunk=cfg.attn_chunk, unroll=cfg.unroll_inner,
              softmax_dtype=jnp.dtype(cfg.attn_softmax_dtype))
    if cache is not None:
        out = blocked_attention(q, k, v, causal=causal, q_offset=cache_index,
                                kv_len=cache_index + S, **kw)
    else:
        out = _full_attention(q, k, v, causal, kw)
    return attn_out(p, out, cfg), new_cache


def cross_attention(p, cfg: ModelConfig, x, enc_kv):
    """Decoder cross-attention; enc_kv = (k, v) precomputed from encoder."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = x @ p["wq"]
    if "bq" in p:
        q = q + p["bq"]
    q = q.reshape(B, S, cfg.n_heads, hd)
    k, v = enc_kv
    out = blocked_attention(q, k, v, causal=False, chunk=cfg.attn_chunk, unroll=cfg.unroll_inner)
    return attn_out(p, out, cfg)


def cross_kv(p, cfg: ModelConfig, enc_h):
    B, S, _ = enc_h.shape
    hd = cfg.resolved_head_dim
    k = enc_h @ p["wk"]
    v = enc_h @ p["wv"]
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    return (k.reshape(B, S, cfg.n_kv_heads, hd), v.reshape(B, S, cfg.n_kv_heads, hd))


# ---------------------------------------------------------------------------
# MLP (dense FFN)
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, dtype, d_ff=None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {
        "up": jax.random.normal(ks[0], (d, f), dtype) * d ** -0.5,
        "down": jax.random.normal(ks[1], (f, d), dtype) * f ** -0.5,
    }
    if cfg.gated_mlp:
        p["gate"] = jax.random.normal(ks[2], (d, f), dtype) * d ** -0.5
    if cfg.use_bias:
        p["b_up"] = jnp.zeros((f,), dtype)
        p["b_down"] = jnp.zeros((d,), dtype)
        if cfg.gated_mlp:
            p["b_gate"] = jnp.zeros((f,), dtype)
    return p


def mlp(p, cfg: ModelConfig, x):
    act = activation_fn(cfg.activation)
    up = x @ p["up"]
    if "b_up" in p:
        up = up + p["b_up"]
    if cfg.gated_mlp:
        g = x @ p["gate"]
        if "b_gate" in p:
            g = g + p["b_gate"]
        h = act(g) * up
    else:
        h = act(up)
    out = h @ p["down"]
    if "b_down" in p:
        out = out + p["b_down"]
    return out


# ---------------------------------------------------------------------------
# MoE: routing rules, capacity or dropless grouped dispatch, shared experts
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig, dtype):
    """Router over all ``num_experts``; expert weights only for the experts
    held here (``moe.held``); the shared experts as one MLP."""
    d, f, m = cfg.d_model, cfg.d_ff, cfg.moe
    e = m.held[1]
    ks = jax.random.split(key, 5 if m.n_shared_experts else 4)
    p = {
        "router": jax.random.normal(ks[0], (d, m.num_experts), dtype) * d ** -0.5,
        "up": jax.random.normal(ks[1], (e, d, f), dtype) * d ** -0.5,
        "down": jax.random.normal(ks[2], (e, f, d), dtype) * f ** -0.5,
    }
    if cfg.gated_mlp:
        p["gate"] = jax.random.normal(ks[3], (e, d, f), dtype) * d ** -0.5
    if m.router == "sigmoid_noaux":   # e_score_correction_bias
        p["router_bias"] = jnp.zeros((m.num_experts,), dtype)
    if m.n_shared_experts:
        p["shared"] = init_mlp(ks[4], cfg, dtype, d_ff=m.n_shared_experts * f)
    return p


def moe_route(p, cfg: ModelConfig, x):
    """(weights, expert ids), each (B, S, top_k), over all ``num_experts``.

    ``softmax_topk``: the top-k router logits, softmaxed among themselves.
    ``sigmoid_noaux`` (DeepSeek-V3 ``noaux_tc``, one group): float32 logits at
    HIGHEST precision, scores s = sigmoid(logits); the experts are the top-k
    of s + router_bias, weighted by their s alone, normalized to sum 1 where
    ``norm_topk_prob``, times ``routed_scaling_factor``."""
    m = cfg.moe
    if m.router == "sigmoid_noaux":
        logits = jnp.dot(x.astype(jnp.float32), p["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(s + p["router_bias"].astype(jnp.float32),
                               m.top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if m.norm_topk_prob:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return (w * m.routed_scaling_factor).astype(x.dtype), idx
    logits = x @ p["router"]                       # (B, S, E)
    gate_w, gate_idx = jax.lax.top_k(logits, m.top_k)
    gate_w = jax.nn.softmax(gate_w.astype(jnp.float32), axis=-1).astype(x.dtype)
    return gate_w, gate_idx


def moe_ffn(p, cfg: ModelConfig, x):
    """x: (B, S, D) -> the routed experts held here, weighted, plus the
    shared experts. Scopes ``moe.router``, ``moe.dispatch``,
    ``moe.experts``, ``moe.shared``; ``moe_dispatch_total{path}`` counts the
    dispatch once per trace."""
    with jax.named_scope("moe.router"):
        gate_w, gate_idx = moe_route(p, cfg, x)
    path = cfg.moe.dispatch
    obs.counter("moe_dispatch_total",
                "Traced MoE layer calls, by dispatch path").inc(path=path)
    if path == "grouped":
        out = _grouped_experts(p, cfg, x, gate_w, gate_idx)
    else:
        out = _capacity_experts(p, cfg, x, gate_w, gate_idx)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            out = out + mlp(p["shared"], cfg, x)
    return out


def _capacity_experts(p, cfg: ModelConfig, x, gate_w, gate_idx):
    """Positions/capacity computed PER ROW so the token cumsum never crosses
    the data-sharded batch axis (no serializing collectives); the
    (B, E, C, D) dispatch buffer resharded b:data -> e:model is the
    all-to-all under expert parallelism. Slots past the capacity drop."""
    B, S, D = x.shape
    mcfg = cfg.moe
    E, K = mcfg.num_experts, mcfg.top_k
    if mcfg.held != (0, E):
        raise ValueError("capacity dispatch computes every expert; a device "
                         "holding a share of them needs dispatch='grouped'")
    C = mcfg.capacity(S)
    act = activation_fn(cfg.activation)

    with jax.named_scope("moe.dispatch"):
        # slot layout: (B, S*K)
        e_idx = gate_idx.reshape(B, S * K)
        onehot = jax.nn.one_hot(e_idx, E, dtype=jnp.int32)          # (B, S*K, E)
        pos_all = jnp.cumsum(onehot, axis=1) - 1                     # (B, S*K, E)
        pos = jnp.take_along_axis(pos_all, e_idx[..., None], axis=2)[..., 0]
        keep = (pos < C)
        pos_c = jnp.clip(pos, 0, C - 1)

        x_slots = jnp.repeat(x, K, axis=1)                           # (B, S*K, D)
        x_slots = x_slots * keep[..., None].astype(x.dtype)
        b_iota = jnp.arange(B)[:, None] * jnp.ones((1, S * K), jnp.int32)
        buf = jnp.zeros((B, E, C, D), x.dtype).at[b_iota, e_idx, pos_c].add(x_slots)

    with jax.named_scope("moe.experts"):
        up = jnp.einsum("becd,edf->becf", buf, p["up"])
        if cfg.gated_mlp:
            g = jnp.einsum("becd,edf->becf", buf, p["gate"])
            h = act(g) * up
        else:
            h = act(up)
        out_buf = jnp.einsum("becf,efd->becd", h, p["down"])

    with jax.named_scope("moe.dispatch"):
        out_slots = out_buf[b_iota, e_idx, pos_c] * keep[..., None].astype(x.dtype)
        out = out_slots.reshape(B, S, K, D) * gate_w[..., None]
        return jnp.sum(out, axis=2)


def _mxu_operands(*xs):
    """The operands a float32 matmul feeds the MXU on a TPU at JAX's default
    precision (one bfloat16 pass), made explicit for ``ragged_dot``, whose
    kernel would otherwise multiply float32 in several passes. Unchanged
    elsewhere and at higher precisions."""
    from repro.kernels import ops  # local import: kernels are optional
    if ops.on_tpu() and jax.config.jax_default_matmul_precision in _ONE_BF16_PASS:
        return tuple(x.astype(jnp.bfloat16) for x in xs)
    return xs


def _dispatch_rows(tokens: int, top_k: int, held: int) -> int:
    """Rows of the sorted slot buffer: the most slots ``held`` experts can be
    sent (each token picks ``top_k`` distinct experts)."""
    return tokens * min(top_k, held)


def _dispatch_ladder(tokens: int, top_k: int, held: int,
                     num_experts: int) -> tuple[int, ...]:
    """Static row bounds of the sorted slot buffer, smallest first: 1.25x
    the held experts' even share of the slots rounded up to 512 rows, twice
    that, and ``_dispatch_rows``, each clipped to the last, duplicates
    dropped. The last bound drops no slot."""
    top = _dispatch_rows(tokens, top_k, held)
    even = -(-5 * tokens * top_k * held // (4 * num_experts))
    low = -(-even // 512) * 512
    return tuple(sorted({min(r, top) for r in (low, 2 * low, top)}))


def _grouped_experts(p, cfg: ModelConfig, x, gate_w, gate_idx):
    """Dropless dispatch to the experts held here: the token slots routed to
    them are sorted by expert and run through one grouped matmul
    (``jax.lax.ragged_dot``) per projection; slots routed to other experts
    add nothing.

    The sorted buffer takes the smallest bound of ``_dispatch_ladder`` that
    holds every live slot, picked on the device by ``lax.switch``, each
    branch under the scope ``moe.rows_<M>``; rows past the live ones add
    exact zeros, so every bound gives the same result. The largest bound
    is the most the held experts can be sent, so no slot is ever dropped.
    One bound (all experts held, or small shapes) emits no switch. Under
    ``vmap`` the switch becomes a select that runs every branch; the search
    evaluates its candidates under ``lax.map``, which keeps it a
    conditional. ``moe_dispatch_rows_total{rows}`` counts each traced
    bound."""
    B, S, D = x.shape
    m = cfg.moe
    K = m.top_k
    start, count = m.held
    T = B * S
    rungs = _dispatch_ladder(T, K, count, m.num_experts)
    act = activation_fn(cfg.activation)
    with jax.named_scope("moe.dispatch"):
        local = gate_idx.reshape(T * K) - start
        held = (local >= 0) & (local < count)
        group = jnp.where(held, local, count)         # others sort last
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=count + 1)[:count]

    def gmm(lhs, rhs):
        lhs, rhs = _mxu_operands(lhs, rhs)
        return jax.lax.ragged_dot(lhs, rhs, sizes,
                                  preferred_element_type=jnp.float32)

    def dispatch(M):
        obs.counter("moe_dispatch_rows_total",
                    "Traced grouped-dispatch row bounds").inc(rows=M)
        with jax.named_scope("moe.dispatch"):
            rows = order[:M]
            live = held[rows]
            tok = rows // K
            xs = jnp.take(x.reshape(T, D), tok, axis=0)
            ws = jnp.where(live, gate_w.reshape(T * K)[rows], 0)
        with jax.named_scope("moe.experts"):
            up = gmm(xs, p["up"])
            h = act(gmm(xs, p["gate"])) * up if cfg.gated_mlp else act(up)
            y = gmm(h, p["down"])
        with jax.named_scope("moe.dispatch"):
            y = jnp.where(live[:, None], y * ws[:, None], 0)
            return jnp.zeros((T, D), jnp.float32).at[tok].add(y)

    def branch(M):
        with jax.named_scope(f"moe.rows_{M}"):
            return dispatch(M)

    if len(rungs) == 1:
        out = dispatch(rungs[0])
    else:
        # the switch itself lies outside ``moe.dispatch``: its branches hold
        # ``moe.experts`` work too
        with jax.named_scope("moe.dispatch"):
            pick = jnp.sum(sizes.sum() > jnp.asarray(rungs[:-1]))
        out = jax.lax.switch(
            pick, [functools.partial(branch, M) for M in rungs])
    with jax.named_scope("moe.dispatch"):
        return out.reshape(B, S, D).astype(x.dtype)


def moe_aux_loss(p, cfg: ModelConfig, x):
    """Load-balancing auxiliary loss (Switch-style), used in training."""
    logits = x @ p["router"]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    E = cfg.moe.num_experts
    _, idx = jax.lax.top_k(logits, cfg.moe.top_k)
    frac_tokens = jnp.mean(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=(0, 1, 2))
    frac_probs = jnp.mean(probs, axis=(0, 1))
    return E * jnp.sum(frac_tokens * frac_probs)
