"""Plain reference of a DeepSeek-V3 stack (Moonlight-16B-A3B), independent of
the program.

Per layer: pre-RMSNorm multi-head latent attention (MLA) without q-LoRA,
then pre-RMSNorm FFN: a dense SwiGLU in the leading dense layers, in the
others the routed experts held here plus the shared experts. A final
RMSNorm and an untied LM head give the logits. Weights are RTN-quantized as
``reference.rtn`` does, and the search's transform of each 1408-wide unit is
the paper's permutation / scaling / Givens rotation on its hidden axis, with
the gate's columns permuted alongside (``transform_gated``).

Written from the published equations, in float32; callers run it under
``Precision.HIGHEST``. ``matmul_dtype`` rounds every matmul's inputs (the
control). Every expert held here is computed for every token and masked by
its routing weight, so no dispatch is shared with the program.

Departures from the published model, each also the program's:
- one chip's share: 8 of the 64 routed experts (``held``) and a slice of
  the vocabulary; the router scores all 64 experts, and tokens routed to the
  other 56 add nothing here (their chips would add it);
- 5 of 27 layers (the dense layer and 4 MoE layers);
- rope on the two halves of each rope head (rotate_half) where the
  checkpoint's layout interleaves pairs: with random weights the two differ
  by a fixed permutation of the rope columns of W_q and W_kva;
- float32 weights (the checkpoint is bfloat16);
- the search's units of the dense FFN are its 8 column blocks of 1408, and a
  permutation stays inside its block.

Imports nothing from the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import NEG, _givens, rtn, token_nll  # noqa: F401

ATTN_LEAVES = ("wq", "wkv_a", "wkv_b", "wo")
FFN_LEAVES = ("up", "gate", "down")


def _rms(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """Rotate the halves of x (..., S, H, d) by position (rotate_half)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv        # (S, d/2)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def _rounder(matmul_dtype):
    if matmul_dtype is None:
        return lambda x: x
    return lambda x: x.astype(matmul_dtype).astype(jnp.float32)


def mla(a, p, conf, r):
    """Latent attention of normalized input a (B, S, D)."""
    B, S, _ = a.shape
    H = conf["num_attention_heads"]
    nope, rope = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
    dv, R = conf["v_head_dim"], conf["kv_lora_rank"]
    pos = jnp.arange(S)
    q = (r(a) @ r(p["wq"])).reshape(B, S, H, nope + rope)
    q = jnp.concatenate([q[..., :nope],
                         _rope(q[..., nope:], pos, conf["rope_theta"])], -1)
    kv_a = r(a) @ r(p["wkv_a"])
    c = _rms(kv_a[..., :R], p["kv_norm"]["w"], conf["rms_norm_eps"])
    k_pe = _rope(kv_a[..., None, R:], pos, conf["rope_theta"])
    kv = (r(c) @ r(p["wkv_b"])).reshape(B, S, H, nope + dv)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (B, S, H, rope))], -1)
    v = kv[..., nope:]
    sc = jnp.einsum("bqhd,bkhd->bhqk", r(q), r(k)) * (nope + rope) ** -0.5
    sc = jnp.where(jnp.tril(jnp.ones((S, S), bool)), sc, NEG)
    pr = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", r(pr), r(v)).reshape(B, S, H * dv)
    return r(o) @ r(p["wo"])


def swiglu(m, p, r):
    h = jax.nn.silu(r(m) @ r(p["gate"])) * (r(m) @ r(p["up"]))
    return r(h) @ r(p["down"])


def route(m, p, conf, r):
    """Published noaux_tc gate with one group: (weights (B, S, E) with zeros
    off the chosen experts, chosen ids (B, S, k))."""
    logits = r(m) @ r(p["router"])
    s = jax.nn.sigmoid(logits)
    k = conf["num_experts_per_tok"]
    _, idx = jax.lax.top_k(s + p["router_bias"], k)
    chosen = jnp.sum(jax.nn.one_hot(idx, s.shape[-1]), axis=-2)     # (B,S,E)
    w = s * chosen
    if conf["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * conf["routed_scaling_factor"], idx


def moe(m, p, conf, r, held_start: int):
    """The held experts' weighted part plus the shared experts."""
    w, idx = route(m, p, conf, r)
    out = swiglu(m, p["shared"], r)
    for e in range(p["up"].shape[0]):
        ex = {k: p[k][e] for k in FFN_LEAVES}
        out = out + w[..., held_start + e, None] * swiglu(m, ex, r)
    return out, idx


def forward(params, tokens, conf, *, matmul_dtype=None, layer_fn=None,
            collect_hidden: bool = False, with_routing: bool = False):
    """Logits (B, S, V) of tokens (B, S); with ``collect_hidden`` the block
    outputs (L, B, S, D) too, with ``with_routing`` the chosen experts of
    each MoE layer (L - 1, B, S, k) too.

    ``layer_fn(kind, i, block_params) -> block_params`` rewrites a layer's
    weights before use; ``kind`` is "dense" (the leading layer, i = 0) or
    "moe" (i = 0.. of the MoE layers)."""
    r = _rounder(matmul_dtype)
    eps = conf["rms_norm_eps"]
    held_start = conf["experts_held_start"]
    h = params["embed"]["tok"][tokens]

    def block(h, p, ffn):
        h = h + mla(_rms(h, p["ln1"]["w"], eps), p["attn"], conf, r)
        f, idx = ffn(_rms(h, p["ln2"]["w"], eps), p)
        return h + f, idx

    def lead(h, xs):
        i, p = xs
        if layer_fn is not None:
            p = layer_fn("dense", i, p)
        h, _ = block(h, p, lambda m, p: (swiglu(m, p["mlp"], r), None))
        return h, h

    def moe_layer(h, xs):
        i, p = xs
        if layer_fn is not None:
            p = layer_fn("moe", i, p)
        h, idx = block(h, p, lambda m, p: moe(m, p["moe"], conf, r,
                                              held_start))
        return h, (h, idx)

    n_lead = params["dense_blocks"]["ln1"]["w"].shape[0]
    n_moe = params["blocks"]["ln1"]["w"].shape[0]
    h, hid0 = jax.lax.scan(lead, h, (jnp.arange(n_lead),
                                     params["dense_blocks"]))
    h, (hid1, idx) = jax.lax.scan(moe_layer, h, (jnp.arange(n_moe),
                                                 params["blocks"]))
    logits = r(_rms(h, params["final_norm"]["w"], eps)) @ r(params["lm_head"])
    out = (logits,)
    if collect_hidden:
        out += (jnp.concatenate([hid0, hid1]),)
    if with_routing:
        out += (idx,)
    return out if len(out) > 1 else logits


def transform_gated(up, gate, down, pi, s, phi):
    """The search's transform of one SwiGLU unit, written for ``x @ up``
    (D, F) and ``h @ down`` (F, D): up's columns rotated, scaled and
    permuted, down's rows by the inverse, the gate's columns permuted."""
    up = (_givens(up, phi, 1) * s[None, :])[:, pi]
    down = (_givens(down, phi, 0) / s[:, None])[pi, :]
    return up, gate[:, pi], down


def unit_counts(conf):
    """(units per dense layer, shared units per MoE layer, routed units per
    MoE layer): the order the search numbers its units in is all dense
    layers' blocks, then all MoE layers' shared experts, then all MoE
    layers' held experts, layer by layer."""
    F = conf["moe_intermediate_size"]
    return (conf["intermediate_size"] // F, conf["n_shared_experts"],
            conf["n_routed_experts"])


def _units(p, n, pi, s, phi, bits, group, F):
    """Split an MLP of width n*F into n units, transform and RTN each, and
    join them back."""
    parts = {k: [] for k in FFN_LEAVES}
    for j in range(n):
        cols = slice(j * F, (j + 1) * F)
        up, gate, down = transform_gated(p["up"][:, cols], p["gate"][:, cols],
                                         p["down"][cols], pi[j], s[j], phi[j])
        for k, w in (("up", up), ("gate", gate), ("down", down)):
            parts[k].append(rtn(w, bits, group))
    return {k: jnp.concatenate(v, axis=0 if k == "down" else 1)
            for k, v in parts.items()}


def searched_layer(conf, pi, s, phi):
    """``layer_fn`` of the searched model: MLA projections RTN-quantized,
    every FFN unit transformed by its (pi, s, phi) and RTN-quantized."""
    bits, group = conf["bits"], conf["group_size"]
    F = conf["moe_intermediate_size"]
    n_dense, n_shared, n_held = unit_counts(conf)
    n_lead = conf["first_k_dense_replace"]
    n_moe = conf["num_hidden_layers"] - n_lead

    def take(lo, i, n):
        a = lo + i * n
        return tuple(jax.lax.dynamic_slice_in_dim(x, a, n) for x in (pi, s, phi))

    def fn(kind, i, p):
        p = jax.tree.map(lambda x: x, p)
        p["attn"] = {k: (rtn(v, bits, group) if k in ATTN_LEAVES else v)
                     for k, v in p["attn"].items()}
        if kind == "dense":
            p["mlp"] = _units(p["mlp"], n_dense, *take(0, i, n_dense),
                              bits, group, F)
            return p
        moe_p = dict(p["moe"])
        lo = n_lead * n_dense
        moe_p["shared"] = _units(moe_p["shared"], n_shared,
                                 *take(lo, i, n_shared), bits, group, F)
        lo += n_moe * n_shared
        tp, ts, tphi = take(lo, i, n_held)
        ex = {k: [] for k in FFN_LEAVES}
        for e in range(n_held):
            up, gate, down = transform_gated(moe_p["up"][e], moe_p["gate"][e],
                                             moe_p["down"][e], tp[e], ts[e],
                                             tphi[e])
            for k, w in (("up", up), ("gate", gate), ("down", down)):
                ex[k].append(rtn(w, bits, group))
        moe_p.update({k: jnp.stack(v) for k, v in ex.items()})
        p["moe"] = moe_p
        return p
    return fn
