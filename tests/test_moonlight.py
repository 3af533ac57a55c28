"""Moonlight-16B-A3B (DeepSeek-V3 blocks) against the plain reference, on the
CPU at a small Moonlight-shaped size with seeded random weights.

The program and the reference (``chipbench/reference_mla_moe.py``) both
compute in float32, the program at the CPU's full float32 matmul precision
and the reference under ``Precision.HIGHEST``; they differ only in the order
of summation, so outputs of order one agree to ``ATOL`` (the largest gap of
logits and taps over 4 seeds, with all and with half of the experts held,
was 9.4e-6).
"""
import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.core import invariance as inv
from repro.core.search import MoEAdapter, make_adapter
from repro.kernels import ops
from repro.models import (decode_step, forward, init_cache, init_params,
                          prefill)
from repro.models import layers as L
from repro.models.config import MLAConfig

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from chipbench import reference_mla_moe as ref  # noqa: E402

ATOL = 2e-5          # float32 summation order (see the module docstring)


def small_cfg(**moe):
    """Moonlight's structure at a small size: one dense layer then MoE
    layers, MLA, 2 shared experts, 16 sigmoid-routed experts, top-4."""
    base = get_config("moonshot-v1-16b-a3b")
    return dataclasses.replace(
        base, n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=32,
        dense_d_ff=128, vocab_size=256, max_seq_len=128, attn_chunk=32,
        param_dtype="float32", compute_dtype="float32",
        mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16),
        moe=dataclasses.replace(base.moe, num_experts=16, top_k=4, **moe))


def conf_of(cfg):
    """The configuration-file keys the reference reads, for ``cfg``."""
    m, e = cfg.mla, cfg.moe
    start, held = e.held
    return {"num_attention_heads": cfg.n_heads,
            "qk_nope_head_dim": m.qk_nope_head_dim,
            "qk_rope_head_dim": m.qk_rope_head_dim,
            "v_head_dim": m.v_head_dim, "kv_lora_rank": m.kv_lora_rank,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "num_experts_per_tok": e.top_k, "norm_topk_prob": e.norm_topk_prob,
            "routed_scaling_factor": e.routed_scaling_factor,
            "experts_held_start": start, "n_routed_experts": held,
            "moe_intermediate_size": cfg.d_ff,
            "intermediate_size": cfg.dense_d_ff,
            "n_shared_experts": e.n_shared_experts,
            "first_k_dense_replace": cfg.first_k_dense,
            "num_hidden_layers": cfg.n_layers, "vocab_size": cfg.vocab_size,
            "bits": 2, "group_size": 16}


def rand_params(cfg, seed=0):
    """init_params with the correction bias and norm gains drawn too."""
    p = init_params(jax.random.PRNGKey(seed), cfg)
    leaves, tree = jax.tree.flatten_with_path(p)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    out = []
    for (path, x), k in zip(leaves, keys):
        name = str(path[-1])
        if "router_bias" in name:
            x = 0.05 * jax.random.normal(k, x.shape)
        elif "'w'" in name:
            x = x + 0.02 * jax.random.normal(k, x.shape)
        out.append(x)
    return jax.tree.unflatten(tree, out)


def tokens(cfg, B=2, S=24, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (B, S), 0,
                              cfg.vocab_size)


def test_mla_matches_plain_attention_over_expanded_kv():
    cfg = small_cfg()
    p = rand_params(cfg)["blocks"]["attn"]
    p = jax.tree.map(lambda x: x[0], p)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, cfg.d_model))
    got, _ = L.mla_attention(p, cfg, x, jnp.arange(24))
    with jax.default_matmul_precision("highest"):
        want = ref.mla(x, p, conf_of(cfg), lambda a: a)
    np.testing.assert_allclose(got, want, atol=ATOL)


def test_sigmoid_routing_rule():
    cfg = small_cfg()
    p = jax.tree.map(lambda x: x[0], rand_params(cfg)["blocks"]["moe"])
    # a correction bias that reorders the choice
    p["router_bias"] = jnp.linspace(-0.5, 0.5, 16)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, cfg.d_model))
    w, idx = L.moe_route(p, cfg, x)
    s = jax.nn.sigmoid(np.asarray(x, np.float64) @ np.asarray(
        p["router"], np.float64))
    want_idx = np.argsort(-(s + np.asarray(p["router_bias"])), -1)[..., :4]
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(want_idx, -1))
    # the bias chooses only: the weights are the chosen scores, normalized
    # and scaled
    chosen = np.take_along_axis(s, np.asarray(idx), -1)
    want_w = 2.446 * chosen / chosen.sum(-1, keepdims=True)
    np.testing.assert_allclose(w, want_w, rtol=1e-5)
    np.testing.assert_allclose(np.sum(w, -1), 2.446, rtol=1e-5)
    # without the bias the choice differs somewhere
    plain = np.argsort(-s, -1)[..., :4]
    assert (np.sort(plain, -1) != np.sort(want_idx, -1)).any()


def test_expert_shares_sum_to_the_uncut_layer():
    """Each 8-expert group's share (its routed part; the shared experts
    every share computes alike counted once) adds up to the whole layer."""
    cfg = small_cfg()
    p = jax.tree.map(lambda x: x[0], rand_params(cfg)["blocks"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, cfg.d_model))
    whole = L.moe_ffn(p, cfg, x)
    shared = L.mlp(p["shared"], cfg, x)
    total = shared
    for g in range(2):
        share_cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, experts_held=(8 * g, 8)))
        share_p = dict(p, **{k: p[k][8 * g:8 * g + 8]
                             for k in ("up", "gate", "down")})
        total = total + (L.moe_ffn(share_p, share_cfg, x) - shared)
    np.testing.assert_allclose(total, whole, atol=ATOL)
    # and the uncut layer is the reference's with all experts held
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe(x, p, conf_of(cfg), lambda a: a, 0)
    np.testing.assert_allclose(whole, want, atol=ATOL)


@pytest.mark.parametrize("held", [None, (4, 8)])
def test_forward_logits_and_taps_match_the_reference(held):
    cfg = small_cfg(experts_held=held)
    params = rand_params(cfg)
    toks = tokens(cfg)
    logits, hidden = forward(params, cfg, toks, collect_hidden=True)
    with jax.default_matmul_precision("highest"):
        want_l, want_h = ref.forward(params, toks, conf_of(cfg),
                                     collect_hidden=True)
    assert hidden.shape == (cfg.n_layers,) + toks.shape + (cfg.d_model,)
    np.testing.assert_allclose(hidden, want_h, atol=ATOL)
    np.testing.assert_allclose(logits, want_l, atol=ATOL)


def test_prefill_and_decode_through_the_latent_cache():
    cfg = small_cfg(experts_held=(0, 8))
    params = rand_params(cfg)
    toks = tokens(cfg, S=20)
    full = forward(params, cfg, toks)
    cache = init_cache(cfg, 2, 32)
    assert set(cache) == {"c_kv", "k_pe"}
    assert cache["c_kv"].shape == (cfg.n_layers, 2, 32, 32)
    assert cache["k_pe"].shape == (cfg.n_layers, 2, 32, 8)
    logits, cache = prefill(params, cfg, toks[:, :12], 32)
    np.testing.assert_allclose(logits, full[:, :12], atol=ATOL)
    step = jax.jit(lambda t, c, i: decode_step(params, cfg, t, c, i))
    for i in range(12, 20):
        logits, cache = step(toks[:, i:i + 1], cache, jnp.int32(i))
        np.testing.assert_allclose(logits[:, 0], full[:, i], atol=ATOL)


def test_unit_transforms_leave_the_fp_forward_unchanged():
    """Permutation and scaling of one unit of each kind (a dense block, a
    shared expert, a held routed expert) are exact for SwiGLU units."""
    cfg = small_cfg(experts_held=(4, 8))
    params = rand_params(cfg)
    ad = make_adapter(cfg)
    assert isinstance(ad, MoEAdapter)
    assert ad.sizes == (4, 4, 16) and ad.f_dim == cfg.d_ff
    toks = tokens(cfg)
    want = forward(params, cfg, toks)
    base = ad.base_stack(params)
    assert jnp.array_equal(forward(ad.install(params, base), cfg, toks), want)
    key = jax.random.PRNGKey(9)
    for u in (1, 5, 17):          # dense block, shared expert, routed expert
        k1, k2, key = jax.random.split(key, 3)
        t = inv.FFNTransform(
            pi=jax.random.permutation(k1, cfg.d_ff).astype(jnp.int32),
            s=jnp.exp(0.3 * jax.random.normal(k2, (cfg.d_ff,))),
            phi=jnp.zeros((cfg.d_ff // 2,)))
        unit = ad.transform_unit(base, t, jnp.int32(u))
        moved = jax.tree.map(lambda b, n: b.at[u].set(n), base, unit)
        assert not jnp.array_equal(moved["up"], base["up"])
        got = forward(ad.install(params, moved), cfg, toks)
        np.testing.assert_allclose(got, want, atol=ATOL)


def test_searched_layer_matches_the_program_quantization():
    """The reference's unit order and RTN agree with the adapter's: the
    identity transform quantized unit by unit is the program's start."""
    from repro.core.quant import QuantConfig, fake_quant
    cfg = small_cfg(experts_held=(4, 8))
    params = rand_params(cfg)
    ad = make_adapter(cfg)
    qcfg = QuantConfig(bits=2, group_size=16)
    fq = jax.vmap(lambda b: ad.quant_unit(b, qcfg))(ad.base_stack(params))
    q = ad.install(params, fq)
    for stack in ("dense_blocks", "blocks"):
        q[stack] = dict(q[stack], attn={
            k: (fake_quant(v, qcfg) if k in ref.ATTN_LEAVES else v)
            for k, v in params[stack]["attn"].items()})
    toks = tokens(cfg)
    n = ad.n_units
    t = inv.identity_transform(cfg.d_ff)
    stacked = [jnp.broadcast_to(x, (n,) + x.shape) for x in t]
    with jax.default_matmul_precision("highest"):
        want = ref.forward(params, toks, conf_of(cfg),
                           layer_fn=ref.searched_layer(conf_of(cfg), *stacked))
    np.testing.assert_allclose(forward(q, cfg, toks), want, atol=ATOL)


def test_dispatch_and_attention_path_counters():
    cfg = small_cfg(experts_held=(0, 8))
    params = rand_params(cfg)
    att, moe = (obs.counter("attention_path_total"),
                obs.counter("moe_dispatch_total"))
    before = (att.value(path="blocked"), moe.value(path="grouped"),
              moe.value(path="capacity"))
    jax.jit(lambda p, t: forward(p, cfg, t)).lower(params, tokens(cfg))
    # one trace of each scanned stack: the dense block and the MoE blocks
    assert att.value(path="blocked") - before[0] == 2
    assert moe.value(path="grouped") - before[1] == 1
    assert moe.value(path="capacity") == before[2]


def test_mla_takes_the_flash_kernel_where_it_tiles(monkeypatch):
    """At Moonlight's head widths (q/k 192, v 128) the no-cache forward on
    a TPU holds the flash-attention kernel: q and k are zero-padded to 256
    lanes, the scale stays 192^-0.5. Here the kernel is interpreted."""
    cfg = dataclasses.replace(
        small_cfg(experts_held=(0, 8)), n_heads=2, n_kv_heads=2,
        mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128))
    params = rand_params(cfg)
    toks = tokens(cfg, B=1, S=128)
    _, want = forward(params, cfg, toks, collect_hidden=True)
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    flash = obs.counter("attention_path_total")
    before = flash.value(path="flash")
    _, got = jax.jit(lambda p, t: forward(p, cfg, t, collect_hidden=True))(
        params, toks)
    assert flash.value(path="flash") - before == 2
    # the dense block's output (before any routing, which bf16 rounding
    # flips at near ties): bf16 operands and output against float32
    np.testing.assert_allclose(got[0], want[0], atol=5e-2)
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 128, 2, 192))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 128, 2, 192))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 128, 2, 128))
    assert not ops.flash_attention_refusal(q.shape, k.shape, v.shape)
    out = ops.flash_attention(q, k, v, causal=True)
    assert out.shape == v.shape
    np.testing.assert_allclose(
        out, L.blocked_attention(q, k, v, causal=True), atol=3e-2)
    # values narrower than 128 lanes while q/k differ: refused
    assert ops.flash_attention_refusal(q.shape, k.shape, (1, 128, 2, 64))


@pytest.mark.parametrize("build", ["cache", "decode", "prefill"])
def test_paged_serving_refuses_latent_attention(build):
    from repro.serving.decode import make_paged_logits_step
    from repro.serving.paged_cache import PagedKVCache
    from repro.serving.prefill import make_paged_prefill_step
    cfg = small_cfg()
    make = {"cache": lambda: PagedKVCache(cfg, 16, 8, 4),
            "decode": lambda: make_paged_logits_step(cfg),
            "prefill": lambda: make_paged_prefill_step(cfg)}[build]
    with pytest.raises(ValueError, match="latent paged cache"):
        make()
