"""Per-architecture smoke tests (deliverable f): reduced same-family config,
one forward + one train step on CPU, asserting shapes + no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs
from repro.launch.steps import make_train_step
from repro.models import init_params, forward, init_cache, decode_step, prefill
from repro.models.frontends import stub_vision_embeds, stub_audio_frames
from repro.optim.adamw import AdamWConfig, adamw_init

ARCHS = list_archs()


def _batch_for(cfg, key, B=2, S=32):
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    if cfg.frontend == "vision":
        batch["vision_embeds"] = stub_vision_embeds(key, cfg, B, cfg.frontend_len)
    if cfg.is_enc_dec:
        batch["enc_embeds"] = stub_audio_frames(key, cfg, B, S)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_no_nan(arch):
    cfg = get_config(arch).reduced()
    key = jax.random.PRNGKey(hash(arch) % 2 ** 31)
    params = init_params(key, cfg)
    batch = _batch_for(cfg, key)
    kw = {k: v for k, v in batch.items() if k != "tokens"}
    logits = forward(params, cfg, batch["tokens"], **kw)
    B, S = batch["tokens"].shape
    prefix = cfg.frontend_len if cfg.frontend == "vision" else 0
    assert logits.shape == (B, S + prefix, cfg.padded_vocab)
    assert not bool(jnp.any(jnp.isnan(logits))), f"{arch}: NaN in logits"


@pytest.mark.parametrize("arch", ARCHS)
def test_one_train_step(arch):
    cfg = get_config(arch).reduced()
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    opt = adamw_init(params)
    step = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-3, total_steps=10,
                                                    warmup_steps=1)))
    batch = _batch_for(cfg, key)
    params2, opt2, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # parameters actually moved
    moved = any(
        bool(jnp.any(a != b))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)))
    assert moved, f"{arch}: train step did not update params"
    # no NaN anywhere in the updated tree
    for leaf in jax.tree.leaves(params2):
        assert not bool(jnp.any(jnp.isnan(leaf))), f"{arch}: NaN in updated params"


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step_runs(arch):
    cfg = get_config(arch).reduced()
    key = jax.random.PRNGKey(1)
    params = init_params(key, cfg)
    B = 2
    tokens = jax.random.randint(key, (B, 1), 0, cfg.vocab_size)
    cache = init_cache(cfg, B, 64)
    if cfg.is_enc_dec:
        enc = stub_audio_frames(key, cfg, B, 16)
        _, cache = prefill(params, cfg, tokens, 64, enc_embeds=enc)
    logits, cache2 = decode_step(params, cfg, tokens, cache, jnp.int32(0))
    assert logits.shape == (B, 1, cfg.padded_vocab)
    assert not bool(jnp.any(jnp.isnan(logits)))


def test_param_count_sane():
    """Full configs match their nameplate sizes (±25% — vocab padding, per-
    config approximations)."""
    expect = {
        "internlm2-1.8b": 1.8e9, "qwen3-4b": 4e9, "yi-6b": 6e9,
        "command-r-35b": 35e9, "mamba2-2.7b": 2.7e9, "zamba2-7b": 7e9,
        "phi3.5-moe-42b-a6.6b": 42e9,
        # the published Moonlight-16B-A3B: 27 layers (one dense, 26 MoE with
        # 64 routed + 2 shared experts), MLA, vocab 163,840
        "moonshot-v1-16b-a3b": 16e9,
    }
    for arch, n in expect.items():
        got = get_config(arch).param_count()
        assert 0.7 * n < got < 1.35 * n, f"{arch}: {got/1e9:.2f}B vs {n/1e9:.1f}B"


def test_moe_active_params():
    cfg = get_config("phi3.5-moe-42b-a6.6b")
    active = cfg.active_param_count()
    assert 4e9 < active < 9e9  # nameplate: 6.6B active
    assert active < cfg.param_count() / 3
    # Moonlight-16B-A3B: about 3B active (6 of 64 routed experts a token,
    # the 2 shared experts, MLA, the dense layer, embedding and head)
    cfg = get_config("moonshot-v1-16b-a3b")
    assert 2.5e9 < cfg.active_param_count() < 3.5e9
