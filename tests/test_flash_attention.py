"""The flash-attention kernel (interpret mode) against ``blocked_attention``,
its gradient, and the routing of the no-cache forward.

On a TPU the no-cache forward takes the kernel where the shape fits; here
``ops.on_tpu`` is patched to True so that the CPU runs the same routing,
with the kernel interpreted. The kernel feeds its matmuls bfloat16 operands
(the TPU's default precision for the float32 reference) and its wrapper
rounds the output to bfloat16, so it meets the float32 ``blocked_attention``
of the CPU within bfloat16 rounding, and the oracle that rounds the same
operands (``ref.flash_attention_ref``) within the output's rounding, float32
accumulation order and the odd flipped rounding of a probability.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.kernels import ops, ref
from repro.models import forward, init_params
from repro.models import layers as L

# largest seen over 24 shapes and seeds: 1.4e-2 against blocked_attention;
# 9.7e-4 against the oracle before the output's rounding (a probability whose
# bfloat16 rounding flips moves a short causal row's output most)
BF16_ATOL = 3e-2        # against float32 blocked_attention
REF_ATOL = 2e-3         # against the oracle with the kernel's roundings


def _qkv(seed, B, S, Hq, Hkv, Dh):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, S, Hq, Dh)),
            jax.random.normal(ks[1], (B, S, Hkv, Dh)),
            jax.random.normal(ks[2], (B, S, Hkv, Dh)))


def _paths():
    c = obs.counter("attention_path_total")
    return c.value(path="flash"), c.value(path="blocked")


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("S,Hq,Hkv,Dh", [
    (128, 2, 2, 64), (256, 2, 2, 64), (512, 2, 2, 64),
    (128, 2, 2, 128), (256, 2, 2, 128), (512, 1, 1, 128),
    (128, 4, 2, 128),                                  # GQA at Dh = 128
])
def test_kernel_matches_blocked_attention(causal, S, Hq, Hkv, Dh):
    q, k, v = _qkv(S + Dh + Hkv, 1, S, Hq, Hkv, Dh)
    assert not ops.flash_attention_refusal(q.shape, k.shape)
    out = ops.flash_attention(q, k, v, causal=causal)
    assert out.shape == q.shape and out.dtype == q.dtype
    np.testing.assert_allclose(
        out, L.blocked_attention(q, k, v, causal=causal), atol=BF16_ATOL)
    # the wrapper rounds the output to bfloat16: half an ulp, 2^-9 relative
    np.testing.assert_allclose(
        out, ref.flash_attention_ref(q, k, v, causal=causal), rtol=2 ** -8,
        atol=REF_ATOL)


@pytest.mark.parametrize("causal", [True, False])
def test_grad_is_blocked_attention_grad(causal):
    q, k, v = _qkv(7, 1, 128, 2, 2, 64)
    w = jax.random.normal(jax.random.PRNGKey(8), q.shape)

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) * w)

    got = jax.grad(loss(lambda q, k, v: L.flash_attention(q, k, v, causal)),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(functools.partial(L.blocked_attention, causal=causal)),
                    argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_array_equal(g, r)


@pytest.mark.parametrize("shape,why", [
    ((1, 100, 2, 64), "multiple of 16"),
    ((1, 128, 4, 64), "GQA at head_dim 64"),
    ((1, 128, 2, 48), "head_dim 48"),
    ((1, 2048, 2, 64), "exceeds 1024"),
])
def test_refusal_names_what_the_kernel_cannot_tile(shape, why):
    kv = shape[:2] + ((2,) if "GQA" in why else shape[2:3]) + shape[3:]
    assert why in ops.flash_attention_refusal(shape, kv)
    q = jnp.zeros(shape)
    with pytest.raises(ValueError, match=why):
        ops.flash_attention(q, jnp.zeros(kv), jnp.zeros(kv), causal=True)


def _tiny_opt(S):
    cfg = dataclasses.replace(get_config("opt-tiny"), n_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0, cfg.vocab_size)
    return cfg, params, toks


def test_forward_takes_the_kernel_on_a_tpu(monkeypatch):
    cfg, params, toks = _tiny_opt(64)
    want, want_h = forward(params, cfg, toks, collect_hidden=True)
    flash0, blocked0 = _paths()
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    got, got_h = forward(params, cfg, toks, collect_hidden=True)
    flash1, blocked1 = _paths()
    assert (flash1 - flash0, blocked1 - blocked0) == (1, 0)  # one trace of the scan body
    np.testing.assert_allclose(got_h, want_h, atol=BF16_ATOL)
    np.testing.assert_allclose(got, want, atol=BF16_ATOL)


def test_grad_through_forward_on_the_kernel_path(monkeypatch):
    cfg, params, toks = _tiny_opt(64)

    def loss(p):
        return jnp.mean(jax.nn.logsumexp(forward(p, cfg, toks), axis=-1))

    want = jax.grad(loss)(params)
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    got = jax.grad(loss)(params)
    # one vector over every leaf: the key bias's true gradient is 0 (a shift
    # of every score of a row), so a per-leaf relative error reads noise
    g, r = (np.concatenate([np.ravel(x) for x in jax.tree.leaves(t)])
            for t in (got, want))
    assert np.all(np.isfinite(g))
    assert np.linalg.norm(g - r) < 5e-2 * np.linalg.norm(r)


def _attn_kw():
    return dict(chunk=get_config("opt-tiny").attn_chunk, unroll=False,
                softmax_dtype=jnp.dtype("float32"), repeat_kv=False)


def test_unfit_shape_keeps_blocked_attention(monkeypatch):
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_FALLBACKS_WARNED", set())
    q, k, v = _qkv(9, 1, 100, 4, 4, 32)
    kw = _attn_kw()
    flash0, blocked0 = _paths()
    with pytest.warns(UserWarning, match="flash_attention.*multiple of 16"):
        out = L._full_attention(q, k, v, True, kw)
    flash1, blocked1 = _paths()
    assert (flash1 - flash0, blocked1 - blocked0) == (0, 1)
    np.testing.assert_array_equal(out, L.blocked_attention(q, k, v, causal=True, **kw))


def test_cpu_forward_keeps_blocked_attention():
    cfg, params, toks = _tiny_opt(64)
    flash0, blocked0 = _paths()
    forward(params, cfg, toks)
    flash1, blocked1 = _paths()
    assert (flash1 - flash0, blocked1 - blocked0) == (0, 1)


@pytest.mark.parametrize("precision,path", [
    (None, "flash"), ("default", "flash"), ("bfloat16", "flash"),
    ("highest", "blocked"), ("float32", "blocked"), ("tensorfloat32", "blocked"),
])
def test_matmul_precision_picks_the_path(monkeypatch, precision, path):
    # the kernel's matmuls take one bfloat16 pass; a program traced at a
    # higher precision keeps blocked_attention, which honours it
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    q, k, v = _qkv(10, 1, 128, 2, 2, 64)
    kw = _attn_kw()
    flash0, blocked0 = _paths()
    with jax.default_matmul_precision(precision):
        out = jax.jit(lambda q, k, v: L._full_attention(q, k, v, True, kw))(
            q, k, v)
        want = L.blocked_attention(q, k, v, causal=True, **kw)
    flash1, blocked1 = _paths()
    assert (flash1 - flash0, blocked1 - blocked0) == (
        (1, 0) if path == "flash" else (0, 1))
    if path == "blocked":
        np.testing.assert_array_equal(out, want)
    else:
        np.testing.assert_allclose(out, want, atol=BF16_ATOL)


def test_host_with_several_devices_keeps_blocked_attention(monkeypatch):
    # a jit on a host with several devices may be partitioned by GSPMD,
    # which cannot split a Mosaic kernel
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    q, k, v = _qkv(11, 1, 128, 2, 2, 64)
    kw = _attn_kw()
    flash0, blocked0 = _paths()
    out = L._full_attention(q, k, v, True, kw)
    flash1, blocked1 = _paths()
    assert (flash1 - flash0, blocked1 - blocked0) == (0, 1)
    np.testing.assert_array_equal(
        out, L.blocked_attention(q, k, v, causal=True, **kw))


def test_shard_map_body_is_a_one_device_program(monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    seen = []

    def body(x):
        seen.append(ops.one_device_program())
        return x

    P = jax.sharding.PartitionSpec
    jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data")))(jnp.zeros((2, 4)))
    assert seen == [True] and not ops.one_device_program()
