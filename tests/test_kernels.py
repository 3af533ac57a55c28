"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import QuantConfig, quantize_tensor
from repro.kernels import quant_matmul, group_quant
from repro.kernels.ref import quant_matmul_ref, group_quant_ref
from repro.kernels.quant_matmul import quant_matmul_pallas
from repro.kernels.group_quant import group_quant_pallas

SHAPES_MM = [(8, 128, 128), (16, 256, 256), (32, 512, 128), (8, 128, 384)]
SHAPES_GQ = [(128, 128), (256, 256), (512, 128), (384, 256)]


@pytest.mark.parametrize("bits,group", [(2, 64), (2, 128), (4, 64), (8, 32), (3, 32)])
@pytest.mark.parametrize("M,K,N", SHAPES_MM)
def test_quant_matmul_sweep(bits, group, M, K, N):
    if K % group:
        pytest.skip("group must divide K")
    key = jax.random.PRNGKey(M * K + N + bits)
    w = jax.random.normal(key, (K, N))
    x = jax.random.normal(jax.random.PRNGKey(1), (M, K))
    qt = quantize_tensor(w, QuantConfig(bits=bits, group_size=group))
    out = quant_matmul(x, qt.packed, qt.scale, qt.zero, bits=bits, group=group)
    want = quant_matmul_ref(x, qt.packed, qt.scale, qt.zero, bits, group)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul_dtypes(dtype):
    bits, group, M, K, N = 2, 64, 8, 128, 128
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (K, N))
    x = jax.random.normal(jax.random.PRNGKey(1), (M, K)).astype(dtype)
    qt = quantize_tensor(w, QuantConfig(bits=bits, group_size=group))
    out = quant_matmul(x, qt.packed, qt.scale, qt.zero, bits=bits, group=group)
    want = quant_matmul_ref(x.astype(jnp.float32), qt.packed, qt.scale, qt.zero,
                            bits, group)
    tol = 5e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=tol, atol=tol)


def test_quant_matmul_fallback_on_odd_shapes():
    """Non-tileable shapes silently use the reference path (still correct)."""
    bits, group = 2, 32
    K, N, M = 96, 100, 7  # N % 128 != 0, M % 8 != 0
    w = jax.random.normal(jax.random.PRNGKey(0), (K, N))
    x = jax.random.normal(jax.random.PRNGKey(1), (M, K))
    qt = quantize_tensor(w, QuantConfig(bits=bits, group_size=group))
    out = quant_matmul(x, qt.packed, qt.scale, qt.zero, bits=bits, group=group)
    want = quant_matmul_ref(x, qt.packed, qt.scale, qt.zero, bits, group)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5)


def _assert_within_one_step(fq, fqr, scale, group):
    """Reduction-order ULP differences in the scale can flip a round-half
    boundary — allow at most ONE quantization step on <0.1% of elements."""
    fq = np.asarray(fq, dtype=np.float32)
    fqr = np.asarray(fqr, dtype=np.float32)
    step = np.repeat(np.asarray(scale), group, axis=0)
    diff = np.abs(fq - fqr)
    assert np.all(diff <= step * 1.001 + 1e-6), "differs by more than one step"
    frac = float(np.mean(diff > step * 0.5))
    assert frac < 1e-3, f"{frac:.2%} of elements off by a step (expected ~0)"


@pytest.mark.parametrize("bits,group", [(2, 32), (2, 128), (4, 64), (8, 64)])
@pytest.mark.parametrize("K,N", SHAPES_GQ)
def test_group_quant_sweep(bits, group, K, N):
    if K % group:
        pytest.skip("group must divide K")
    key = jax.random.PRNGKey(K + N + bits)
    w = jax.random.normal(key, (K, N)) * 2.5
    fq, s, z = group_quant(w, bits=bits, group=group)
    fqr, sr, zr = group_quant_ref(w, bits, group)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-5, atol=1e-8)
    np.testing.assert_allclose(np.asarray(z), np.asarray(zr), rtol=1e-5, atol=1e-8)
    _assert_within_one_step(fq, fqr, sr, group)


def test_group_quant_bf16():
    w = (jax.random.normal(jax.random.PRNGKey(0), (128, 128)) * 2).astype(jnp.bfloat16)
    fq, s, z = group_quant(w, bits=4, group=64)
    fqr, sr, _ = group_quant_ref(w, 4, 64)
    assert fq.dtype == jnp.bfloat16
    _assert_within_one_step(fq, fqr, sr, 64)


def test_pallas_grid_accumulation():
    """K-axis grid accumulation: multiple k-steps must sum correctly."""
    bits, group = 2, 64
    M, K, N = 8, 1024, 128  # K/bk = 2 grid steps at bk=512
    w = jax.random.normal(jax.random.PRNGKey(0), (K, N))
    x = jax.random.normal(jax.random.PRNGKey(1), (M, K))
    qt = quantize_tensor(w, QuantConfig(bits=bits, group_size=group))
    out = quant_matmul_pallas(x, qt.packed, qt.scale, qt.zero, bits=bits,
                              group=group, bm=8, bk=512, bn=128, interpret=True)
    want = quant_matmul_ref(x, qt.packed, qt.scale, qt.zero, bits, group)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=5e-4, atol=5e-4)


def test_group_quant_tile_shapes():
    """bg tiling never straddles a group boundary."""
    w = jax.random.normal(jax.random.PRNGKey(0), (512, 256))
    for bg in (1, 2, 4):
        fq, s, z = group_quant_pallas(w, bits=2, group=128, bg=bg, bn=128,
                                      interpret=True)
        fqr, sr, _ = group_quant_ref(w, 2, 128)
        np.testing.assert_allclose(np.asarray(fq), np.asarray(fqr), rtol=1e-5,
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# flash_decode: fused single-token decode attention (bf16 + int8 KV)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,Dh,chunk", [(2, 256, 4, 32, 64), (1, 512, 2, 64, 128),
                                            (2, 128, 8, 16, 128)])
def test_flash_decode_sweep(B, S, H, Dh, chunk):
    from repro.kernels import flash_decode
    from repro.kernels.ref import flash_decode_ref
    key = jax.random.PRNGKey(B + S + H)
    q = jax.random.normal(key, (B, H, Dh))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, Dh))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, Dh))
    out = flash_decode(q, k, v, chunk=chunk)
    want = flash_decode_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_flash_decode_kv_len_mask():
    from repro.kernels import flash_decode
    from repro.kernels.ref import flash_decode_ref
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 2, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 128, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 128, 2, 16))
    # poison masked region: result must be unaffected
    k2 = k.at[:, 100:].set(50.0)
    v2 = v.at[:, 100:].set(50.0)
    out = flash_decode(q, k2, v2, kv_len=100, chunk=32)
    want = flash_decode_ref(q, k, v, kv_len=100)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_flash_decode_int8_cache():
    """int8-quantized KV + per-(pos, head) scales vs explicit-dequant oracle."""
    from repro.kernels import flash_decode
    from repro.kernels.ref import flash_decode_ref
    key = jax.random.PRNGKey(3)
    B, S, H, Dh = 2, 256, 4, 32
    q = jax.random.normal(key, (B, H, Dh))
    kf = jax.random.normal(jax.random.PRNGKey(4), (B, S, H, Dh))
    vf = jax.random.normal(jax.random.PRNGKey(5), (B, S, H, Dh))
    ks = jnp.max(jnp.abs(kf), axis=-1) / 127.0 + 1e-8
    vs = jnp.max(jnp.abs(vf), axis=-1) / 127.0 + 1e-8
    k8 = jnp.round(kf / ks[..., None]).astype(jnp.int8)
    v8 = jnp.round(vf / vs[..., None]).astype(jnp.int8)
    out = flash_decode(q, k8, v8, ks, vs, chunk=64)
    want = flash_decode_ref(q, k8, v8, ks, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4, atol=2e-4)
    # and the int8 path approximates the fp path
    dense = flash_decode_ref(q, kf, vf)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), rtol=0.05, atol=0.05)


# ---------------------------------------------------------------------------
# Fused transform+fake-quant (the population search's per-proposal hot path)
# ---------------------------------------------------------------------------

def _random_transform(f, seed=0, identity=False):
    import repro.core.invariance as inv
    if identity:
        t = inv.identity_transform(f)
        return t.pi, t.s, t.phi
    pi = jax.random.permutation(jax.random.PRNGKey(seed), f).astype(jnp.int32)
    s = 1.0 + 0.05 * jax.random.normal(jax.random.PRNGKey(seed + 1), (f,))
    phi = 1e-2 * jax.random.normal(jax.random.PRNGKey(seed + 2), (f // 2,))
    return pi, s, phi


@pytest.mark.parametrize("mode", ["up", "down"])
@pytest.mark.parametrize("bits,group", [(2, 16), (2, 32), (4, 32), (3, 16)])
@pytest.mark.parametrize("D,F", [(64, 128), (128, 64), (96, 96)])
def test_transform_quant_sweep(mode, bits, group, D, F):
    """Fused kernel == materialize-then-quantize oracle to <=1e-5 in
    interpret mode across shapes / group sizes / modes (ISSUE 3 bar)."""
    from repro.kernels import transform_quant
    from repro.kernels.ref import transform_quant_ref
    K = D if mode == "up" else F
    if K % group:
        pytest.skip("group must divide the quant (K) axis")
    shape = (D, F) if mode == "up" else (F, D)
    w = jax.random.normal(jax.random.PRNGKey(D + F + bits), shape)
    f = F
    pi, s, phi = _random_transform(f, seed=bits)
    out = transform_quant(w, pi, s, phi, bits=bits, group=group, mode=mode)
    want = transform_quant_ref(w, pi, s, phi, bits=bits, group=group, mode=mode)
    for o, wt in zip(out, want):
        np.testing.assert_allclose(np.asarray(o), np.asarray(wt),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mode", ["up", "down"])
def test_transform_quant_identity_is_plain_fake_quant(mode):
    """Identity (pi, s, phi) must reduce to the plain group fake-quant
    roundtrip — ties the fused kernel to core.quant.fake_quant exactly."""
    from repro.core.quant import fake_quant
    from repro.kernels import transform_quant
    D, F, group = 64, 128, 32
    shape = (D, F) if mode == "up" else (F, D)
    w = jax.random.normal(jax.random.PRNGKey(9), shape)
    pi, s, phi = _random_transform(F, identity=True)
    fq, _, _ = transform_quant(w, pi, s, phi, bits=2, group=group, mode=mode)
    want = fake_quant(w, QuantConfig(bits=2, group_size=group))
    np.testing.assert_allclose(np.asarray(fq), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_transform_quant_matches_apply_transform_ffn():
    """Kernel pair (up, down) == inv.apply_transform_ffn + fake_quant on a
    real FFN weight pair (the exact computation the search engine fuses)."""
    import repro.core.invariance as inv
    from repro.core.quant import fake_quant
    from repro.kernels import transform_quant
    D, F, group = 64, 128, 32
    w_up = jax.random.normal(jax.random.PRNGKey(0), (D, F))
    w_down = jax.random.normal(jax.random.PRNGKey(1), (F, D))
    pi, s, phi = _random_transform(F, seed=42)
    t = inv.FFNTransform(pi=pi, s=s, phi=phi)
    up_t, down_t, _, _, _ = inv.apply_transform_ffn(t, w_up, w_down)
    qcfg = QuantConfig(bits=2, group_size=group)
    got_up = transform_quant(w_up, pi, s, phi, bits=2, group=group, mode="up")[0]
    got_down = transform_quant(w_down, pi, s, phi, bits=2, group=group,
                               mode="down")[0]
    np.testing.assert_allclose(np.asarray(got_up),
                               np.asarray(fake_quant(up_t, qcfg)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_down),
                               np.asarray(fake_quant(down_t, qcfg)),
                               rtol=1e-5, atol=1e-5)


def test_transform_quant_ref_fallback_on_untileable_shapes():
    """A down-mode N that cannot column-tile (192 > 128, not a multiple)
    must silently fall back to the jnp reference — same contract as the
    other ops.py wrappers."""
    from repro.kernels import transform_quant
    from repro.kernels.ref import transform_quant_ref
    F, D, group = 64, 192, 32
    w = jax.random.normal(jax.random.PRNGKey(2), (F, D))
    pi, s, phi = _random_transform(F, seed=3)
    out = transform_quant(w, pi, s, phi, bits=2, group=group, mode="down")
    want = transform_quant_ref(w, pi, s, phi, bits=2, group=group, mode="down")
    for o, wt in zip(out, want):
        np.testing.assert_allclose(np.asarray(o), np.asarray(wt),
                                   rtol=1e-5, atol=1e-5)


def test_reference_fallback_warns_once_per_shape_on_tpu(monkeypatch):
    """On a TPU a shape guard that routes to the jnp reference must say so —
    once per kernel and shape, with the reason — so a fallback cannot pass
    for the kernel; on the CPU it stays silent."""
    import warnings

    from repro.kernels import ops

    w = jax.random.normal(jax.random.PRNGKey(3), (96, 40))   # N % 128 != 0
    monkeypatch.setattr(ops, "_FALLBACKS_WARNED", set())
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        ops._fallback("group_quant", w.shape, "cpu: silent")
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    with pytest.warns(UserWarning, match=r"group_quant.*N % 128 == 0"):
        fq, _, _ = ops.group_quant(w, bits=2, group=32)
    np.testing.assert_allclose(np.asarray(fq),
                               np.asarray(group_quant_ref(w, 2, 32)[0]),
                               rtol=1e-6, atol=1e-6)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        ops._fallback("group_quant", w.shape, "already reported")
