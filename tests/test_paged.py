"""Paged serving subsystem: kernel vs dense oracle, allocator, batcher.

The acceptance bar (ISSUE 2): ``paged_decode_attention`` must match the
dense ``kernels/ref.py`` oracle to <=1e-5 with fp32 pages across page sizes,
ragged sequence lengths, and GQA head ratios; int8 pages match their own
explicit-dequant oracle to <=1e-5 and the fp path to the 5e-2 tolerance the
contiguous int8 cache already documents in test_kernels.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core.quant import QuantConfig
from repro.kernels import paged_decode
from repro.kernels.paged_decode import paged_decode_gqa_pallas
from repro.kernels.ref import flash_decode_ref, paged_decode_ref
from repro.models import forward, init_params, prefill
from repro.quantized.qmodel import pack_model, cache_bytes, serving_memory_report
from repro.serving import (ContinuousBatcher, NULL_PAGE, PageAllocator,
                           PagedKVCache, PagedRequest, make_paged_prefill_step)


def _random_paged(key, B, H, Hkv, Dh, page_size, n_pages, max_pages, int8=False):
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    q = jax.random.normal(ks[0], (B, H, Dh))
    kp = jax.random.normal(ks[1], (n_pages, Hkv, page_size, Dh))
    vp = jax.random.normal(ks[2], (n_pages, Hkv, page_size, Dh))
    # distinct physical pages per sequence (disjoint live tables), padded
    # with the null page like the batcher does
    perm = jax.random.permutation(ks[3], n_pages - 1) + 1
    bt = np.zeros((B, max_pages), np.int32)
    flat = np.asarray(perm)[: B * max_pages]
    bt.flat[: flat.size] = flat
    bt = jnp.asarray(bt)
    lens = jax.random.randint(ks[4], (B,), 1, max_pages * page_size + 1)
    if not int8:
        return q, kp, vp, bt, lens, None, None
    kscale = jnp.max(jnp.abs(kp), axis=-1) / 127.0 + 1e-8
    vscale = jnp.max(jnp.abs(vp), axis=-1) / 127.0 + 1e-8
    k8 = jnp.round(kp / kscale[..., None]).astype(jnp.int8)
    v8 = jnp.round(vp / vscale[..., None]).astype(jnp.int8)
    return q, k8, v8, bt, lens, kscale, vscale


def _dense_oracle(q, kp, vp, bt, lens, ks, vs):
    """Gather pages into a contiguous cache, then the flash_decode oracle."""
    B, H, Dh = q.shape
    Hkv, psz = kp.shape[1], kp.shape[2]
    P = bt.shape[1]

    def tokens(pages):     # (B, P, Hkv, psz, ...) -> (B, P * psz, Hkv, ...)
        t = jnp.swapaxes(pages, 2, 3)
        return t.reshape((B, P * psz, Hkv) + t.shape[4:])

    k = tokens(kp[bt]).astype(jnp.float32)
    v = tokens(vp[bt]).astype(jnp.float32)
    if ks is not None:
        k = k * tokens(ks[bt])[..., None]
        v = v * tokens(vs[bt])[..., None]
    if Hkv < H:
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    rows = [flash_decode_ref(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                             kv_len=int(lens[b])) for b in range(B)]
    return jnp.concatenate(rows, axis=0)


# ---------------------------------------------------------------------------
# Kernel vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("page_size", [4, 8, 16, 32])
def test_paged_decode_page_sizes(page_size):
    q, kp, vp, bt, lens, _, _ = _random_paged(
        page_size, B=3, H=4, Hkv=4, Dh=16, page_size=page_size,
        n_pages=3 * 3 + 1, max_pages=3)
    out = paged_decode(q, kp, vp, bt, lens)
    want = _dense_oracle(q, kp, vp, bt, lens, None, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=12, deadline=None)
@given(st.integers(1, 4), st.sampled_from([4, 8, 16]), st.integers(1, 4),
       st.integers(0, 10_000))
def test_paged_decode_property(B, page_size, max_pages, seed):
    """Ragged lengths x page sizes x batch: kernel == gathered-dense oracle."""
    q, kp, vp, bt, lens, _, _ = _random_paged(
        seed, B=B, H=4, Hkv=4, Dh=8, page_size=page_size,
        n_pages=B * max_pages + 1, max_pages=max_pages)
    out = paged_decode(q, kp, vp, bt, lens)
    want = paged_decode_ref(q, kp, vp, bt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("H,Hkv", [(8, 4), (8, 2), (4, 1)])
def test_paged_decode_gqa(H, Hkv):
    """Query head h must read KV head h // rep straight from the pool."""
    q, kp, vp, bt, lens, _, _ = _random_paged(
        7, B=2, H=H, Hkv=Hkv, Dh=16, page_size=8, n_pages=9, max_pages=4)
    out = paged_decode(q, kp, vp, bt, lens)
    want = _dense_oracle(q, kp, vp, bt, lens, None, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_paged_decode_int8_pages():
    """int8 codes + per-(slot, head) scales: exact vs the int8 oracle,
    ~5e-2 vs the fp pages they quantize (documented tolerance)."""
    q, k8, v8, bt, lens, ks, vs = _random_paged(
        11, B=2, H=4, Hkv=4, Dh=32, page_size=8, n_pages=9, max_pages=4,
        int8=True)
    out = paged_decode(q, k8, v8, bt, lens, ks, vs)
    want = paged_decode_ref(q, k8, v8, bt, lens, ks, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    kp = k8.astype(jnp.float32) * ks[..., None]
    vp = v8.astype(jnp.float32) * vs[..., None]
    dense = _dense_oracle(q, kp, vp, bt, lens, None, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=5e-2, atol=5e-2)


def test_paged_decode_poisoned_dead_pages():
    """Positions past seq_len and block-table null-padding never leak."""
    q, kp, vp, bt, lens, _, _ = _random_paged(
        3, B=2, H=4, Hkv=4, Dh=16, page_size=8, n_pages=9, max_pages=4)
    want = paged_decode(q, kp, vp, bt, lens)
    # poison the null page and every slot past each sequence's length
    kp2, vp2 = kp.at[NULL_PAGE].set(500.0), vp.at[NULL_PAGE].set(500.0)
    psz = kp.shape[2]
    P = bt.shape[1]
    for b in range(q.shape[0]):
        used = int(lens[b])
        for p in range(P):
            for s in range(psz):
                if p * psz + s >= used:
                    pg = int(bt[b, p])
                    kp2 = kp2.at[pg, :, s].set(500.0)
                    vp2 = vp2.at[pg, :, s].set(500.0)
    out = paged_decode(q, kp2, vp2, bt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_paged_partials_merge_across_shards():
    """normalize=False partials + dist.merge_partials == unsharded dense —
    a sequence-sharded cache can page each shard independently."""
    from repro.dist.attention import merge_partials
    psz, P = 8, 4
    q, kp, vp, bt, lens, _, _ = _random_paged(
        5, B=2, H=4, Hkv=4, Dh=16, page_size=psz, n_pages=9, max_pages=P)
    want = paged_decode_ref(q, kp, vp, bt, lens)
    half = P // 2 * psz
    parts = [
        paged_decode(q, kp, vp, bt[:, : P // 2], jnp.minimum(lens, half),
                     normalize=False),
        paged_decode(q, kp, vp, bt[:, P // 2:],
                     jnp.maximum(lens - half, 0), normalize=False),
    ]
    merged = merge_partials(jnp.stack([p[0] for p in parts]),
                            jnp.stack([p[1] for p in parts]),
                            jnp.stack([p[2] for p in parts]))
    np.testing.assert_allclose(np.asarray(merged), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("H,Hkv", [(8, 4), (8, 2), (4, 1), (8, 8)])
def test_paged_decode_gqa_fused_matches_oracle(H, Hkv):
    """The fused (B, Hkv, P)-grid kernel — one page DMA per KV head serving
    its whole query-head group — must match the dense oracle to <=1e-5,
    including the normalize=False LSE partials (the dist merge contract)."""
    q, kp, vp, bt, lens, _, _ = _random_paged(
        19, B=3, H=H, Hkv=Hkv, Dh=16, page_size=8, n_pages=13, max_pages=4)
    out = paged_decode_gqa_pallas(q, kp, vp, bt, lens, interpret=True)
    want = paged_decode_ref(q, kp, vp, bt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    acc, m, l = paged_decode_gqa_pallas(q, kp, vp, bt, lens,
                                        normalize=False, interpret=True)
    acc_r, m_r, l_r = paged_decode_ref(q, kp, vp, bt, lens, normalize=False)
    for got, ref_ in ((acc, acc_r), (m, m_r), (l, l_r)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref_),
                                   rtol=1e-5, atol=1e-5)


def test_paged_decode_gqa_fused_int8_and_ragged():
    """int8 pages + ragged lengths through the fused grid (dead-page skip
    included): exact vs the int8 oracle."""
    q, k8, v8, bt, lens, ks, vs = _random_paged(
        23, B=4, H=8, Hkv=2, Dh=32, page_size=4, n_pages=17, max_pages=4,
        int8=True)
    lens = jnp.asarray([1, 5, 9, 16], jnp.int32)  # 1 token .. full table
    out = paged_decode_gqa_pallas(q, k8, v8, bt, lens, ks, vs, interpret=True)
    want = paged_decode_ref(q, k8, v8, bt, lens, ks, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_paged_decode_routes_gqa_to_fused():
    """ops.paged_decode must use the fused grid for GQA shapes by default
    and still match the per-query-head kernel (same math, one page read)."""
    q, kp, vp, bt, lens, _, _ = _random_paged(
        29, B=2, H=8, Hkv=2, Dh=16, page_size=8, n_pages=9, max_pages=4)
    fused = paged_decode(q, kp, vp, bt, lens)                   # default
    unfused = paged_decode(q, kp, vp, bt, lens, fused_gqa=False)
    want = paged_decode_ref(q, kp, vp, bt, lens)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(unfused), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mp", [2, 4])
def test_paged_decode_gqa_multipage_matches_oracle(mp):
    """pages_per_block > 1 (the multi-page inner grid axis: MP pages staged
    into VMEM scratch, one (rep, MP*psz) online-softmax update per block)
    must match the oracle AND the single-page grid across ragged lengths —
    including a max_pages that MP does not divide (the last block is
    partially dead) and the normalize=False LSE partials."""
    q, kp, vp, bt, lens, _, _ = _random_paged(
        31, B=3, H=8, Hkv=2, Dh=16, page_size=8, n_pages=16, max_pages=5)
    base = paged_decode_gqa_pallas(q, kp, vp, bt, lens, interpret=True)
    out = paged_decode_gqa_pallas(q, kp, vp, bt, lens, interpret=True,
                                  pages_per_block=mp)
    want = paged_decode_ref(q, kp, vp, bt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               rtol=1e-5, atol=1e-5)
    got = paged_decode_gqa_pallas(q, kp, vp, bt, lens, interpret=True,
                                  pages_per_block=mp, normalize=False)
    ref_ = paged_decode_ref(q, kp, vp, bt, lens, normalize=False)
    for g, r in zip(got, ref_):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-5, atol=1e-5)


def test_paged_decode_gqa_multipage_int8_and_routing():
    """int8 pages through the multi-page grid (per-page dequant happens at
    stage time, before the block matmul), and the ops wrapper's
    ``gqa_pages_per_block`` knob routes to it."""
    q, k8, v8, bt, lens, ks, vs = _random_paged(
        37, B=4, H=8, Hkv=2, Dh=32, page_size=4, n_pages=17, max_pages=4,
        int8=True)
    lens = jnp.asarray([1, 5, 9, 16], jnp.int32)  # 1 token .. full table
    out = paged_decode_gqa_pallas(q, k8, v8, bt, lens, ks, vs,
                                  interpret=True, pages_per_block=2)
    want = paged_decode_ref(q, k8, v8, bt, lens, ks, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    routed = paged_decode(q, k8, v8, bt, lens, ks, vs, gqa_pages_per_block=2)
    np.testing.assert_allclose(np.asarray(routed), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Chunked paged prefill (serving v2 admit path)
# ---------------------------------------------------------------------------

def _chunked_prefill(cfg, params_q, cache, page_ids, prompt, chunk_pages):
    """Drive make_paged_prefill_step over a prompt; returns last-token
    logits. Mutates cache.pools exactly like the batcher's admit."""
    psz = cache.page_size
    step = jax.jit(make_paged_prefill_step(cfg))
    bt = jnp.asarray(cache.block_table_row(page_ids)[None])
    plen = len(prompt)
    off = 0
    logits = last_off = None
    while off < plen:
        n_tok = min(chunk_pages * psz, plen - off)
        c = cache.pages_for(n_tok) * psz
        toks = np.zeros((1, c), np.int32)
        toks[0, :n_tok] = prompt[off: off + n_tok]
        logits, cache.pools = step(params_q, jnp.asarray(toks), cache.pools,
                                   bt, jnp.int32(off))
        last_off, off = off, off + n_tok
    return logits[0, (plen - 1) - last_off]


@pytest.mark.parametrize("page_size,plen,chunk_pages,n_kv",
                         [(4, 3, 2, 4),    # sub-page prompt
                          (8, 8, 1, 4),    # exact page multiple, 1-page chunks
                          (8, 13, 2, 2),   # ragged tail + GQA 2x
                          (4, 21, 4, 1),   # many chunks + GQA 4x
                          (16, 9, 2, 4)])  # page bigger than half the prompt
def test_paged_prefill_matches_contiguous_scatter(page_size, plen, chunk_pages,
                                                  n_kv):
    """Acceptance: chunked paged prefill == contiguous prefill +
    ``write_prefill`` scatter to <=1e-5 on the K/V pool contents (live token
    rows) AND on next-token logits, across ragged prompt lengths, page sizes
    and GQA ratios."""
    cfg = get_config("opt-tiny").reduced(n_layers=2, d_model=64, d_ff=128,
                                         vocab_size=256, n_heads=4,
                                         n_kv_heads=n_kv)
    params_q = pack_model(init_params(jax.random.PRNGKey(0), cfg),
                          QuantConfig(bits=2, group_size=32))
    prompt = np.random.default_rng(plen).integers(
        0, cfg.vocab_size, size=plen).astype(np.int32)
    def mk():
        return PagedKVCache(cfg, n_pages=16, page_size=page_size,
                            max_pages_per_seq=8)
    # reference: the v1 admit path (contiguous prefill, then scatter)
    ref_cache = mk()
    n_pages = ref_cache.pages_for(plen)
    ids = ref_cache.allocator.alloc(n_pages)
    s_pad = n_pages * page_size
    toks = np.zeros((1, s_pad), np.int32)
    toks[0, :plen] = prompt
    logits_ref, kv = prefill(params_q, cfg, jnp.asarray(toks), s_pad)
    ref_cache.write_prefill(ids, kv, plen)
    # v2: chunks written straight into the same page ids
    new_cache = mk()
    assert new_cache.allocator.alloc(n_pages) == ids
    last = _chunked_prefill(cfg, params_q, new_cache, ids, prompt, chunk_pages)
    want = ref_cache.gather_tokens(ids, plen)
    got = new_cache.gather_tokens(ids, plen)
    for key in want:
        np.testing.assert_allclose(np.asarray(got[key]),
                                   np.asarray(want[key]),
                                   rtol=1e-5, atol=1e-5, err_msg=key)
    np.testing.assert_allclose(
        np.asarray(last[: cfg.vocab_size]),
        np.asarray(logits_ref[0, plen - 1, : cfg.vocab_size]),
        rtol=1e-5, atol=1e-4)


def test_paged_prefill_int8_pool_matches_scatter():
    """int8 pools: the chunk writer must quantize with the same per-(slot,
    head) convention as the contiguous cache, code-for-code."""
    cfg = get_config("opt-tiny").reduced(n_layers=2, d_model=64, d_ff=128,
                                         vocab_size=256, n_heads=4,
                                         n_kv_heads=4)
    cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    params_q = pack_model(init_params(jax.random.PRNGKey(0), cfg),
                          QuantConfig(bits=2, group_size=32))
    prompt = np.random.default_rng(5).integers(
        0, cfg.vocab_size, size=11).astype(np.int32)
    def mk():
        return PagedKVCache(cfg, n_pages=12, page_size=4,
                            max_pages_per_seq=6)
    ref_cache, new_cache = mk(), mk()
    ids = ref_cache.allocator.alloc(ref_cache.pages_for(11))
    assert new_cache.allocator.alloc(len(ids)) == ids
    toks = np.zeros((1, len(ids) * 4), np.int32)
    toks[0, :11] = prompt
    _, kv = prefill(params_q, cfg, jnp.asarray(toks), len(ids) * 4)
    ref_cache.write_prefill(ids, kv, 11)
    _chunked_prefill(cfg, params_q, new_cache, ids, prompt, chunk_pages=2)
    want = ref_cache.gather_tokens(ids, 11)
    got = new_cache.gather_tokens(ids, 11)
    assert got["k"].dtype == jnp.int8
    for key in want:  # int8 codes must agree exactly, scales to fp tolerance
        np.testing.assert_allclose(np.asarray(got[key], np.float32),
                                   np.asarray(want[key], np.float32),
                                   rtol=1e-5, atol=2e-5, err_msg=key)


def test_admit_path_never_runs_contiguous_prefill(packed_tiny, monkeypatch):
    """Acceptance: no ``(1, s_pad)`` contiguous KV buffer on the admit path —
    the batcher must not call ``write_prefill`` (the scatter copy) nor
    ``models.prefill`` (the contiguous cache builder) at all."""
    cfg, params_q = packed_tiny

    def boom(*a, **k):
        raise AssertionError("contiguous prefill path used on admit")

    monkeypatch.setattr(PagedKVCache, "write_prefill", boom)
    monkeypatch.setattr("repro.models.model.prefill", boom)
    cache = PagedKVCache(cfg, n_pages=24, page_size=8, max_pages_per_seq=4)
    b = ContinuousBatcher(params_q, cfg, cache, max_batch=2,
                          prefill_chunk_pages=1)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 12, 9)]
    outs = b.run([PagedRequest(prompt=p, max_new=3) for p in prompts])
    for p, out in zip(prompts, outs):
        assert out == _greedy_oracle(params_q, cfg, p, 3)
    assert b.stats["prefill_chunks"] >= sum(
        cache.pages_for(len(p)) for p in prompts)


def test_gqa_server_end_to_end_matches_greedy_oracle():
    """GQA config through the WHOLE v2 stack (chunked GQA prefill + fused
    GQA paged decode): every request equals its own greedy chain."""
    cfg = get_config("opt-tiny").reduced(n_layers=2, d_model=64, d_ff=128,
                                         vocab_size=256, n_heads=4,
                                         n_kv_heads=2)
    params_q = pack_model(init_params(jax.random.PRNGKey(0), cfg),
                          QuantConfig(bits=2, group_size=32))
    cache = PagedKVCache(cfg, n_pages=24, page_size=8, max_pages_per_seq=4)
    b = ContinuousBatcher(params_q, cfg, cache, max_batch=2,
                          prefill_chunk_pages=2)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (4, 9, 14)]
    outs = b.run([PagedRequest(prompt=p, max_new=4) for p in prompts])
    for p, out in zip(prompts, outs):
        assert out == _greedy_oracle(params_q, cfg, p, 4)


# ---------------------------------------------------------------------------
# Page allocator
# ---------------------------------------------------------------------------

def test_allocator_reuse_and_exhaustion():
    a = PageAllocator(n_pages=6)  # 5 usable (page 0 reserved)
    first = a.alloc(3)
    assert len(first) == 3 and NULL_PAGE not in first
    assert a.alloc(3) is None, "all-or-nothing: only 2 left"
    assert a.num_free == 2, "failed alloc must not leak pages"
    a.free(first)
    again = a.alloc(5)
    assert sorted(again) == sorted(set(again)), "no duplicate grants"
    assert set(first) <= set(again), "freed pages are reused"
    assert a.alloc(1) is None and a.num_free == 0


def test_allocator_rejects_double_free():
    a = PageAllocator(n_pages=4)
    ids = a.alloc(2)
    a.free(ids[:1])
    with pytest.raises(ValueError):
        a.free(ids[:1])
    with pytest.raises(ValueError):
        a.free([NULL_PAGE])  # the reserved page is never allocatable


# ---------------------------------------------------------------------------
# Continuous batcher
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def packed_tiny():
    cfg = get_config("opt-tiny").reduced(n_layers=2, d_model=64, d_ff=128,
                                         vocab_size=256, n_heads=4,
                                         n_kv_heads=4)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, pack_model(params, QuantConfig(bits=2, group_size=32))


def _greedy_oracle(params_q, cfg, prompt, n):
    seq = list(prompt)
    out = []
    for _ in range(n):
        logits = forward(params_q, cfg, jnp.asarray([seq], dtype=jnp.int32))
        nxt = int(jnp.argmax(logits[0, -1, : cfg.vocab_size]))
        out.append(nxt)
        seq.append(nxt)
    return out


def test_batcher_admit_order_and_reclamation(packed_tiny):
    """More requests than slots: FIFO admission, per-request greedy outputs
    exact, and every page returns to the free list at the end."""
    cfg, params_q = packed_tiny
    cache = PagedKVCache(cfg, n_pages=24, page_size=8, max_pages_per_seq=4)
    b = ContinuousBatcher(params_q, cfg, cache, max_batch=2)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 9, 13, 3, 8)]  # 8 = exact page multiple
    outs = b.run([PagedRequest(prompt=p, max_new=4) for p in prompts])
    for p, out in zip(prompts, outs):
        assert out == _greedy_oracle(params_q, cfg, p, 4)
    assert b.stats["prefills"] == 5 and not b.queue
    assert cache.allocator.num_free == cache.n_pages - cache.allocator.reserved


def test_batcher_eviction_under_page_pressure(packed_tiny):
    """A pool too small for the offered load must preempt (newest first),
    re-admit, and still produce the exact greedy continuation."""
    cfg, params_q = packed_tiny
    cache = PagedKVCache(cfg, n_pages=7, page_size=4, max_pages_per_seq=6)
    b = ContinuousBatcher(params_q, cfg, cache, max_batch=3)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (6, 8, 11)]
    outs = b.run([PagedRequest(prompt=p, max_new=8) for p in prompts])
    assert b.stats["evictions"] >= 1, "this pool size must force preemption"
    for p, out in zip(prompts, outs):
        assert out == _greedy_oracle(params_q, cfg, p, 8)
    assert cache.allocator.num_free == cache.n_pages - cache.allocator.reserved


def test_batcher_int8_pages(packed_tiny):
    """int8 page pools serve end to end; memory accounting sees the pool."""
    cfg, params_q = packed_tiny
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    cache = PagedKVCache(cfg8, n_pages=16, page_size=8, max_pages_per_seq=4)
    assert set(cache.pools) == {"k", "v", "k_scale", "v_scale"}
    b = ContinuousBatcher(params_q, cfg8, cache, max_batch=2)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, size=6).astype(np.int32)
               for _ in range(3)]
    outs = b.run([PagedRequest(prompt=p, max_new=4) for p in prompts])
    assert all(len(o) == 4 for o in outs)
    rep = serving_memory_report(params_q, cache.pools)
    assert rep["kv_bytes"] == cache_bytes(cache.pools) == cache.pool_bytes()
    assert 0.0 < rep["kv_fraction"] < 1.0


def test_batcher_rejects_oversized_request(packed_tiny):
    cfg, params_q = packed_tiny
    cache = PagedKVCache(cfg, n_pages=16, page_size=8, max_pages_per_seq=2)
    b = ContinuousBatcher(params_q, cfg, cache, max_batch=2)
    with pytest.raises(ValueError):
        b.submit(PagedRequest(prompt=np.zeros(15, np.int32), max_new=4))
    with pytest.raises(ValueError, match="empty prompt"):
        b.submit(PagedRequest(prompt=np.zeros(0, np.int32), max_new=4))


def test_paged_cache_rejects_stateless_archs():
    cfg = get_config("mamba2-2.7b").reduced()
    with pytest.raises(ValueError):
        PagedKVCache(cfg, n_pages=8, page_size=8, max_pages_per_seq=2)


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_step_lowers_from_dryrun_structs(kv_dtype):
    """The dryrun-facing specs (steps.paged_pool_structs + qparam_structs)
    must lower the paged decode step without allocating — and the structs
    must be the exact layout PagedKVCache allocates (derived, not
    duplicated)."""
    from repro.core.quant import QuantConfig as QC
    from repro.launch.steps import (make_paged_serve_step, paged_pool_structs,
                                    qparam_structs)
    cfg = get_config("opt-tiny").reduced(n_layers=2, d_model=64, d_ff=128,
                                         vocab_size=256, n_heads=4,
                                         n_kv_heads=4)
    cfg = dataclasses.replace(cfg, kv_cache_dtype=kv_dtype)
    B, P, n_pages, psz = 2, 3, 7, 8
    pools = paged_pool_structs(cfg, n_pages, psz)
    live = PagedKVCache(cfg, n_pages=n_pages, page_size=psz,
                        max_pages_per_seq=P).pools
    assert jax.tree.structure(pools) == jax.tree.structure(live)
    assert ([(s.shape, s.dtype) for s in jax.tree.leaves(pools)]
            == [(a.shape, a.dtype) for a in jax.tree.leaves(live)])
    args = (qparam_structs(cfg, QC(bits=2, group_size=32)),
            jax.ShapeDtypeStruct((B, 1), jnp.int32), pools,
            jax.ShapeDtypeStruct((B, P), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32))
    tok_s, pools_s = jax.eval_shape(make_paged_serve_step(cfg), *args)
    assert tok_s.shape == (B, 1)
    assert jax.tree.structure(pools_s) == jax.tree.structure(pools)


# ---------------------------------------------------------------------------
# Dead-page skipping (pl.when on page index vs sequence length)
# ---------------------------------------------------------------------------

def test_paged_decode_short_seqs_deep_pool_equivalence():
    """Short sequences in DEEP pools (many dead block-table slots) — the
    skip path (compute gated by pl.when, dead slots clamped to the last live
    page so no fresh DMA is issued) must be exactly equivalent to the dense
    oracle, including a one-token sequence in a 16-page table."""
    psz, P, B, H, Dh = 8, 16, 4, 4, 16
    q, kp, vp, bt, _, _, _ = _random_paged(
        21, B=B, H=H, Hkv=H, Dh=Dh, page_size=psz, n_pages=B * P + 1,
        max_pages=P)
    lens = jnp.asarray([1, psz, psz + 3, P * psz], jnp.int32)  # 1..full
    out = paged_decode(q, kp, vp, bt, lens)
    want = paged_decode_ref(q, kp, vp, bt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # partials too (the dist merge contract must see identical (acc, m, l))
    acc, m, l = paged_decode(q, kp, vp, bt, lens, normalize=False)
    acc_r, m_r, l_r = paged_decode_ref(q, kp, vp, bt, lens, normalize=False)
    np.testing.assert_allclose(np.asarray(m), np.asarray(m_r), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(l), np.asarray(l_r), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(acc_r), rtol=1e-5,
                               atol=1e-5)


def test_paged_decode_skipped_pages_never_read():
    """Poison every PHYSICAL page beyond each sequence's live count with NaN:
    the skip path must never let a NaN reach the output (NaN would survive
    any masking arithmetic, unlike the masked-softmax zeros)."""
    psz, P, B, H = 4, 8, 2, 2
    q, kp, vp, bt, _, _, _ = _random_paged(
        13, B=B, H=H, Hkv=H, Dh=8, page_size=psz, n_pages=B * P + 1,
        max_pages=P)
    lens = jnp.asarray([3, 2 * psz], jnp.int32)
    want = paged_decode(q, kp, vp, bt, lens)
    kp2, vp2 = kp, vp
    for b in range(B):
        n_live = -(-int(lens[b]) // psz)
        for p in range(n_live, P):
            pg = int(bt[b, p])
            kp2 = kp2.at[pg].set(jnp.nan)
            vp2 = vp2.at[pg].set(jnp.nan)
    out = paged_decode(q, kp2, vp2, bt, lens)
    assert not bool(jnp.any(jnp.isnan(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Sampling (temperature / top-k) in the paged decode step
# ---------------------------------------------------------------------------

def test_sampling_step_seeded_determinism(packed_tiny):
    """Same per-sequence keys => identical sampled tokens; different keys
    may differ; greedy step signature/output stays byte-identical."""
    from repro.serving import (make_paged_decode_step, sample_step_keys,
                               PagedKVCache)
    cfg, params_q = packed_tiny
    cache = PagedKVCache(cfg, n_pages=16, page_size=8, max_pages_per_seq=4)
    B, P = 2, 4
    ids = cache.allocator.alloc(B * P)
    bt = jnp.asarray(np.asarray(ids).reshape(B, P), jnp.int32)
    lens = jnp.asarray([5, 9], jnp.int32)
    toks = jnp.asarray([[7], [11]], jnp.int32)
    greedy = jax.jit(make_paged_decode_step(cfg))
    sampled = jax.jit(make_paged_decode_step(cfg, temperature=0.8, top_k=8))
    keys = sample_step_keys(jax.random.PRNGKey(42), B)
    t1, _ = sampled(params_q, toks, cache.pools, bt, lens, keys)
    t2, _ = sampled(params_q, toks, cache.pools, bt, lens, keys)
    assert np.array_equal(np.asarray(t1), np.asarray(t2)), "seeded => identical"
    assert t1.shape == (B, 1) and t1.dtype == jnp.int32
    assert bool(jnp.all((t1 >= 0) & (t1 < cfg.vocab_size)))
    # greedy default: unchanged 5-arg signature and argmax selection
    tg, _ = greedy(params_q, toks, cache.pools, bt, lens)
    assert tg.shape == (B, 1)


def test_sampling_cold_temperature_is_greedy(packed_tiny):
    """T->0 and top_k=1 must both reproduce the greedy argmax exactly."""
    from repro.serving import (make_paged_decode_step, sample_step_keys,
                               PagedKVCache)
    cfg, params_q = packed_tiny
    cache = PagedKVCache(cfg, n_pages=16, page_size=8, max_pages_per_seq=4)
    B, P = 2, 4
    ids = cache.allocator.alloc(B * P)
    bt = jnp.asarray(np.asarray(ids).reshape(B, P), jnp.int32)
    lens = jnp.asarray([4, 7], jnp.int32)
    toks = jnp.asarray([[3], [5]], jnp.int32)
    keys = sample_step_keys(jax.random.PRNGKey(0), B)
    tg, _ = jax.jit(make_paged_decode_step(cfg))(
        params_q, toks, cache.pools, bt, lens)
    t_cold, _ = jax.jit(make_paged_decode_step(cfg, temperature=1e-6))(
        params_q, toks, cache.pools, bt, lens, keys)
    t_top1, _ = jax.jit(make_paged_decode_step(cfg, temperature=5.0, top_k=1))(
        params_q, toks, cache.pools, bt, lens, keys)
    assert np.array_equal(np.asarray(tg), np.asarray(t_cold))
    assert np.array_equal(np.asarray(tg), np.asarray(t_top1))


def test_sample_logits_top_k_support():
    """top-k sampling never leaves the k highest logits."""
    from repro.serving import sample_logits, sample_step_keys
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(4, 64)),
                         jnp.float32)
    top_rows = np.argsort(np.asarray(logits), axis=-1)[:, -8:]
    for seed in range(5):
        keys = sample_step_keys(jax.random.PRNGKey(seed), 4)
        toks = sample_logits(logits, keys, temperature=3.0, top_k=8)
        for b in range(4):
            assert int(toks[b]) in set(top_rows[b].tolist())


def test_sample_logits_per_seq_matches_static():
    """The per-sequence path must agree row-for-row with the static-config
    sampler at the same (key, temperature, top_k), and take the exact argmax
    on temperature <= 0 rows."""
    from repro.serving import (sample_logits, sample_logits_per_seq,
                               sample_step_keys)
    logits = jnp.asarray(np.random.default_rng(1).normal(size=(4, 64)),
                         jnp.float32)
    keys = sample_step_keys(jax.random.PRNGKey(7), 4)
    temps = jnp.asarray([0.0, 0.8, 2.0, 0.8], jnp.float32)
    top_ks = jnp.asarray([0, 8, 0, 5], jnp.int32)
    got = sample_logits_per_seq(logits, keys, temps, top_ks)
    assert int(got[0]) == int(jnp.argmax(logits[0]))
    for b in (1, 2, 3):
        want = sample_logits(logits[b: b + 1], keys[b: b + 1],
                             temperature=float(temps[b]),
                             top_k=int(top_ks[b]))
        assert int(got[b]) == int(want[0])


# ---------------------------------------------------------------------------
# Per-request sampling through the batcher (serving v2)
# ---------------------------------------------------------------------------

def _mixed_requests(cfg, seed=6):
    rng = np.random.default_rng(seed)
    def mk(n):
        return rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
    return [
        PagedRequest(prompt=mk(5), max_new=5),                      # greedy
        PagedRequest(prompt=mk(9), max_new=5, temperature=0.9,
                     top_k=16, seed=11),
        PagedRequest(prompt=mk(7), max_new=5, temperature=1.3, seed=12),
    ]


def test_batcher_mixed_greedy_and_sampled(packed_tiny):
    """Greedy and sampled requests share decode steps: the greedy request
    must still equal its greedy chain EXACTLY, sampled requests are
    deterministic in their seeds and stay in-vocab."""
    cfg, params_q = packed_tiny

    def serve():
        cache = PagedKVCache(cfg, n_pages=24, page_size=8, max_pages_per_seq=4)
        b = ContinuousBatcher(params_q, cfg, cache, max_batch=3)
        return b.run(_mixed_requests(cfg))

    outs1, outs2 = serve(), serve()
    assert outs1 == outs2, "same seeds => identical serve output"
    greedy_req = _mixed_requests(cfg)[0]
    assert outs1[0] == _greedy_oracle(params_q, cfg, greedy_req.prompt, 5)
    for out in outs1[1:]:
        assert len(out) == 5
        assert all(0 <= t < cfg.vocab_size for t in out)


def test_sampling_keys_survive_preemption(packed_tiny):
    """The SAME sampled streams must come out whether or not a request was
    recompute-preempted mid-generation: keys derive from (seed, token index),
    not from the schedule. A page-starved pool (forces evictions) and a roomy
    pool (none) must produce identical outputs."""
    cfg, params_q = packed_tiny

    def serve(n_pages, page_size, max_pages):
        cache = PagedKVCache(cfg, n_pages=n_pages, page_size=page_size,
                             max_pages_per_seq=max_pages)
        b = ContinuousBatcher(params_q, cfg, cache, max_batch=3)
        rng = np.random.default_rng(1)
        reqs = [PagedRequest(
            prompt=rng.integers(0, cfg.vocab_size, size=n).astype(np.int32),
            max_new=8, temperature=0.7, top_k=12, seed=100 + i)
            for i, n in enumerate((6, 8, 11))]
        return b.run(reqs), b.stats

    starved, stats_s = serve(n_pages=7, page_size=4, max_pages=6)
    roomy, stats_r = serve(n_pages=32, page_size=4, max_pages=6)
    assert stats_s["evictions"] >= 1, "starved pool must preempt"
    assert stats_r["evictions"] == 0
    assert starved == roomy, \
        "preemption must not fork a request's sample stream"


def test_sampling_preemption_padded_vocab_stream_identical():
    """Regression: with vocab_size NOT a multiple of vocab_pad_multiple the
    LM head emits padded-V logits; admit-time sampling must draw over the
    SAME full-width masked row as the jitted step (categorical draws depend
    on array width), or preemption forks the stream."""
    cfg = get_config("opt-tiny").reduced(n_layers=2, d_model=64, d_ff=128,
                                         vocab_size=250, n_heads=4,
                                         n_kv_heads=4)
    assert cfg.padded_vocab > cfg.vocab_size
    params_q = pack_model(init_params(jax.random.PRNGKey(0), cfg),
                          QuantConfig(bits=2, group_size=32))

    def serve(n_pages):
        cache = PagedKVCache(cfg, n_pages=n_pages, page_size=4,
                             max_pages_per_seq=6)
        b = ContinuousBatcher(params_q, cfg, cache, max_batch=3)
        rng = np.random.default_rng(1)
        reqs = [PagedRequest(
            prompt=rng.integers(0, cfg.vocab_size, size=n).astype(np.int32),
            max_new=6, temperature=0.9, top_k=20, seed=40 + i)
            for i, n in enumerate((6, 8, 11))]
        return b.run(reqs), b.stats

    starved, stats_s = serve(n_pages=7)
    roomy, _ = serve(n_pages=32)
    assert stats_s["evictions"] >= 1
    assert starved == roomy
    assert all(0 <= t < cfg.vocab_size for out in starved for t in out)


def test_preempt_near_completion_respects_max_new(packed_tiny):
    """Regression (ISSUE 4): a request preempted one token short of its
    budget must re-admit, finish with EXACTLY max_new tokens (admit-time
    prefill must not over-append), and still match its greedy chain — with
    ``run()`` no longer truncating outputs."""
    cfg, params_q = packed_tiny
    cache = PagedKVCache(cfg, n_pages=24, page_size=4, max_pages_per_seq=6)
    b = ContinuousBatcher(params_q, cfg, cache, max_batch=2)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (6, 9)]
    reqs = [PagedRequest(prompt=p, max_new=4) for p in prompts]
    for r in reqs:
        b.submit(r)
    # run until the younger request is one token short of done
    while len(reqs[1].out) < reqs[1].max_new - 1:
        assert b.step() > 0
    # force recompute preemption of the newest (= reqs[1]) slot
    assert b._evict_newest()
    assert len(reqs[1].out) == reqs[1].max_new - 1
    while b.queue or any(s is not None for s in b.slots):
        b.step()
    assert b.stats["evictions"] >= 1
    for r, p in zip(reqs, prompts):
        assert len(r.out) == r.max_new, "generation must stop AT the budget"
        assert r.out == _greedy_oracle(params_q, cfg, p, r.max_new)


def test_admit_skips_already_complete_requests(packed_tiny):
    """A queued request whose budget is already spent (preempted at the
    finish line) must go straight to done — no prefill, no page churn."""
    cfg, params_q = packed_tiny
    cache = PagedKVCache(cfg, n_pages=24, page_size=8, max_pages_per_seq=4)
    b = ContinuousBatcher(params_q, cfg, cache, max_batch=2)
    done = PagedRequest(prompt=np.asarray([5, 7], np.int32), max_new=2,
                        out=[1, 2])
    b.queue.append(done)
    assert b._admit_one()
    assert done in b.done and done.out == [1, 2]
    assert b.stats["prefills"] == 0
    assert cache.allocator.num_free == cache.n_pages - cache.allocator.reserved
