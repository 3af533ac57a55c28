"""Kernel microbenchmarks: quant_matmul / group_quant / paged_decode vs
their jnp references.

On this CPU container the Pallas kernels run in interpret mode (slow by
construction); the numbers that matter here are the REFERENCE-path timings
and the analytic HBM-traffic derivation for the TPU target printed as
``derived`` (weight-bytes ratio = the roofline win of the fused kernel; for
paged decode, live-page bytes vs the max_len-capacity cache read).

Rows also land in ``artifacts/benchmarks/BENCH_kernels.json`` so CI can
upload them and a perf trajectory accumulates across commits.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import ART, emit, timed
from repro.core.invariance import apply_rotation_cols
from repro.core.quant import QuantConfig, quantize_tensor
from repro.kernels.ref import (group_quant_ref, paged_decode_ref,
                               quant_matmul_ref, transform_quant_ref)


def run():
    rows = []

    def record(name, us, derived):
        emit(name, us, derived)
        rows.append({"name": name, "us_per_call": round(us, 1),
                     "derived": derived})

    key = jax.random.PRNGKey(0)
    for (M, K, N, bits, G) in [(8, 2048, 2048, 2, 128), (8, 2048, 2048, 4, 128),
                               (128, 1024, 1024, 2, 64)]:
        w = jax.random.normal(key, (K, N))
        x = jax.random.normal(key, (M, K))
        qt = quantize_tensor(w, QuantConfig(bits=bits, group_size=G))
        f = jax.jit(lambda x, p, s, z: quant_matmul_ref(x, p, s, z, bits, G))
        f(x, qt.packed, qt.scale, qt.zero)[0].block_until_ready()  # warm
        _, us = timed(lambda: jax.block_until_ready(
            f(x, qt.packed, qt.scale, qt.zero)), repeat=5)
        dense_bytes = K * N * 2
        packed_bytes = qt.memory_bytes()
        record(f"kernel/quant_matmul/{M}x{K}x{N}b{bits}", us,
               f"weight_hbm_ratio={dense_bytes/packed_bytes:.2f}x")

    for (K, N, bits, G) in [(2048, 2048, 2, 128), (4096, 1024, 4, 64)]:
        w = jax.random.normal(key, (K, N))
        f = jax.jit(lambda w: group_quant_ref(w, bits, G))
        jax.block_until_ready(f(w))
        _, us = timed(lambda: jax.block_until_ready(f(w)), repeat=5)
        # fused kernel: 1 read + 1 write vs 4 passes un-fused
        record(f"kernel/group_quant/{K}x{N}b{bits}", us, "fused_hbm_passes=2_of_8")

    # fused transform+fake-quant (the population search's per-proposal hot
    # path) vs materialize-then-quantize. ``derived``: the fused kernel reads
    # the weight once and writes the roundtrip once (2 HBM passes) where the
    # unfused path also materializes T(θ) in fp32 and re-reads it to quantize
    # (4 passes) — a 2x weight-traffic cut per proposal on the TPU target.
    # CPU proxy: one composed XLA program vs two jit programs with a real
    # materialization boundary between them.
    for (F, G) in [(256, 64), (512, 128), (512, 32)]:
        D, bits = 256, 2
        w = jax.random.normal(key, (D, F))
        pi = jax.random.permutation(jax.random.PRNGKey(1), F).astype(jnp.int32)
        s = 1.0 + 0.05 * jax.random.normal(jax.random.PRNGKey(2), (F,))
        phi = 1e-3 * jax.random.normal(jax.random.PRNGKey(3), (F // 2,))
        t_stage = jax.jit(lambda w, pi, s, phi:
                          (apply_rotation_cols(w, phi) * s[None, :])[:, pi])
        q_stage = jax.jit(lambda t: group_quant_ref(t, bits, G)[0])
        fused = jax.jit(lambda w, pi, s, phi: transform_quant_ref(
            w, pi, s, phi, bits=bits, group=G, mode="up")[0])
        jax.block_until_ready(q_stage(t_stage(w, pi, s, phi)))  # warm
        jax.block_until_ready(fused(w, pi, s, phi))
        _, us_mat = timed(lambda: jax.block_until_ready(
            q_stage(jax.block_until_ready(t_stage(w, pi, s, phi)))), repeat=5)
        _, us_fused = timed(lambda: jax.block_until_ready(
            fused(w, pi, s, phi)), repeat=5)
        record(f"kernel/transform_quant/F{F}g{G}/materialize", us_mat,
               "weight_hbm_passes=4")
        record(f"kernel/transform_quant/F{F}g{G}/fused", us_fused,
               f"weight_hbm_passes=2_of_4={us_mat/max(us_fused, 1e-9):.2f}x_cpu")

    # paged decode attention: B sequences at ragged depths over a page pool.
    # ``derived``: CAPACITY ratio — tokens a contiguous (B, max_len) cache
    # must hold in HBM vs the page-granular live allocation. Since the
    # dead-page skip (pl.when on page index vs length + clamped block
    # index), the same ratio bounds the kernel's decode READ traffic too:
    # dead block-table slots issue no DMA, so reads track live pages.
    for (B, H, Dh, psz, max_pages, fill) in [(8, 8, 64, 16, 16, 0.5),
                                             (16, 8, 64, 32, 8, 0.25)]:
        n_pages = B * max_pages + 1
        kp = jax.random.normal(key, (n_pages, H, psz, Dh))
        vp = jax.random.normal(key, (n_pages, H, psz, Dh))
        q = jax.random.normal(key, (B, H, Dh))
        bt = jnp.asarray(
            1 + np.arange(B * max_pages).reshape(B, max_pages), jnp.int32)
        lens = jnp.full((B,), int(max_pages * psz * fill), jnp.int32)
        f = jax.jit(lambda q, kp, vp, bt, lens: paged_decode_ref(
            q, kp, vp, bt, lens))
        jax.block_until_ready(f(q, kp, vp, bt, lens))
        _, us = timed(lambda: jax.block_until_ready(f(q, kp, vp, bt, lens)),
                      repeat=5)
        live_pages = B * -(-int(lens[0]) // psz)  # page-granular allocation
        cap_pages = B * max_pages
        record(f"kernel/paged_decode/B{B}xH{H}xD{Dh}p{psz}", us,
               f"capacity_vs_live_pages={cap_pages/max(live_pages, 1):.2f}x")

    # fused-GQA paged decode: the per-query-head grid DMAs each KV head's
    # page ``rep = H // Hkv`` times per decode token; the (B, Hkv, P) fused
    # grid loads it ONCE and batches the group's query heads against it in
    # VMEM. ``derived``: the KV-page HBM read cut (the decode-dominant term).
    # CPU proxy: repeat-KV-to-H-heads oracle vs a grouped einsum that never
    # repeats the pool.
    for (B, H, Hkv, Dh, psz, max_pages) in [(8, 8, 2, 64, 16, 8),
                                            (8, 16, 4, 64, 16, 8)]:
        rep = H // Hkv
        n_pages = B * max_pages + 1
        kp = jax.random.normal(key, (n_pages, Hkv, psz, Dh))
        vp = jax.random.normal(key, (n_pages, Hkv, psz, Dh))
        q = jax.random.normal(key, (B, H, Dh))
        bt = jnp.asarray(
            1 + np.arange(B * max_pages).reshape(B, max_pages), jnp.int32)
        lens = jnp.full((B,), max_pages * psz, jnp.int32)
        unfused = jax.jit(lambda q, kp, vp, bt, lens: paged_decode_ref(
            q, kp, vp, bt, lens))

        def gqa_grouped_ref(q, kp, vp, bt, lens):
            # read each KV head once; queries grouped (B, Hkv, rep, Dh)
            Bq, Hq, D = q.shape
            P, ps = bt.shape[1], kp.shape[2]
            kf = kp[bt].swapaxes(2, 3).reshape(Bq, P * ps, Hkv, D)
            vf = vp[bt].swapaxes(2, 3).reshape(Bq, P * ps, Hkv, D)
            qg = q.reshape(Bq, Hkv, Hq // Hkv, D)
            s = jnp.einsum("bgrd,bsgd->bgrs", qg, kf) * D ** -0.5
            mask = jnp.arange(P * ps)[None, :] < lens[:, None]
            s = jnp.where(mask[:, None, None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bgrs,bsgd->bgrd", p, vf).reshape(Bq, Hq, D)

        fused = jax.jit(gqa_grouped_ref)
        jax.block_until_ready(unfused(q, kp, vp, bt, lens))
        jax.block_until_ready(fused(q, kp, vp, bt, lens))
        _, us_u = timed(lambda: jax.block_until_ready(
            unfused(q, kp, vp, bt, lens)), repeat=5)
        _, us_f = timed(lambda: jax.block_until_ready(
            fused(q, kp, vp, bt, lens)), repeat=5)
        record(f"kernel/paged_decode_gqa/H{H}kv{Hkv}/unfused", us_u,
               f"kv_page_reads_per_token={H}")
        record(f"kernel/paged_decode_gqa/H{H}kv{Hkv}/fused", us_f,
               f"kv_page_reads_per_token={Hkv}_of_{H}={rep}x_cut="
               f"{us_u / max(us_f, 1e-9):.2f}x_cpu")

        # multi-page inner grid axis (pages_per_block=MP): the fused kernel's
        # per-page (rep, psz) matmul is below MXU granularity for small rep;
        # staging MP pages per online-softmax update replaces MP tiny matmuls
        # with one (rep, MP*psz) one. CPU proxy: an online-softmax scan over
        # single pages vs over MP-page blocks — same math, matmul granularity
        # is the only variable.
        def make_blocked(mp):
            nblk = max_pages // mp

            def f(q, kp, vp, bt, lens):
                Bq, Hq, D = q.shape
                ps = kp.shape[2]
                kf = kp[bt].swapaxes(2, 3).reshape(Bq, nblk, mp * ps, Hkv, D)
                vf = vp[bt].swapaxes(2, 3).reshape(Bq, nblk, mp * ps, Hkv, D)
                qg = q.reshape(Bq, Hkv, Hq // Hkv, D)

                def body(carry, xs):
                    o, m, l, blk = carry
                    kb, vb = xs                       # (B, mp*ps, Hkv, D)
                    s = jnp.einsum("bgrd,bsgd->bgrs", qg, kb) * D ** -0.5
                    pos = blk * (mp * ps) + jnp.arange(mp * ps)
                    msk = pos[None, :] < lens[:, None]
                    s = jnp.where(msk[:, None, None], s, -1e30)
                    m2 = jnp.maximum(m, s.max(-1))
                    prob = jnp.where(msk[:, None, None],
                                     jnp.exp(s - m2[..., None]), 0.0)
                    corr = jnp.exp(m - m2)
                    o = o * corr[..., None] + jnp.einsum("bgrs,bsgd->bgrd",
                                                         prob, vb)
                    return (o, m2, l * corr + prob.sum(-1), blk + 1), None

                init = (jnp.zeros((Bq, Hkv, Hq // Hkv, D)),
                        jnp.full((Bq, Hkv, Hq // Hkv), -1e30),
                        jnp.zeros((Bq, Hkv, Hq // Hkv)), jnp.int32(0))
                (o, m, l, _), _ = jax.lax.scan(
                    body, init, (kf.swapaxes(0, 1), vf.swapaxes(0, 1)))
                return (o / jnp.maximum(l, 1e-30)[..., None]
                        ).reshape(Bq, Hq, D)
            return jax.jit(f)

        one = make_blocked(1)
        blk4 = make_blocked(4)
        np.testing.assert_allclose(
            np.asarray(one(q, kp, vp, bt, lens)),
            np.asarray(blk4(q, kp, vp, bt, lens)), atol=1e-5)
        _, us_1 = timed(lambda: jax.block_until_ready(
            one(q, kp, vp, bt, lens)), repeat=5)
        _, us_4 = timed(lambda: jax.block_until_ready(
            blk4(q, kp, vp, bt, lens)), repeat=5)
        record(f"kernel/paged_decode_gqa/H{H}kv{Hkv}/fused_mp1", us_1,
               f"matmul_shape={rep}x{psz}_per_update")
        record(f"kernel/paged_decode_gqa/H{H}kv{Hkv}/fused_mp4", us_4,
               f"matmul_shape={rep}x{4 * psz}_per_update="
               f"{us_1 / max(us_4, 1e-9):.2f}x_cpu")

    # chunked paged prefill: prompt K/V written straight into pages, chunk
    # attention streamed page-by-page from the pool. ``derived``: admit
    # tokens/sec through the attention path plus the copy the v1 admit no
    # longer pays (contiguous prefill + write_prefill scatter re-touched
    # every prompt KV byte once more).
    from repro.serving.prefill import paged_prefill_attention
    for (plen, psz, H, Dh, chunk_pages) in [(256, 16, 8, 64, 4),
                                            (512, 32, 8, 64, 4)]:
        n_pages = plen // psz + 1
        pools = {"k": jax.random.normal(key, (n_pages, H, psz, Dh)),
                 "v": jax.random.normal(key, (n_pages, H, psz, Dh))}
        bt = jnp.asarray(1 + np.arange(plen // psz), jnp.int32)[None]
        C = chunk_pages * psz
        f = jax.jit(lambda q, pools, bt, off: paged_prefill_attention(
            q, pools, bt, off))
        q = jax.random.normal(key, (1, C, H, Dh))
        jax.block_until_ready(f(q, pools, bt, jnp.int32(0)))
        def run_chunks():
            for off in range(0, plen, C):
                jax.block_until_ready(f(q, pools, bt, jnp.int32(off)))
        _, us = timed(run_chunks, repeat=3)
        toks_per_s = plen / (us * 1e-6)
        kv_bytes = 2 * plen * H * Dh * 4
        record(f"kernel/paged_prefill/S{plen}p{psz}c{chunk_pages}", us,
               f"prefill_toks_per_s={toks_per_s:.0f};"
               f"admit_copy_bytes_saved={kv_bytes}")

    ART.mkdir(parents=True, exist_ok=True)
    (ART / "BENCH_kernels.json").write_text(json.dumps(rows, indent=1))
    return rows


if __name__ == "__main__":
    run()
