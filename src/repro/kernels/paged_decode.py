"""Paged single-token decode attention: gather KV pages via a block table.

Continuous batching stores the KV cache as a global pool of fixed-size pages
plus one block table per sequence mapping logical page slots to physical page
ids. The pool is HEAD-MAJOR:

    k_pages / v_pages : (N_pages, Hkv, page_size, Dh)
    k_scale / v_scale : (N_pages, Hkv, page_size)      (int8 pools only)

so one (KV head, page) tile is the array's two minor dimensions whole — the
block shape the TPU compiler accepts for any page size and head dim (it
requires the last two block dims to be (8, 128)-tile multiples or the full
array dims). The scales' block is a page's whole (Hkv, page_size) tile; the
kernel selects its head's row in-register.

This kernel computes one decode token of attention per sequence WITHOUT
materialising a contiguous per-sequence cache: the block table and sequence
lengths are scalar-prefetched (SMEM), so each grid step's BlockSpec index map
DMAs exactly one physical page HBM→VMEM, and the online-softmax state
(m, l, acc) stays in VMEM across the page axis of the grid.

    out[b,h] = softmax(q[b,h] · K[pages(b),h%]ᵀ / sqrt(Dh)) · V[pages(b),h%]

Fully-masked pages (slot index at or past ``ceil(seq_len / page_size)``) are
skipped with ``pl.when`` and their block index is clamped to the last live
page so the dead steps issue no fresh DMA — short sequences in deep pools pay
only for their live pages. int8 pages carry per-(slot, head) absmax scales
and are dequantized in-register per page. With ``normalize=False`` the kernel
returns the raw partial stats (acc, m, l) instead of the normalized output —
the exact log-sum-exp partials ``repro.dist.attention.merge_partials`` merges
across sequence shards, so a sequence-sharded cache can be paged per shard.

Queries enter the kernel as (B, G, r, Dh) and the stats as (B, G, r, 1), so
every block again spans the two minor dims whole. Three grids share one
kernel body:

- ``paged_decode_pallas`` — grid (B, H, P), r = 1: one query head per step.
  Under GQA every query head re-DMAs its KV head's page, so each live page
  crosses HBM→VMEM ``rep = H // Hkv`` times per token.
- ``paged_decode_gqa_pallas`` — grid (B, Hkv, P), r = rep: one KV HEAD per
  step. The page is loaded ONCE and all ``rep`` query heads of the group are
  batched against it in VMEM ((rep, psz) score tile on the MXU), cutting
  decode's dominant HBM term — KV page reads — by the GQA ratio.
- ``pages_per_block > 1`` on the fused kernel adds a MULTI-PAGE INNER AXIS:
  grid (B, Hkv, ceil(P / MP), MP). Each inner step stages one DMA'd page into
  a (MP, psz, Dh) VMEM scratch tile and only the LAST inner step runs the
  (rep, MP*psz) score matmul + online-softmax update, so Pallas's inner-axis
  pipelining overlaps the next pages' DMA with one better-shaped matmul.

Score and value matmuls run at ``Precision.HIGHEST`` (full f32 on the MXU),
the precision of the ``kernels/ref.py`` oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_decode_pallas", "paged_decode_gqa_pallas"]

NEG = -1e30
_HI = jax.lax.Precision.HIGHEST


def _n_live(seq_len, page_size):
    # a sequence with seq_len == 0 keeps one "live" page whose slots are all
    # masked; its output stays the zero init
    return jnp.maximum((seq_len + page_size - 1) // page_size, 1)


def _page_f32(page_ref, scale_ref, head):
    """One (KV head, page) tile as (psz, Dh) f32, dequantized in-register.

    ``scale_ref`` holds the page's (Hkv, psz) scales for every head; the
    head's row is selected and turned into a (psz, 1) column with masked
    sums (exact: one nonzero term each), which keeps the multiply identical
    to the oracle's ``codes * scale[..., None]``.
    """
    x = page_ref[0, 0].astype(jnp.float32)                  # (psz, Dh)
    if scale_ref is None:
        return x
    sc = scale_ref[0].astype(jnp.float32)                   # (Hkv, psz)
    hsel = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0) == head
    row = jnp.sum(jnp.where(hsel, sc, 0.0), axis=0, keepdims=True)
    psz = sc.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (psz, psz), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (psz, psz), 1))
    col = jnp.sum(jnp.where(eye, jnp.broadcast_to(row, (psz, psz)), 0.0),
                  axis=1, keepdims=True)                    # (psz, 1)
    return x * col


def _init(o_ref, m_ref, l_ref):
    o_ref[...] = jnp.zeros_like(o_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG)
    l_ref[...] = jnp.zeros_like(l_ref)


def _attend(q_ref, kk, vv, pos0, seq_len, o_ref, m_ref, l_ref):
    """Online-softmax update of the (r, Dh) query tile over ``n`` keys
    (``kk``/``vv`` (n, Dh) f32) at absolute positions ``pos0 + [0, n)``."""
    q = q_ref[0, 0].astype(jnp.float32)                     # (r, Dh)
    n = kk.shape[0]
    s = jax.lax.dot_general(q, kk, (((1,), (1,)), ((), ())), precision=_HI,
                            preferred_element_type=jnp.float32)
    s = s * (q.shape[-1] ** -0.5)                           # (r, n)
    mask = pos0 + jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) < seq_len
    s = jnp.where(mask, s, NEG)
    m_prev = m_ref[0, 0]                                    # (r, 1)
    l_prev = l_ref[0, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    prob = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    o_ref[0, 0] = o_ref[0, 0] * corr + jnp.dot(
        prob, vv, precision=_HI, preferred_element_type=jnp.float32)
    m_ref[0, 0] = m_new
    l_ref[0, 0] = l_prev * corr + jnp.sum(prob, axis=1, keepdims=True)


def _finish(o_ref, l_ref):
    o_ref[0, 0] = o_ref[0, 0] / jnp.maximum(l_ref[0, 0], 1e-30)


def _split_refs(refs, quantized):
    """(k_scale, v_scale, o, m, l, *scratch) from the kernel's trailing refs
    (the scale inputs exist only for int8 pools)."""
    refs = list(refs)
    scales = (refs.pop(0), refs.pop(0)) if quantized else (None, None)
    return scales + tuple(refs)


def _kernel(bt_ref, sl_ref, q_ref, k_ref, v_ref, *refs, page_size, head_div,
            quantized, normalize):
    ks_ref, vs_ref, o_ref, m_ref, l_ref = _split_refs(refs, quantized)
    b = pl.program_id(0)
    p = pl.program_id(2)
    head = pl.program_id(1) // head_div                     # KV head
    n_live = _n_live(sl_ref[b], page_size)

    @pl.when(p == 0)
    def _():
        _init(o_ref, m_ref, l_ref)

    # pages at or past n_live contribute exactly zero: skip their compute
    # (the index maps clamp their block index, so no fresh DMA either)
    @pl.when(p < n_live)
    def _():
        kb = _page_f32(k_ref, ks_ref, head)
        vb = _page_f32(v_ref, vs_ref, head)
        _attend(q_ref, kb, vb, p * page_size, sl_ref[b], o_ref, m_ref, l_ref)

    if normalize:
        @pl.when(p == pl.num_programs(2) - 1)
        def _():
            _finish(o_ref, l_ref)


def _kernel_mp(bt_ref, sl_ref, q_ref, k_ref, v_ref, *refs, page_size,
               pages_per_block, quantized, normalize):
    ks_ref, vs_ref, o_ref, m_ref, l_ref, k_buf, v_buf = _split_refs(
        refs, quantized)
    b = pl.program_id(0)
    head = pl.program_id(1)
    blk = pl.program_id(2)                       # outer page-block
    i = pl.program_id(3)                         # inner page within block
    mp = pages_per_block
    n_live = _n_live(sl_ref[b], page_size)
    p = blk * mp + i                             # logical page slot

    @pl.when((blk == 0) & (i == 0))
    def _():
        _init(o_ref, m_ref, l_ref)

    # stage this inner step's page into the scratch tile (dequantized f32);
    # dead pages are ZEROED, not skipped — their positions are masked out of
    # the softmax below, but a zero row costs nothing while stale scratch
    # content could be NaN-poisoned garbage that 0-weight cannot cancel
    @pl.when(p < n_live)
    def _():
        k_buf[i] = _page_f32(k_ref, ks_ref, head)
        v_buf[i] = _page_f32(v_ref, vs_ref, head)

    @pl.when(p >= n_live)
    def _():
        k_buf[i] = jnp.zeros(k_buf.shape[1:], k_buf.dtype)
        v_buf[i] = jnp.zeros(v_buf.shape[1:], v_buf.dtype)

    # one online-softmax update per PAGE BLOCK: the (rep, mp*psz) matmul
    # replaces mp undersized (rep, psz) ones
    @pl.when((i == mp - 1) & (blk * mp < n_live))
    def _():
        kk = k_buf[...].reshape(mp * page_size, -1)        # (mp*psz, Dh)
        vv = v_buf[...].reshape(mp * page_size, -1)
        _attend(q_ref, kk, vv, blk * mp * page_size, sl_ref[b], o_ref, m_ref,
                l_ref)

    if normalize:
        @pl.when((blk == pl.num_programs(2) - 1) & (i == mp - 1))
        def _():
            _finish(o_ref, l_ref)


def _paged_call(q4, k_pages, v_pages, block_tables, seq_lens, k_scale,
                v_scale, *, head_div, pages_per_block, normalize, interpret):
    """Shared pallas_call over (B, G, r, Dh) queries; returns (B, H, Dh)
    output or the (acc, m, l) partials with (B, H) stats."""
    B, G, r, Dh = q4.shape
    _, Hkv, page_size, _ = k_pages.shape
    P = block_tables.shape[1]
    quantized = k_scale is not None
    mp = pages_per_block

    def live_page(bt, sl, b, p):
        # clamp dead page slots (p >= ceil(len/psz)) to the last live page:
        # the block index repeats across consecutive dead grid steps, so no
        # fresh DMA is issued for pages the kernel will skip
        return bt[b, jnp.minimum(p, _n_live(sl[b], page_size) - 1)]

    if mp > 1:
        grid = (B, G, -(-P // mp), mp)

        def slot(blk, i):
            return blk * mp + i
        kernel = functools.partial(_kernel_mp, page_size=page_size,
                                   pages_per_block=mp, quantized=quantized,
                                   normalize=normalize)
        scratch = [pltpu.VMEM((mp, page_size, Dh), jnp.float32),  # K stage
                   pltpu.VMEM((mp, page_size, Dh), jnp.float32)]  # V stage
    else:
        grid = (B, G, P)

        def slot(p):
            return p
        kernel = functools.partial(_kernel, page_size=page_size,
                                   head_div=head_div, quantized=quantized,
                                   normalize=normalize)
        scratch = []

    def head_map(b, g, *rest):
        return (b, g, 0, 0)

    def kv_map(b, g, *rest):
        *pos, bt, sl = rest
        return (live_page(bt, sl, b, slot(*pos)), g // head_div, 0, 0)

    def scale_map(b, g, *rest):
        *pos, bt, sl = rest
        return (live_page(bt, sl, b, slot(*pos)), 0, 0)

    kv_spec = pl.BlockSpec((1, 1, page_size, Dh), kv_map)
    in_specs = [pl.BlockSpec((1, 1, r, Dh), head_map), kv_spec, kv_spec]
    operands = [q4, k_pages, v_pages]
    if quantized:
        sc_spec = pl.BlockSpec((1, Hkv, page_size), scale_map)
        in_specs += [sc_spec, sc_spec]
        operands += [k_scale, v_scale]
    stat_spec = pl.BlockSpec((1, 1, r, 1), head_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, 1, r, Dh), head_map), stat_spec,
                   stat_spec],
        scratch_shapes=scratch,
    )
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, G, r, Dh), jnp.float32),
            jax.ShapeDtypeStruct((B, G, r, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, G, r, 1), jnp.float32),
        ],
        interpret=interpret,
        name="paged_decode",
    )(block_tables, seq_lens, *operands)
    H = G * r
    out = out.reshape(B, H, Dh)
    if normalize:
        return out
    return out, m.reshape(B, H), l.reshape(B, H)


def paged_decode_pallas(q, k_pages, v_pages, block_tables, seq_lens,
                        k_scale=None, v_scale=None, *, normalize: bool = True,
                        interpret: bool = False):
    """q: (B, H, Dh); k/v_pages: (N, Hkv, page_size, Dh) f32/bf16 or int8
    (+ scales (N, Hkv, page_size)); block_tables: (B, P) int32 physical page
    ids; seq_lens: (B,) int32.

    Block-table entries past a sequence's last used page may be arbitrary
    VALID page ids (the batcher pads with page 0): those positions are masked
    by ``seq_lens``. Returns (B, H, Dh) f32, or the unnormalized partial
    stats (acc (B, H, Dh), m (B, H), l (B, H)) when ``normalize=False``.
    """
    B, H, Dh = q.shape
    rep = H // k_pages.shape[1]
    return _paged_call(q.reshape(B, H, 1, Dh), k_pages, v_pages, block_tables,
                       seq_lens, k_scale, v_scale, head_div=rep,
                       pages_per_block=1, normalize=normalize,
                       interpret=interpret)


def paged_decode_gqa_pallas(q, k_pages, v_pages, block_tables, seq_lens,
                            k_scale=None, v_scale=None, *,
                            normalize: bool = True, interpret: bool = False,
                            pages_per_block: int = 1):
    """Fused-GQA paged decode: same contract as ``paged_decode_pallas``
    with a (B, Hkv, P) grid — each KV head's page is DMA'd once and its
    ``H // Hkv`` query heads are reduced against it in VMEM (query head h
    belongs to KV head h // rep, so the group is a contiguous reshape).

    ``pages_per_block > 1`` switches to the multi-page inner-axis grid
    (B, Hkv, ceil(P / MP), MP): pages stage into a VMEM scratch tile and one
    (rep, MP*psz) matmul per block overlaps the next pages' DMA — the small-
    ``rep`` regime where per-page matmuls are below MXU granularity.
    """
    B, H, Dh = q.shape
    Hkv = k_pages.shape[1]
    if H % Hkv != 0:
        raise ValueError(f"H={H} not a multiple of Hkv={Hkv}")
    return _paged_call(q.reshape(B, Hkv, H // Hkv, Dh), k_pages, v_pages,
                       block_tables, seq_lens, k_scale, v_scale, head_div=1,
                       pages_per_block=pages_per_block, normalize=normalize,
                       interpret=interpret)
