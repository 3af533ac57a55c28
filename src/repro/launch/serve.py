"""Serving driver for quantized models: paged KV cache + continuous batching.

    PYTHONPATH=src python -m repro.launch.serve --arch opt-tiny --bits 2

The config runs in its ``.reduced()`` form unless ``--full`` asks for the
published widths and depth (weights are random, made from ``--seed``).

Default flow (``PagedServer``): requests stream through
``repro.serving.ContinuousBatcher`` — per-request prefill scatters K/V into a
fixed-size page pool, one jitted decode step advances every live sequence at
its own depth (attention reads pages through the block-table Pallas kernel),
finished sequences hand their page references back between steps, and
exhaustion preempts the scheduler's victim (FIFO: the newest sequence).
Pages are refcounted and content-addressed: shared prompt prefixes are
aliased from the prefix cache at admit instead of re-prefilled
(``--no-prefix-cache`` disables), and ``--scheduler slo`` turns on priority
admission with per-tenant page quotas (``--tenant-quota``). Weights stay
packed QTensors throughout (dequant-on-the-fly in each scan body; the fused
quant_matmul kernel on TPU).

``BatchedServer`` (``--legacy``) keeps the old fixed-slot recycling loop for
comparison: it pads every batch to the longest member and holds max_len-deep
cache slots whether used or not.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs import get_config
from repro.core.quant import QuantConfig
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.steps import make_serve_step
from repro.models import init_params, prefill
from repro.quantized.qmodel import pack_model, packed_bytes, dense_bytes
from repro.serving import (ContinuousBatcher, PagedKVCache, PagedRequest,
                           make_scheduler)


@dataclasses.dataclass
class Request:
    prompt: np.ndarray              # (S,) int32
    max_new: int = 32
    out: Optional[list] = None
    temperature: float = 0.0        # <= 0: greedy (paged server only)
    top_k: int = 0                  # 0: unrestricted
    seed: int = 0                   # per-request sample stream
    tenant: str = "default"         # quota bucket (SLO scheduler)
    priority: int = 0               # admission order (SLO scheduler)


class BatchedServer:
    """Fixed-batch greedy decoding server with slot recycling."""

    def __init__(self, params_q, cfg, batch_size: int = 4, max_len: int = 512):
        self.cfg = cfg
        self.params = params_q
        self.B = batch_size
        self.max_len = max_len
        self.step_fn = jax.jit(make_serve_step(cfg))
        self.prefill_fn = jax.jit(
            lambda p, toks: prefill(p, cfg, toks, max_len))

    def generate(self, requests: List[Request]):
        """Serve all requests; returns list of generated-token lists."""
        queue = list(requests)
        results = {id(r): [] for r in requests}
        while queue:
            chunk = queue[: self.B]
            queue = queue[self.B:]
            # pad the batch to B with copies (masked out of results)
            live = len(chunk)
            while len(chunk) < self.B:
                chunk.append(chunk[-1])
            plen = max(len(r.prompt) for r in chunk)
            toks = np.stack([np.pad(r.prompt, (plen - len(r.prompt), 0),
                                    constant_values=0) for r in chunk]).astype(np.int32)
            logits, cache = self.prefill_fn(self.params, jnp.asarray(toks))
            last = jnp.argmax(logits[:, -1, : self.cfg.vocab_size], axis=-1
                              ).astype(jnp.int32)[:, None]
            index = jnp.int32(plen)
            max_new = max(r.max_new for r in chunk[:live])
            outs = [last]
            tok = last
            for t in range(max_new - 1):
                tok, cache = self.step_fn(self.params, tok, cache, index + t)
                outs.append(tok)
            gen = jnp.concatenate(outs, axis=1)
            for i, r in enumerate(chunk[:live]):
                results[id(r)] = np.asarray(gen[i, : r.max_new]).tolist()
        return [results[id(r)] for r in requests]


class PagedServer:
    """Continuous-batching server over the paged KV cache.

    ``n_pages`` bounds TOTAL cache memory across all live sequences (the
    dense server's cost was batch x max_len whether used or not);
    ``max_pages_per_seq`` bounds a single sequence. Accepts the same
    ``Request`` objects as ``BatchedServer``.

    ``prefix_cache`` (default on) shares pages between requests: full-page
    prompt runs already in the pool are aliased at admit (zero prefill) and
    identical in-flight requests decode from one copy (COW-forked at the
    first diverging write) — outputs stay token-identical to sharing
    disabled. ``scheduler`` picks the admission/eviction policy: ``"fifo"``
    (legacy-identical default), ``"slo"`` (uses ``Request.tenant`` /
    ``priority`` with ``tenant_quota`` pages per tenant), or any
    ``serving.Scheduler`` instance.
    """

    def __init__(self, params_q, cfg, max_batch: int = 4, page_size: int = 16,
                 n_pages: Optional[int] = None, max_len: int = 512,
                 use_pallas: bool = True, prefill_chunk_pages: int = 4,
                 prefix_cache: bool = True, scheduler="fifo",
                 tenant_quota: Optional[int] = None,
                 gqa_pages_per_block: int = 1):
        pages_per_seq = -(-max_len // page_size)
        if n_pages is None:
            n_pages = max_batch * pages_per_seq + 1  # +1 null page
        self.cfg = cfg
        self.cache = PagedKVCache(cfg, n_pages=n_pages, page_size=page_size,
                                  max_pages_per_seq=pages_per_seq)
        if isinstance(scheduler, str):
            scheduler = make_scheduler(scheduler, tenant_quota=tenant_quota)
        self.batcher = ContinuousBatcher(params_q, cfg, self.cache,
                                         max_batch=max_batch,
                                         use_pallas=use_pallas,
                                         prefill_chunk_pages=prefill_chunk_pages,
                                         scheduler=scheduler,
                                         prefix_cache=prefix_cache,
                                         gqa_pages_per_block=gqa_pages_per_block)

    def generate(self, requests: List[Request]):
        paged = [PagedRequest(prompt=np.asarray(r.prompt, np.int32),
                              max_new=r.max_new, temperature=r.temperature,
                              top_k=r.top_k, seed=r.seed, tenant=r.tenant,
                              priority=r.priority) for r in requests]
        return self.batcher.run(paged)

    def sharing_report(self) -> dict:
        """Prefix-sharing + latency stats for the run(s) so far.

        TTFT percentiles come from the ``serving_ttft_seconds`` histogram
        (accurate to within one bucket width; exact under multi-host merge),
        not a per-request list."""
        st = self.batcher.stats
        total = st["prefill_tokens"] + st["prefill_tokens_saved"]
        ttft = self.batcher.obs["ttft"]

        def pct(p):
            return ttft.quantile(p) if ttft.count() else 0.0

        return {
            "prefill_tokens": st["prefill_tokens"],
            "prefill_tokens_saved": st["prefill_tokens_saved"],
            "saved_frac": st["prefill_tokens_saved"] / total if total else 0.0,
            "aliased_pages": st["aliased_pages"],
            "dedup_admits": st["dedup_admits"],
            "cow_forks": st["cow_forks"],
            "ttft_p50_s": pct(0.50),
            "ttft_p99_s": pct(0.99),
        }


def serving_model(arch: str, *, full: bool = False, bits: int = 2,
                  group: int = 32, seed: int = 0):
    """(cfg, params_q): ``arch`` at its published size (``full``) or its
    ``.reduced()`` form, random weights from ``seed`` packed at ``bits`` with
    quant groups of ``group``. The fp weights are dropped before returning."""
    cfg = get_config(arch) if full else get_config(arch).reduced()
    params = init_params(jax.random.PRNGKey(seed), cfg)
    return cfg, pack_model(params, QuantConfig(bits=bits, group_size=group))


def shared_prefix_requests(cfg, n: int, *, page_size: int, tenants: int = 1,
                           max_new: int = 16, temperature: float = 0.0,
                           top_k: int = 0, seed: int = 0) -> List[Request]:
    """``n`` requests behind a shared two-page system prompt (so the prefix
    cache is visible in every run); ``tenants > 1`` adds a one-page
    per-tenant template on top (the many-tenant trace shape)."""
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(0, cfg.vocab_size,
                              size=2 * page_size).astype(np.int32)
    tenant_tpl = {t: rng.integers(0, cfg.vocab_size,
                                  size=page_size).astype(np.int32)
                  for t in range(tenants)}
    reqs = []
    for i in range(n):
        t = i % tenants
        tail = rng.integers(0, cfg.vocab_size,
                            size=rng.integers(4, 12)).astype(np.int32)
        reqs.append(Request(
            prompt=np.concatenate([sys_prompt, tenant_tpl[t], tail]),
            max_new=max_new, temperature=temperature, top_k=top_k, seed=i,
            tenant=f"tenant{t}", priority=t % 3))
    return reqs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="opt-tiny")
    ap.add_argument("--full", action="store_true",
                    help="published widths and depth (default: .reduced())")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the request trace")
    ap.add_argument("--bits", type=int, default=2)
    ap.add_argument("--group", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--pages", type=int, default=None,
                    help="total page-pool size (default: batch x max_len/page)")
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--prefill-chunk-pages", type=int, default=4,
                    help="pages per paged-prefill chunk (admit granularity)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable prefix-cache page sharing across requests")
    ap.add_argument("--scheduler", default="fifo", choices=("fifo", "slo"),
                    help="admission/eviction policy (slo: priority + quotas)")
    ap.add_argument("--tenant-quota", type=int, default=None,
                    help="max pages a tenant's live requests may hold (slo)")
    ap.add_argument("--gqa-pages-per-block", type=int, default=1,
                    help="pages staged per fused-GQA decode block (1 keeps "
                         "the single-page grid bit-for-bit)")
    ap.add_argument("--tenants", type=int, default=1,
                    help="spread requests round-robin over N tenants, each "
                         "sharing one system-prompt prefix")
    ap.add_argument("--legacy", action="store_true",
                    help="fixed-slot BatchedServer instead of the paged path")
    ap.add_argument("--metrics-out", default=obs.DEFAULT_METRICS_PATH,
                    help="merged metrics snapshot path ('' disables)")
    ap.add_argument("--trace-out", default="",
                    help="span/event JSONL sink path ('' disables)")
    args = ap.parse_args()
    if args.trace_out:
        obs.set_trace_sink(args.trace_out)
    setup_compile_cache()

    cfg, params_q = serving_model(args.arch, full=args.full, bits=args.bits,
                                  group=args.group, seed=args.seed)
    pb, db = packed_bytes(params_q), dense_bytes(params_q)
    print(f"[serve] packed={pb/1e6:.2f}MB vs fp16={db/1e6:.2f}MB "
          f"({db/pb:.1f}x smaller)")

    reqs = shared_prefix_requests(
        cfg, args.requests, page_size=args.page_size, tenants=args.tenants,
        max_new=args.max_new, temperature=args.temperature, top_k=args.top_k,
        seed=args.seed)
    if args.legacy:
        server = BatchedServer(params_q, cfg, batch_size=args.batch,
                               max_len=args.max_len)
    else:
        server = PagedServer(params_q, cfg, max_batch=args.batch,
                             page_size=args.page_size, n_pages=args.pages,
                             max_len=args.max_len,
                             prefill_chunk_pages=args.prefill_chunk_pages,
                             prefix_cache=not args.no_prefix_cache,
                             scheduler=args.scheduler,
                             tenant_quota=args.tenant_quota,
                             gqa_pages_per_block=args.gqa_pages_per_block)
        pool = server.cache.pool_bytes()
        dense = server.cache.dense_equiv_bytes(args.batch, args.max_len)
        print(f"[serve] page pool: {server.cache.n_pages} x "
              f"{args.page_size}-token pages = {pool/1e6:.2f}MB "
              f"(contiguous {args.batch}x{args.max_len} cache: {dense/1e6:.2f}MB)")
    t0 = time.monotonic()
    outs = server.generate(reqs)
    dt = time.monotonic() - t0
    n_tok = sum(len(o) for o in outs)
    print(f"[serve] {len(reqs)} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok/dt:.1f} tok/s)")
    if not args.legacy:
        print(f"[serve] batcher stats: {server.batcher.stats}")
        rep = server.sharing_report()
        print(f"[serve] sharing: {rep['prefill_tokens_saved']} prompt tokens "
              f"aliased ({rep['saved_frac']:.0%} of prefill), "
              f"{rep['dedup_admits']} duplicate admits, "
              f"{rep['cow_forks']} COW forks; "
              f"TTFT p50={rep['ttft_p50_s']*1e3:.1f}ms "
              f"p99={rep['ttft_p99_s']*1e3:.1f}ms")
    for i, o in enumerate(outs[:3]):
        print(f"  req{i}: {o[:10]}...")
    if args.metrics_out:
        p = obs.write_snapshot(path=args.metrics_out)
        print(f"[serve] metrics snapshot -> {p}")


if __name__ == "__main__":
    main()
