"""Chip smoke test: both jobs of the system, once, on a TPU, at OPT-1.3B widths.

    python chip_smoke.py                # one chip: serve, then search
    python chip_smoke.py --four-chips   # four chips: mapped island search only

Phases, all in this one process (it starts no child that needs the chip):

1. device — JAX must see a TPU. On any other platform the script exits 2
   before any work.
2. serve  — ``PagedServer`` at full OPT-1.3B (24 layers, d_model 2048, 32 MHA
   heads of 64, d_ff 8192), weights random from ``--seed`` and packed at 2
   bits in groups of 32: 8 shared-prefix requests, batch 4, page size 16, 32
   new tokens each, greedy. The compiled decode step must contain the Pallas
   kernel (``tpu_custom_call``) and no page may leak. Then, with the whole
   model at ``Precision.HIGHEST``, one decode step's logits are compared
   between the Pallas and the ``use_pallas=False`` path (tolerance below),
   and the tokens of both servers must be equal.
3. search — ``run_search_bench`` at OPT-1.3B widths with the depth cut to 8
   of 24 blocks (the search keeps fp and base models, the FFN base and
   fake-quant stacks and the calibration logits resident; 24 blocks need
   ~18 GB): population 4, 4 steps, 2 bits, group 32. The
   ``search_proposals_total`` counter must reconcile with
   ``stats["proposals"]`` and the final loss must not exceed the initial one.

``--four-chips`` runs only the mapped island search: 4 islands, one per chip
(``mapped=True``), against the sequential 4-island loop on one chip, at
OPT-1.3B widths cut to 4 blocks (the sequential lane holds all four islands'
fake-quant stacks on one chip). The histories must be identical.

Logit tolerance: at the default matmul precision the TPU rounds matmul inputs
to bf16, so any difference in attention output, however small, flips
roundings downstream and the two paths' logits differ at bf16 level (both
are printed). The comparison therefore runs the whole model at
``Precision.HIGHEST``, where the two attention paths differ only in
summation order: one decode step's logits must agree to 1e-3 of the largest
logit magnitude. A wrong page, head or mask moves them by O(1).

Wall times printed are smoke times of one cold run, compilation included —
not benchmarks. Outputs go to ``chiprun_out/chip_smoke/`` (git-ignored), so
no committed file is rewritten. The last line of standard output is the JSON
result; a failed phase raises, exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.launch.compile_cache import setup_compile_cache  # noqa: E402

OUT = ROOT / "chiprun_out" / "chip_smoke"
ARCH = "opt-1.3b"
LOGIT_RTOL = 1e-3


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def peak_bytes(jax) -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def phase(name, fn, *args):
    import jax
    print(f"[{name}] start", flush=True)
    t0 = time.monotonic()
    out = fn(*args)
    dt = time.monotonic() - t0
    print(f"[{name}] smoke wall time {dt:.1f} s (one cold run, compile "
          f"included; not a benchmark); peak_bytes_in_use="
          f"{peak_bytes(jax)}", flush=True)
    return out


def serve_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.launch.serve import (PagedServer, serving_model,
                                    shared_prefix_requests)
    from repro.serving.decode import make_paged_logits_step

    batch, page, max_len = 4, 16, 128
    cfg, params_q = serving_model(ARCH, full=True, bits=2, group=32,
                                  seed=seed)
    check(cfg == get_config(ARCH), f"{ARCH} at its published size: "
          f"{cfg.n_layers} layers, d_model {cfg.d_model}, {cfg.n_heads} "
          f"heads of {cfg.resolved_head_dim}, d_ff {cfg.d_ff}")
    reqs = shared_prefix_requests(cfg, 8, page_size=page, max_new=32,
                                  seed=seed)

    def serve(use_pallas):
        server = PagedServer(params_q, cfg, max_batch=batch, page_size=page,
                             max_len=max_len, use_pallas=use_pallas)
        t0 = time.monotonic()
        outs = server.generate(reqs)
        dt = time.monotonic() - t0
        alloc = server.cache.allocator
        check(alloc.num_free == alloc.n_pages - alloc.reserved
              and alloc.num_live == 0,
              f"use_pallas={use_pallas}: every page returned "
              f"({alloc.num_free} free of {alloc.n_pages - alloc.reserved})")
        n_tok = sum(len(o) for o in outs)
        print(f"  use_pallas={use_pallas}: {len(outs)} requests, {n_tok} "
              f"tokens, smoke wall time {dt:.1f} s (not a benchmark); "
              f"stats {server.batcher.stats}", flush=True)
        return server, outs

    # the production path, at the default matmul precision
    server, _ = serve(True)
    P = server.cache.max_pages_per_seq
    zeros = (jnp.zeros((batch, 1), jnp.int32), server.cache.pools,
             jnp.zeros((batch, P), jnp.int32), jnp.zeros((batch,), jnp.int32))
    hlo = server.batcher.step_fn.lower(params_q, *zeros).compile().as_text()
    check("tpu_custom_call" in hlo,
          "compiled decode step runs the Pallas kernel (tpu_custom_call)")

    # one decode step on identical inputs through both attention paths: the
    # pool still holds the K/V the run wrote; four sequences over distinct
    # pages at ragged depths
    pools = server.cache.pools
    del server
    bt = jnp.asarray(1 + np.arange(batch * P).reshape(batch, P), jnp.int32)
    lens = jnp.asarray([1, 37, 64, max_len - 1], jnp.int32)
    toks = jnp.asarray([[5], [17], [4242], [31337]], jnp.int32)

    def logit_diff(precision):
        lg = {}
        for use_pallas in (True, False):
            step = jax.jit(make_paged_logits_step(cfg, use_pallas=use_pallas))
            out, _ = step(params_q, toks, pools, bt, lens)
            lg[use_pallas] = np.asarray(out[:, 0, :cfg.vocab_size])
        check(np.all(np.isfinite(lg[True])),
              f"Pallas logits are finite ({precision} precision)")
        scale = float(np.max(np.abs(lg[False])))
        diff = float(np.max(np.abs(lg[True] - lg[False])))
        print(f"  decode-step logits, {precision} matmul precision: max "
              f"|pallas - reference| = {diff:.3e}, max |logit| = "
              f"{scale:.3e}", flush=True)
        return diff, scale

    logit_diff("default")
    with jax.default_matmul_precision("highest"):
        diff, scale = logit_diff("highest")
        check(diff <= LOGIT_RTOL * max(scale, 1.0),
              f"decode-step logits agree within {LOGIT_RTOL:g} x max |logit|")
        _, outs = serve(True)
        _, ref_outs = serve(False)
    same = sum(a == b for a, b in zip(outs, ref_outs))
    if same == len(outs):
        check(True, f"tokens equal to the use_pallas=False server "
                     f"({same}/{len(outs)} requests identical)")
    else:
        # a near-tie between two logits can flip one greedy token; the
        # logit comparison above is then the check that holds
        print(f"  note: {len(outs) - same}/{len(outs)} requests differ from "
              f"the use_pallas=False server; accepted on the decode-step "
              f"logit agreement within {LOGIT_RTOL:g} x max |logit|",
              flush=True)


def search_phase(seed: int) -> None:
    from repro import obs
    from repro.launch.search import run_search_bench

    proposals = obs.counter("search_proposals_total",
                            "Candidate transforms proposed")
    before = proposals.total()
    row, sr = run_search_bench(
        ARCH, full=True, n_layers=8, steps=4, population=4, bits=2, group=32,
        n_seqs=4, seq_len=128, seed=seed, out=OUT / "BENCH_search.json",
        metrics_out=str(OUT / "metrics.json"))
    grew = proposals.total() - before
    check(grew == sr.stats["proposals"] == 4 * 4,
          f"search_proposals_total grew by {grew} == stats['proposals'] "
          f"{sr.stats['proposals']}")
    check(sr.final_loss <= sr.initial_loss,
          f"final loss {sr.final_loss:.6f} <= initial {sr.initial_loss:.6f}")
    print(f"  {row['name']}: {row['derived']} (proposals/sec is a smoke "
          f"number, compile included; not a benchmark)", flush=True)


def mapped_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.pipeline import quantize_model
    from repro.core.quant import QuantConfig
    from repro.core.search import SearchConfig
    from repro.data.calib import calibration_tokens
    from repro.launch.search import search_config
    from repro.models import init_params

    n = len(jax.devices())
    check(n == 4, f"four devices visible ({n})")
    cfg = search_config(ARCH, full=True, n_layers=4)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    calib = jnp.asarray(calibration_tokens(cfg.vocab_size, n_seqs=4,
                                           seq_len=128))
    qcfg = QuantConfig(bits=2, group_size=32)
    scfg = SearchConfig(steps=6, seed=seed, n_match_layers=2, log_every=0,
                        islands=4, migrate_every=3)
    results = {}
    for mapped in (False, True):
        t0 = time.monotonic()
        res = quantize_model(params, cfg, qcfg, method="rtn",
                             calib_tokens=calib,
                             search=dataclasses.replace(scfg, mapped=mapped))
        print(f"  mapped={mapped}: smoke wall time "
              f"{time.monotonic() - t0:.1f} s (not a benchmark); "
              f"migrations={res.search.stats['migrations']} "
              f"loss {res.search.initial_loss:.6f} -> "
              f"{res.search.final_loss:.6f}", flush=True)
        results[mapped] = res.search
    seq, mp = results[False], results[True]
    check(seq.island_histories == mp.island_histories,
          "mapped island histories identical to the sequential loop "
          f"({len(seq.island_histories)} islands x "
          f"{len(seq.island_histories[0])} entries)")
    check(seq.final_loss == mp.final_loss
          and seq.stats["migrations"] == mp.stats["migrations"],
          "final loss and migration count identical")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mapped island search on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache_dir = setup_compile_cache()
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r}); "
              f"nothing runs", file=sys.stderr)
        return 2
    print(f"[device] {dev.device_kind} x {len(devs)} ({dev.platform}); "
          f"compile cache {cache_dir}", flush=True)
    OUT.mkdir(parents=True, exist_ok=True)

    if args.four_chips:
        phase("mapped-search", mapped_phase, args.seed)
    else:
        phase("serve", serve_phase, args.seed)
        phase("search", search_phase, args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
