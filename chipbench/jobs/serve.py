"""Serve job: open-loop traffic through ``PagedServer``'s continuous batcher.

Set-up makes the f32 weights from the seed on the device, packs them with the
program's ``pack_model``, builds the server and warms every shape the cell
uses: one prompt of each length 1..64 goes through the batcher, which
compiles each prefill-chunk length (16/32/48/64), the selection of each
chunk's last row and the greedy decode step; one copy-on-write page fork
compiles that program. The window then replays the traffic's requests at
their due times through ``ContinuousBatcher.submit``/``step`` and drains
what is in flight when it closes.

Each token is stamped when the batcher appends it to the request's output, so
time to first token runs from the request's due time to its first token, and
the gaps between tokens are read per request.

Correctness: once the window has closed and the server is freed, a sample of
finished requests drawn from the seed, the longest among them, is run through
the plain reference (the seed's f32 weights, RTN-quantized by the reference
itself, float32 at ``Precision.HIGHEST``) over prompt and served tokens. The
number compared is the widest gap by which a served token's reference logit
lies below the reference's best logit at that position.
"""
from __future__ import annotations

import gc
import json
import math
import shutil
import time
from typing import List

from chipbench import harness, reference, trace
from chipbench.spans import SpanSink

# Limit of the widest served-token logit gap (set from chip readings of sound
# runs and of the float8 control; see PERF.md).
LIMITS = {"served_logit_gap": 0.1}
REF_BATCH = 2          # sequences per reference call


class StampedList(list):
    """A request's output list that records when each token was appended."""

    def __init__(self):
        super().__init__()
        self.t: List[float] = []

    def append(self, x) -> None:
        super().append(x)
        self.t.append(time.monotonic())


def make_server(params_q, cfg, srv: dict):
    from repro.launch.serve import PagedServer
    return PagedServer(params_q, cfg, max_batch=srv["max_batch"],
                       page_size=srv["page_size"], n_pages=srv["n_pages"],
                       max_len=srv["context"],
                       prefill_chunk_pages=srv["prefill_chunk_pages"],
                       prefix_cache=True)


def build(ctx):
    """(program config, packed weights): the seed's f32 weights, packed."""
    import jax

    from repro.core.quant import QuantConfig
    from repro.models import init_params
    from repro.quantized.qmodel import pack_model
    from chipbench import weights

    conf = ctx.config
    cfg = harness.program_model_config(conf)
    params = weights.make_weights(conf, harness.seed_key(ctx.seed))
    want = weights.tree_shapes(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    if weights.tree_shapes(params) != want:
        raise ValueError("the program's parameter layout changed")
    params_q = pack_model(params, QuantConfig(bits=conf["bits"],
                                              group_size=conf["group_size"]))
    jax.block_until_ready(params_q)
    del params
    return cfg, params_q


def warm(server, vocab: int, page_size: int, chunk_pages: int) -> None:
    """Compile every shape the traffic uses (see the module docstring)."""
    import numpy as np

    from repro.serving import PagedRequest
    rng = np.random.default_rng(0)
    n = page_size * chunk_pages
    reqs = [PagedRequest(prompt=rng.integers(0, vocab, p, dtype=np.int32),
                         max_new=2) for p in range(1, n + 1)]
    server.batcher.run(reqs)
    server.cache.fork_page(1, 2)


def replay(server, turns, seconds: float, drain_s: float, on_tick=None):
    """Submit each turn at its due time, step the batcher, drain.

    Returns (records, info): one record per turn with its request (whose
    ``out`` holds the stamped tokens) and its due and submit times (s from
    the window's start), and the window's start, queue lengths and
    lateness."""
    import numpy as np

    from repro.serving import PagedRequest
    b = server.batcher
    recs = [{"turn": t, "req": PagedRequest(prompt=t.prompt,
                                            max_new=t.max_new,
                                            out=StampedList())}
            for t in turns]
    t0 = time.monotonic()
    i, q_mid, q_end = 0, None, None
    while True:
        now = time.monotonic() - t0
        while i < len(recs) and recs[i]["turn"].due_s <= now:
            recs[i]["submit_s"] = now
            b.submit(recs[i]["req"])
            i += 1
        if q_mid is None and now >= seconds / 2:
            q_mid = len(b.queue)
        if q_end is None and now >= seconds:
            q_end = len(b.queue)
        if on_tick is not None:
            on_tick(now)
        busy = bool(b.queue) or any(s is not None for s in b.slots)
        if not busy:
            if i >= len(recs) and now >= seconds:
                break
            nxt = recs[i]["turn"].due_s if i < len(recs) else seconds
            time.sleep(max(0.0, min(nxt - now, 0.05)))
            continue
        if now > seconds + drain_s:
            break
        b.step()
    late = [r["submit_s"] - r["turn"].due_s for r in recs if "submit_s" in r]
    info = {"t0": t0, "queue_mid": q_mid if q_mid is not None else 0,
            "queue_end": q_end if q_end is not None else len(b.queue),
            "late_max_s": max(late) if late else 0.0,
            "late_p99_s": float(np.percentile(late, 99)) if late else 0.0}
    return recs, info


def latency(recs, t0: float, seconds: float) -> dict:
    """End-to-end numbers of one replay (see BENCHMARK.json)."""
    ttft, gaps, in_window, failed = [], [], 0, 0
    for r in recs:
        req = r["req"]
        times = [t - t0 for t in req.out.t]
        done = len(req.out) >= req.max_new
        failed += not done
        ttft.append(times[0] - r["turn"].due_s if times else math.inf)
        gaps += [b - a for a, b in zip(times, times[1:])]
        in_window += sum(1 for t in times if t <= seconds)
    return {"ttft_p90_ms": 1e3 * harness.percentile(ttft, 90),
            "ttft_p80_ms": 1e3 * harness.percentile(ttft, 80),
            "ttft_p50_ms": 1e3 * harness.percentile(ttft, 50),
            "itl_p95_ms": 1e3 * harness.percentile(gaps, 95) if gaps
            else math.inf,
            "itl_p50_ms": 1e3 * harness.percentile(gaps, 50) if gaps
            else math.inf,
            "output_tokens_per_s": in_window / seconds,
            "failed": failed, "n_gaps": len(gaps)}


def check_sample(recs, seed: int, tokens: int) -> list:
    """Finished requests drawn from the seed, the longest first, until they
    hold ``tokens`` served tokens: [(prompt, served tokens)]."""
    import numpy as np
    done = [r["req"] for r in recs if len(r["req"].out) >= r["req"].max_new]
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: len(done[i].prompt) + len(done[i].out))
    order = [longest] + [int(i) for i in np.random.default_rng(
        [int(seed), 0xC4EC]).permutation(len(done)) if i != longest]
    out, n = [], 0
    for i in order:
        out.append((np.asarray(done[i].prompt), list(done[i].out)))
        n += len(done[i].out)
        if n >= tokens:
            break
    return out


def reference_logits(conf: dict, params, sample, context: int,
                     matmul_dtype=None):
    """Per request: the reference's logits (n_served, V) at the positions
    that produced each served token (``matmul_dtype``: the control's)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    layer = reference.quantize_layer(conf["bits"], conf["group_size"])
    heads, V = conf["num_attention_heads"], conf["vocab_size"]

    def fwd(params, toks):
        return reference.forward(params, toks, heads,
                                 matmul_dtype=matmul_dtype,
                                 layer_fn=layer)[..., :V]

    out = []
    with jax.default_matmul_precision("highest"):
        f = jax.jit(fwd)
        for r in range(0, len(sample), REF_BATCH):
            part = sample[r:r + REF_BATCH]
            toks = np.zeros((REF_BATCH, context), np.int32)
            for j, (prompt, served) in enumerate(part):
                seq = np.concatenate([prompt, np.asarray(served, np.int32)])
                toks[j, :len(seq)] = seq[:context]
            lg = f(params, jnp.asarray(toks))
            for j, (prompt, served) in enumerate(part):
                p = len(prompt)
                out.append(np.asarray(lg[j, p - 1:p - 1 + len(served)]))
    return out


def control_sample(sample, control_logits) -> list:
    """The control in the program's place: at each position of the same
    prompts and served tokens, the token the control's logits put first."""
    return [(prompt, [int(t) for t in lg.argmax(axis=-1)])
            for (prompt, _), lg in zip(sample, control_logits)]


def served_gaps(logits, sample) -> List[float]:
    """For every served token: best reference logit minus the served one's."""
    import numpy as np
    gaps = []
    for lg, (_, served) in zip(logits, sample):
        s = np.asarray(served)
        gaps += list(lg.max(axis=-1) - lg[np.arange(len(s)), s])
    return [float(g) for g in gaps]


def run(ctx) -> harness.JobResult:
    import jax

    from repro import obs
    from chipbench import weights

    tr, conf = ctx.traffic, ctx.config
    srv = tr["server"]
    cfg, params_q = build(ctx)
    server = make_server(params_q, cfg, srv)
    warm(server, conf["vocab_size"], srv["page_size"],
         srv["prefill_chunk_pages"])
    turns = ctx.generator.make(tr, conf, ctx.seed, ctx.seconds)
    sink = SpanSink(ctx.compiles.mark)
    obs.set_trace_sink(sink)
    b = server.batcher

    # traced runs: a sub-window of the replay is profiled, and the decode
    # step's sequence lengths are recorded around each call into the step
    tdir = harness.TRACE_DIR / ctx.workload["name"]
    tw = {"lo": tr["trace_start_s"], "hi": tr["trace_start_s"]
          + tr["trace_seconds"], "on": False, "done": False}
    decode_lens = []
    if ctx.trace:
        shutil.rmtree(tdir, ignore_errors=True)
        step_fn = b.step_fn

        def recording_step(*args):
            out = step_fn(*args)
            if tw["on"]:
                decode_lens.append([int(n) + 1 for n in
                                    jax.device_get(args[4]) if n > 0])
            return out
        b.step_fn = recording_step

    def on_tick(now):
        if not ctx.trace or tw["done"]:
            return
        if not tw["on"] and now >= tw["lo"]:
            obs.enable_xprof(True)
            trace.start(tdir)
            tw["ann"] = jax.profiler.TraceAnnotation("bench.window")
            tw["ann"].__enter__()
            tw["stats0"] = dict(b.stats)
            tw["mono0"] = time.monotonic()
            tw["on"] = True
        elif tw["on"] and now >= tw["hi"]:
            tw["ann"].__exit__(None, None, None)
            jax.profiler.stop_trace()
            obs.enable_xprof(False)
            tw["stats1"] = dict(b.stats)
            tw["mono1"] = time.monotonic()
            tw["on"], tw["done"] = False, True

    mark0 = ctx.compiles.mark()
    recs, info = replay(server, turns, ctx.seconds, tr["drain_s"], on_tick)
    mark1 = ctx.compiles.mark()
    obs.set_trace_sink(None)
    setup_s = info["t0"] - ctx.t_process
    mem = harness.memory_peak_bytes()
    lat = latency(recs, info["t0"], ctx.seconds)
    stats = dict(b.stats)
    harness.log(f"[serve] {len(recs)} requests due in the window, "
                f"{len(recs) - lat['failed']} completed, {lat['failed']} "
                f"failed; {lat['n_gaps']} token gaps; compiles in window "
                f"{mark1[0] - mark0[0]}, traces {mark1[1] - mark0[1]}; "
                f"setup_s {setup_s:.3f}; peak_bytes_in_use {mem}")
    harness.log(f"[serve] generator lateness max {info['late_max_s']:.4f} s, "
                f"p99 {info['late_p99_s']:.4f} s; queue at middle "
                f"{info['queue_mid']}, at end {info['queue_end']}; "
                f"{json.dumps({k: round(v, 4) for k, v in lat.items()})}")
    harness.log(f"[serve] batcher stats {stats}")

    sample = check_sample(recs, ctx.seed, tr["check_tokens"])
    del server, params_q, b, recs
    gc.collect()

    layer = {"model": conf, "page_size": srv["page_size"],
             "peaks": ctx.peaks}
    reduced = None
    if ctx.trace and tw["done"]:
        s0, s1 = tw["stats0"], tw["stats1"]
        layer.update(
            prefix_saved=s1["prefill_tokens_saved"]
            - s0["prefill_tokens_saved"],
            prefill_tokens=s1["prefill_tokens"] - s0["prefill_tokens"],
            decode_spans_s=sink.durations("serve.decode_step", tw["mono0"],
                                          tw["mono1"]),
            decode_lens=decode_lens)
        events = trace.load_xplane(str(tdir))
        win = trace.span_window(events, "bench.window")
        trace.write_notes(harness.TRACE_DIR / "summary",
                          ctx.workload["name"], events, win[0])
        reduced = trace.reduce(events, *win)
        shutil.rmtree(tdir, ignore_errors=True)
        layer["trace"] = reduced
        harness.log(f"[serve] traced window {reduced['window_s']:.4f} s, "
                    f"busy {reduced['busy_s']:.4f} s, "
                    f"{len(decode_lens)} decode steps")

    params = weights.make_weights(conf, harness.seed_key(ctx.seed))
    logits = reference_logits(conf, params, sample, srv["context"])
    gaps = served_gaps(logits, sample)
    widest = max(gaps) if gaps else math.inf
    harness.log(f"[serve] reference over {len(sample)} requests, "
                f"{len(gaps)} served tokens; widest gap {widest!r}")
    return harness.JobResult(
        end_to_end=lat,
        layer=layer, attempted=len(turns), failed=lat["failed"],
        checks=[harness.Check("served_logit_gap", widest,
                              LIMITS["served_logit_gap"])],
        setup_s=setup_s, memory_peak_bytes=mem, trace=reduced,
        artifacts={"sample": sample, "reference_logits": logits})
