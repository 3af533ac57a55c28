"""Search job: the paper's InvarExplore search through ``quantize_model``.

Set-up makes the weights and the calibration batch from the seed, then runs
one short warm-up search, which compiles every step program and times one
step. The measured search is one more ``quantize_model`` call sized so that
its steps 2..N last about ``--seconds``; the window runs from the end of
step 1 to the end of step N, read from the engine's ``search.step`` spans.

Correctness: once the window has closed, the plain reference recomputes the
calibration cross-entropy and the activation MSE (the two terms of the
paper's objective) of the starting model and of the model the search
returned, from the seed's weights and the returned transforms alone. Each is
compared with what the search reported for that state. The search must also
have moved: at least one step accepted, and the reference's objective of the
returned state below the start's by more than the comparison's own noise, so
a search that returns its start unchanged is not correct.
"""
from __future__ import annotations

import gc
import math
import shutil

from chipbench import counts, harness, reference, trace
from chipbench.spans import SpanSink

ROW_BLOCK = 4      # calibration rows per reference call
# Limits of the relative gaps between the search's reported terms and the
# reference (set from chip readings of sound runs and of the float8
# control), and the least gain of the reference's objective from the start
# to the returned state (set from sound runs and a search that never
# accepts); see PERF.md.
LIMITS = {"ce_gap": 4e-5, "mse_gap": 4e-3, "objective_gain": 1e-3,
          "accepted_steps": 1}


def search_config(traffic: dict, seed: int, steps: int):
    from repro.core.search import SearchConfig
    return SearchConfig(
        steps=steps, seed=int(seed), objective=traffic["objective"],
        population=traffic["population"], islands=traffic["islands"],
        temperature=traffic["temperature"], install=traffic["install"],
        ce_weight=traffic["ce_weight"], log_every=0)


def make_inputs(ctx):
    """(program config, weights, calibration batch, quant config)."""
    import jax
    import jax.numpy as jnp

    from repro.core.quant import QuantConfig
    from repro.models import init_params
    from chipbench import weights

    conf = ctx.config
    cfg = harness.program_model_config(conf)
    params = weights.make_weights(conf, harness.seed_key(ctx.seed))
    want = weights.tree_shapes(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    if weights.tree_shapes(params) != want:
        raise ValueError("the program's parameter layout changed: "
                         f"{sorted(want)}")
    calib = jnp.asarray(ctx.generator.make(ctx.traffic, conf, ctx.seed))
    qcfg = QuantConfig(bits=conf["bits"], group_size=conf["group_size"])
    return cfg, params, calib, qcfg


def reported_terms(history):
    """(ce, mse) the search reported for its start and for the state it
    returned (its last accepted step; the start where none was accepted)."""
    start = history[0]
    last = [h for h in history if h[4]][-1]
    return (start[2], start[3]), (last[2], last[3])


def reference_terms(conf: dict, params, calib, transforms, n_match: int,
                    matmul_dtype=None):
    """[(ce, mse) of the start, (ce, mse) of ``transforms``] by the plain
    reference, in blocks of calibration rows. ``matmul_dtype`` gives the
    control's readings."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    bits, group = conf["bits"], conf["group_size"]
    heads, vocab = conf["num_attention_heads"], conf["vocab_size"]
    F = conf["ffn_dim"]
    L = conf["num_hidden_layers"]
    ident = (jnp.tile(jnp.arange(F, dtype=jnp.int32), (L, 1)),
             jnp.ones((L, F), jnp.float32),
             jnp.zeros((L, F // 2), jnp.float32))
    final = tuple(jnp.asarray(x) for x in transforms)

    def block(params, toks, t0, t1):
        lg_fp, h_fp = reference.forward(params, toks, heads,
                                        matmul_dtype=matmul_dtype,
                                        collect_hidden=True)
        del lg_fp
        out = []
        for t in (t0, t1):
            lg, h = reference.forward(
                params, toks, heads, matmul_dtype=matmul_dtype,
                collect_hidden=True,
                layer_fn=reference.searched_layer(bits, group, *t))
            nll = reference.token_nll(lg, toks, vocab).sum()
            d = h[:n_match] - h_fp[:n_match]
            out.append((nll, jnp.sum(d * d)))
        return out

    sums = np.zeros((2, 2))
    with jax.default_matmul_precision("highest"):
        f = jax.jit(block)
        for r in range(0, calib.shape[0], ROW_BLOCK):
            res = f(params, calib[r:r + ROW_BLOCK], ident, final)
            sums += np.asarray([[float(a), float(b)] for a, b in res])
    B, S = calib.shape
    n_tok = B * (S - 1)
    n_act = n_match * B * S * conf["hidden_size"]
    return [(sums[i, 0] / n_tok, sums[i, 1] / n_act) for i in range(2)]


def objective_gain(ref, ce_weight: float) -> float:
    """1 - J(returned) / J(start) for the paper's objective J = CE + a * MSE,
    with a set from the reference's own start so that CE weighs ``ce_weight``
    times the MSE there (paper section 4.1)."""
    (ce0, mse0), (ce1, mse1) = ref
    a = ce0 / (ce_weight * mse0) if mse0 > 0 else 0.0
    return 1.0 - (ce1 + a * mse1) / (ce0 + a * mse0)


def rel_gaps(program, ref):
    """(ce gap, mse gap): the worst relative gap over the two states."""
    ce = max(abs(p[0] - r[0]) / abs(r[0]) for p, r in zip(program, ref))
    mse = max(abs(p[1] - r[1]) / abs(r[1]) for p, r in zip(program, ref))
    return ce, mse


def run(ctx) -> harness.JobResult:
    import jax
    import numpy as np

    from repro import obs
    from repro.core.pipeline import quantize_model

    tr = ctx.traffic
    cfg, params, calib, qcfg = make_inputs(ctx)
    sink = SpanSink(ctx.compiles.mark)
    obs.set_trace_sink(sink)

    def call(steps):
        return quantize_model(params, cfg, qcfg, method=tr["method"],
                              calib_tokens=calib,
                              search=search_config(tr, ctx.seed, steps))

    warm = call(tr["warmup_steps"])
    step_s = sink.durations("search.step")[-1]
    n_match = min(search_config(tr, ctx.seed, 1).n_match_layers,
                  cfg.n_layers)
    del warm
    gc.collect()
    steps = max(int(tr["min_steps"]), 1 + math.ceil(ctx.seconds / step_s))
    harness.log(f"[search] warm-up step {step_s:.4f} s; measuring {steps} "
                f"steps; live bytes before "
                f"{sum(a.nbytes for a in jax.live_arrays())}")

    sink.events.clear()
    tdir = harness.TRACE_DIR / ctx.workload["name"]
    if ctx.trace:
        shutil.rmtree(tdir, ignore_errors=True)
        obs.enable_xprof(True)
        trace.start(tdir)
    res = call(steps)
    if ctx.trace:
        jax.profiler.stop_trace()
        obs.enable_xprof(False)
    obs.set_trace_sink(None)

    spans = sink.spans("search.step")
    (_, e1), (_, e_n) = spans[0], spans[-1]
    window = e_n["mono"] - e1["mono"]
    K = tr["population"] * tr["islands"]
    proposals = (len(spans) - 1) * K
    compiled = e_n["mark"][0] - e1["mark"][0]
    traced = e_n["mark"][1] - e1["mark"][1]
    setup_s = e1["mono"] - ctx.t_process
    mem = harness.memory_peak_bytes()
    history = list(res.search.history)
    t = res.search.transforms
    transforms = (np.asarray(t.pi), np.asarray(t.s), np.asarray(t.phi))
    del res
    gc.collect()
    harness.log(f"[search] window {window:.4f} s, {len(spans) - 1} steps, "
                f"{proposals} proposals; compiles in window {compiled}, "
                f"traces in window {traced}; setup_s {setup_s:.3f}; "
                f"peak_bytes_in_use {mem}")
    harness.log(f"[search] loss {history[0][1]:.6f} -> "
                f"{min(h[1] for h in history if h[4]):.6f}, accepted "
                f"{sum(1 for h in history[1:] if h[4])}/{len(history) - 1}")

    layer = {"proposals": proposals,
             "flops_per_proposal": counts.forward_flops(
                 ctx.config, calib.shape[0], calib.shape[1]),
             "peaks": ctx.peaks}
    reduced = None
    if ctx.trace:
        events = trace.load_xplane(str(tdir))
        steps_ev = sorted((e for e in events if e.name == "search.step"
                           and e.plane.startswith(trace.HOST_PREFIX)),
                          key=lambda e: e.start_ns)
        lo, hi = steps_ev[0].end_ns, steps_ev[-1].end_ns
        trace.write_notes(harness.TRACE_DIR / "summary",
                          ctx.workload["name"], events, lo)
        reduced = trace.reduce(events, lo, hi)
        shutil.rmtree(tdir, ignore_errors=True)
        layer.update(trace=reduced, proposals=(len(steps_ev) - 1) * K)
        harness.log(f"[search] traced window {reduced['window_s']:.4f} s, "
                    f"busy {reduced['busy_s']:.4f} s")

    program = reported_terms(history)
    ref = reference_terms(ctx.config, params, calib, transforms, n_match)
    ce_gap, mse_gap = rel_gaps(program, ref)
    gain = objective_gain(ref, tr["ce_weight"])
    accepted = sum(1 for h in history[1:] if h[4])
    harness.log(f"[search] reported (ce, mse) {program}; reference {ref}")
    failed = sum(1 for h in history if not math.isfinite(h[1]))
    return harness.JobResult(
        end_to_end={"search_proposals_per_s": proposals / window},
        layer=layer, attempted=len(spans) * K, failed=failed * K,
        checks=[harness.Check("ce_gap", ce_gap, LIMITS["ce_gap"]),
                harness.Check("mse_gap", mse_gap, LIMITS["mse_gap"]),
                harness.Check("accepted_steps", accepted,
                              LIMITS["accepted_steps"], at_least=True),
                harness.Check("objective_gain", gain,
                              LIMITS["objective_gain"], at_least=True)],
        setup_s=setup_s, memory_peak_bytes=mem, trace=reduced,
        artifacts={"transforms": transforms, "program": program,
                   "reference": ref, "n_match": n_match})

