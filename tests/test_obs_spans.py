"""Span ids and parents, the compile listener, and the names the program
gives its device work (``jax.named_scope`` paths, Pallas kernel names)."""
import io
import json
import re
import threading

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs import get_config
from repro.core.pipeline import quantize_model
from repro.core.quant import QuantConfig
from repro.core.search import SearchConfig
from repro.models import init_params

QCFG = QuantConfig(bits=2, group_size=32)


def _events(buf):
    return [json.loads(line) for line in buf.getvalue().splitlines()]


def _begins(events):
    return {e["id"]: e for e in events if e["ph"] == "B"}


def test_span_ids_and_parents_nest():
    buf = io.StringIO()
    with obs.trace_to(buf):
        with obs.trace_span("a") as a:
            assert obs.current_span() == a.id
            with obs.trace_span("b") as b:
                with obs.trace_span("c") as c:
                    pass
            with obs.trace_span("d") as d:
                pass
        assert obs.current_span() is None
    events = _events(buf)
    begins = _begins(events)
    assert [begins[s.id]["parent"] for s in (a, b, c, d)] == \
        [None, a.id, b.id, a.id]
    assert len({a.id, b.id, c.id, d.id}) == 4
    # E events keep their shape; E.ts - B.ts is the span's duration
    ends = [e for e in events if e["ph"] == "E"]
    assert [e["name"] for e in ends] == ["c", "b", "d", "a"]
    assert all("id" not in e and "parent" not in e for e in ends)
    assert ends[-1]["ts"] - begins[a.id]["ts"] == pytest.approx(a.dur,
                                                                abs=1e-5)
    log = {r["id"]: r for r in obs.span_log() if r.get("id") in begins}
    assert log[c.id]["parent"] == b.id and log[c.id]["dur_s"] == c.dur


def test_span_parents_stay_on_their_thread():
    buf = io.StringIO()
    seen = {}
    go = threading.Event()

    def worker():
        with obs.trace_span("t.outer") as o:
            go.wait(5)
            with obs.trace_span("t.inner") as i:
                seen.update(outer=o.id, inner=i.id)

    with obs.trace_to(buf):
        with obs.trace_span("main.outer") as m:
            th = threading.Thread(target=worker)
            th.start()
            with obs.trace_span("main.inner") as mi:
                go.set()
                th.join()
    begins = _begins(_events(buf))
    assert begins[seen["outer"]]["parent"] is None
    assert begins[seen["inner"]]["parent"] == seen["outer"]
    assert begins[mi.id]["parent"] == m.id


def test_a_span_closed_out_of_order_leaves_no_parent_behind():
    outer = obs.trace_span("x.outer").__enter__()
    inner = obs.trace_span("x.inner").__enter__()
    outer.__exit__(None, None, None)
    inner.__exit__(None, None, None)
    assert obs.current_span() is None


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("opt-tiny").reduced(
        n_layers=2, d_model=64, d_ff=128, vocab_size=256, n_heads=4,
        n_kv_heads=4, max_seq_len=256)
    params = init_params(jax.random.PRNGKey(0), cfg)
    calib = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                               cfg.vocab_size)
    return params, cfg, calib


@pytest.mark.parametrize("population", [1, 2])
def test_search_span_tree(tiny, population):
    """pipeline.quantize > {pipeline.base, search.prepare, search.run};
    each search.step holds one search.eval (with one search.dispatch and one
    search.sync) and one search.accept after it."""
    params, cfg, calib = tiny
    steps = 3
    buf = io.StringIO()
    with obs.trace_to(buf):
        quantize_model(params, cfg, QCFG, calib_tokens=calib,
                       search=SearchConfig(steps=steps, seed=0,
                                           population=population,
                                           log_every=0))
    events = _events(buf)
    begins = _begins(events)
    parent = {i: begins.get(e["parent"], {}).get("name")
              for i, e in begins.items()}
    by_name = {}
    for i, e in begins.items():
        by_name.setdefault(e["name"], []).append(i)
    (q,) = by_name["pipeline.quantize"]
    assert begins[q]["parent"] is None
    for name in ("pipeline.base", "search.prepare", "search.run"):
        (i,) = by_name[name]
        assert parent[i] == "pipeline.quantize"
    assert len(by_name["search.step"]) == steps
    for name, up in (("search.step", "search.run"),
                     ("search.eval", "search.step"),
                     ("search.dispatch", "search.eval"),
                     ("search.sync", "search.eval"),
                     ("search.accept", "search.step")):
        assert len(by_name[name]) == steps
        assert all(parent[i] == up for i in by_name[name])
    # accept comes after its step's eval
    order = [e["name"] for e in events if e["ph"] == "B"
             and e["name"] in ("search.eval", "search.accept")]
    assert order == ["search.eval", "search.accept"] * steps
    # every compile of the search is attributed to an open span
    comp = [e for e in events if e["name"] == "jax.compile"]
    assert comp and all(e["parent"] in begins for e in comp)
    assert {begins[e["parent"]]["name"] for e in comp} >= {"search.prepare"}


def test_compile_listener_counts_a_new_program_once():
    reg = obs.get_registry()

    def fresh_program(x):
        return jnp.sin(x) * 3.0 + 1.0

    f = jax.jit(fresh_program)
    fn = "jit(fresh_program)"
    c0 = reg.counter("jax_compiles_total").value(fn=fn)
    t0 = reg.counter("jax_traces_total").value(fn="fresh_program")
    buf = io.StringIO()
    with obs.trace_to(buf):
        with obs.trace_span("outer") as sp:
            f(jnp.ones(7)).block_until_ready()
        f(jnp.ones(7)).block_until_ready()    # cached: nothing new
    assert reg.counter("jax_compiles_total").value(fn=fn) == c0 + 1
    assert reg.counter("jax_traces_total").value(fn="fresh_program") == t0 + 1
    assert reg.histogram("jax_compile_seconds").count(fn=fn) >= 1
    (rec,) = [e for e in _events(buf) if e["name"] == "jax.compile"
              and e["fn"] == fn]
    assert rec["parent"] == sp.id
    assert rec["begin"] == pytest.approx(rec["ts"] - rec["dur_s"], abs=1e-6)
    assert any(r["name"] == "jax.compile" and r["fn"] == fn
               and r["parent"] == sp.id for r in obs.span_log())


def test_compile_listener_is_installed_once():
    assert obs.install_compile_listener() is True
    from jax._src import monitoring
    listeners = monitoring.get_event_duration_listeners()
    from repro.obs import tracing
    assert listeners.count(tracing._on_jax_duration) == 1


def test_eval_program_carries_the_model_scopes(tiny):
    """With profiler annotations on, the engine records its step programs'
    compiled scope maps; the candidate-eval program's op names carry every
    scope the device-share readers look for."""
    params, cfg, calib = tiny
    obs.enable_xprof(True)
    try:
        quantize_model(params, cfg, QCFG, calib_tokens=calib,
                       search=SearchConfig(steps=1, seed=0, population=2,
                                           log_every=0))
    finally:
        obs.enable_xprof(False)
    maps = obs.program_scopes()
    assert {"jit_propose_body", "jit_build_units_body", "jit_eval_body",
            "jit_pick_body", "jit_eval_stack_fn"} <= set(maps)
    parts = {p for op in maps["jit_eval_body"].values()
             for p in op.split("/")}
    assert {"embed", "blocks", "attn", "ffn", "lm_head", "objective.ce",
            "objective.act_mse"} <= parts
    taps = [op for op in maps["jit_eval_body"].values()
            if op.endswith("blocks/while/body/dynamic_update_slice")]
    assert taps


def test_scope_map_reads_instruction_op_names():
    text = ('  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
            'calls=%fc, metadata={op_name="jit(f)/attn/dot_general" '
            'stack_frame_id=2}\n'
            '  ROOT %copy.1 = f32[8]{0} copy(%fusion.3)\n'
            '  ROOT %add.2 = f32[] add(%a, %b), metadata={op_name="jit(f)/ffn/add"}\n')
    assert obs.scope_map(text) == {"fusion.3": "jit(f)/attn/dot_general",
                                   "add.2": "jit(f)/ffn/add"}


def _kernel_cases():
    from repro.kernels.flash_decode import flash_decode_pallas
    from repro.kernels.group_quant import group_quant_pallas
    from repro.kernels.paged_decode import paged_decode_pallas
    from repro.kernels.quant_matmul import quant_matmul_pallas
    from repro.kernels.transform_quant import transform_quant_pallas
    f32, i32 = jnp.float32, jnp.int32
    K = N = 256
    w = jnp.ones((K, N), f32)
    return {
        "group_quant": (lambda w: group_quant_pallas(
            w, bits=2, group=32, interpret=True), (w,)),
        "transform_quant": (lambda w, pi, s, phi: transform_quant_pallas(
            w, pi, s, phi, bits=2, group=32, mode="up", interpret=True),
            (w, jnp.arange(N, dtype=i32), jnp.ones(N, f32),
             jnp.zeros(N // 2, f32))),
        "quant_matmul": (lambda x, p, s, z: quant_matmul_pallas(
            x, p, s, z, bits=2, group=32, interpret=True),
            (jnp.ones((8, K), f32), jnp.zeros((K // 16, N), jnp.uint32),
             jnp.ones((K // 32, N), f32), jnp.zeros((K // 32, N), f32))),
        "flash_decode": (lambda q, k, v: flash_decode_pallas(
            q, k, v, interpret=True),
            (jnp.ones((2, 4, 64), f32), jnp.ones((2, 128, 4, 64), f32),
             jnp.ones((2, 128, 4, 64), f32))),
        "paged_decode": (lambda q, kp, vp, bt, sl: paged_decode_pallas(
            q, kp, vp, bt, sl, interpret=True),
            (jnp.ones((2, 4, 64), f32), jnp.ones((9, 4, 8, 64), f32),
             jnp.ones((9, 4, 8, 64), f32),
             jnp.arange(8, dtype=i32).reshape(2, 4) + 1,
             jnp.asarray([20, 9], i32))),
    }


@pytest.mark.parametrize("name", ["paged_decode", "flash_decode",
                                  "quant_matmul", "group_quant",
                                  "transform_quant"])
def test_pallas_kernel_carries_its_name(name):
    fn, args = _kernel_cases()[name]
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    assert re.search(rf'/{name}/', text), name
