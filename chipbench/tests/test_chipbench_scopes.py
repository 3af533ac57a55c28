"""The readers of the program's own records: device time by model part (the
trace's operations named through the compiled scope maps), the search's
prepare span and the compiles before the window; and the trace reduction
they rest on, unchanged."""
import json
import pathlib

import pytest

from chipbench import harness, scopes, trace
from chipbench.trace import Event

DATA = pathlib.Path(__file__).parent / "data"
SHARES = ["search_device_share.attention", "search_device_share.ffn",
          "search_device_share.lm_head", "search_device_share.hidden_taps"]

EVAL = "jit(eval_body)/while/body/closed_call/blocks/while/body"


@pytest.mark.parametrize("op_name, part", [
    (f"{EVAL}/attn/dot_general", "attention"),
    (f"{EVAL}/attn/jit(_where)/select_n", "attention"),
    (f"{EVAL}/ffn/jit(relu)/max", "ffn"),
    ("jit(eval_body)/while/body/closed_call/lm_head/dot_general", "lm_head"),
    ("jit(eval_body)/while/body/closed_call/objective.ce/jit(log_softmax)"
     "/reduce_max", "lm_head"),
    ("jit(eval_body)/while/body/closed_call/objective.act_mse/sub",
     "hidden_taps"),
    (f"{EVAL}/dynamic_update_slice", "hidden_taps"),   # the tap write
    # the candidate unit's install and lax.map's own output are not taps
    ("jit(eval_body)/while/body/closed_call/dynamic_update_slice", None),
    ("jit(eval_body)/while/body/dynamic_update_slice", None),
    (f"{EVAL}/dynamic_slice", None),
    (f"{EVAL}/add", None),                             # residual, layernorm
    ("ctx['params']['blocks']['attn']['wq']", None),   # a parameter's name
    ("jit(eval_body)/while/body/closed_call/embed/jit(_take)/gather", None),
])
def test_part_of(op_name, part):
    assert scopes.part_of(op_name) == part


def test_seconds_by_layer_reads_a_shared_name_from_the_longer_program():
    ops = {"%fusion.1 fusion": 3.0, "%fusion.2 fusion": 2.0,
           "%copy.4 copy": 1.0, "%custom-call.7 custom-call": 0.5}
    maps = {"jit_eval_body": {"fusion.1": f"{EVAL}/attn/dot_general",
                              "fusion.2": f"{EVAL}/ffn/dot_general"},
            "jit_pick_body": {"fusion.1": "jit(pick_body)/argmin",
                              "copy.4": "jit(pick_body)/objective.ce/x"}}
    got = scopes.seconds_by_layer(
        ops, maps, {"jit_eval_body": 9.0, "jit_pick_body": 0.1})
    assert got == {"attention": 3.0, "ffn": 2.0, "lm_head": 1.0,
                   "hidden_taps": 0.0, "other": 0.5}
    assert sum(got.values()) == pytest.approx(sum(ops.values()))


def test_device_share_readers(monkeypatch):
    maps = {"jit_eval_body": {"fusion.1": f"{EVAL}/attn/dot_general",
                              "fusion.2": f"{EVAL}/ffn/dot_general",
                              "fusion.3": "jit(eval_body)/lm_head/dot",
                              "fusion.4": f"{EVAL}/dynamic_update_slice"}}
    monkeypatch.setattr(scopes, "program_scopes", lambda: maps)
    layer = {"trace": {"window_s": 10.0, "programs": {"jit_eval_body": 9.0},
                       "ops": {"%fusion.1 fusion": 2.0,
                               "%fusion.2 fusion": 3.0,
                               "%fusion.3 fusion": 1.5,
                               "%fusion.4 fusion": 0.5,
                               "%copy.9 copy": 1.0}}}
    got = {m: harness.metric_reader(m)(layer) for m in SHARES}
    assert got == pytest.approx({SHARES[0]: 20.0, SHARES[1]: 30.0,
                                 SHARES[2]: 15.0, SHARES[3]: 5.0})
    assert harness.metric_reader(SHARES[0])({}) is None


@pytest.mark.parametrize("name", SHARES + ["search_prepare_s",
                                           "setup_compile_s"])
def test_readers_give_nothing_for_a_program_without_records(monkeypatch,
                                                           name):
    """A checkout whose ``repro.obs`` predates the records (the parent of the
    change that added them) reads None and does not raise."""
    from repro import obs
    monkeypatch.delattr(obs, "program_scopes")
    monkeypatch.delattr(obs, "span_log")
    layer = {"trace": {"window_s": 1.0, "programs": {},
                       "ops": {"%fusion.1 fusion": 0.5}}}
    assert harness.metric_reader(name)(layer) is None


def _span(name, id_, parent, ts, dur, **attrs):
    return {"name": name, "id": id_, "parent": parent, "ts": ts,
            "dur_s": dur, **({"attrs": attrs} if attrs else {})}


def _compile(fn, parent, end, dur):
    return {"name": "jax.compile", "fn": fn, "parent": parent,
            "ts": end - dur, "dur_s": dur}


def test_span_log_readers():
    log = [_compile("jit(build)", None, 1.0, 0.5),
           _span("search.prepare", 3, 2, 1.0, 2.0),
           _compile("jit(eval_body)", 6, 5.5, 1.0),
           _span("search.step", 6, 4, 5.0, 1.0, step=1),
           _span("search.run", 4, 2, 4.0, 3.0),           # warm-up call
           _span("search.prepare", 9, 8, 10.0, 1.5),
           _compile("jit(eval_stack_fn)", 9, 11.0, 0.25),
           _compile("jit(pick_body)", 12, 12.5, 0.125),
           _span("search.step", 12, 10, 12.0, 1.0, step=1),  # window starts
           _compile("jit(leak)", 13, 13.5, 0.0625),          # inside it
           _span("search.step", 13, 10, 13.0, 1.0, step=2),
           _span("search.run", 10, 8, 11.5, 2.5),
           _compile("jit(block)", None, 16.0, 4.0)]          # reference
    assert scopes.prepare_seconds(log) == 1.5
    assert scopes.compile_seconds_before_window(log) == 0.5 + 1.0 + 0.25 \
        + 0.125
    assert scopes.compile_seconds_before_window(log[:3]) is None
    assert scopes.prepare_seconds(log[:1]) is None


def test_span_log_readers_on_a_tiny_search():
    """Two quantize_model calls as the search job makes them: the prepare
    span of the second is read, the compiles before its first step's end are
    summed, and none falls between its first and last step's end."""
    import jax

    from repro import obs
    from repro.configs import get_config
    from repro.core.pipeline import quantize_model
    from repro.core.quant import QuantConfig
    from repro.core.search import SearchConfig
    from repro.models import init_params
    cfg = get_config("opt-tiny").reduced(
        n_layers=2, d_model=64, d_ff=128, vocab_size=256, n_heads=4,
        n_kv_heads=4, max_seq_len=256)
    params = init_params(jax.random.PRNGKey(0), cfg)
    calib = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                               cfg.vocab_size)
    for steps in (2, 4):
        quantize_model(params, cfg, QuantConfig(bits=2, group_size=32),
                       calib_tokens=calib,
                       search=SearchConfig(steps=steps, seed=3, population=2,
                                           log_every=0))
    log = obs.span_log()
    run = [r for r in log if r["name"] == "search.run"][-1]
    steps = sorted((r for r in log if r["name"] == "search.step"
                    and r["parent"] == run["id"]), key=lambda r: r["ts"])
    assert len(steps) == 4
    lo = steps[0]["ts"] + steps[0]["dur_s"]
    hi = steps[-1]["ts"] + steps[-1]["dur_s"]
    comp = [r for r in log if r["name"] == "jax.compile"]
    assert not [r for r in comp if lo < r["ts"] + r["dur_s"] <= hi]
    before = scopes.compile_seconds_before_window(log)
    assert 0 < before <= sum(r["dur_s"] for r in comp)
    prep = [r for r in log if r["name"] == "search.prepare"][-1]
    assert harness.metric_reader("search_prepare_s")({}) == prep["dur_s"]
    assert harness.metric_reader("setup_compile_s")({}) == before


def test_reduce_is_unchanged_on_the_recorded_extract():
    """``trace.reduce`` gives what it gave before the scope readers were
    added, key by key, on the first recorded TPU extract."""
    rows = json.loads((DATA / "tpu_v5e_trace_extract.json").read_text())
    want = json.loads(
        (DATA / "tpu_v5e_trace_extract.reduced.json").read_text())
    for cell, rs in rows.items():
        events = [Event(*r) for r in rs]
        lo = min(e.start_ns for e in events if e.plane == "/device:TPU:0")
        got = json.loads(json.dumps(trace.reduce(events, lo, lo + 5e6)))
        assert sorted(got) == sorted(want[cell])
        for key in want[cell]:
            assert got[key] == want[cell][key], key


def test_recorded_scoped_extract(monkeypatch):
    """100 ms of a TPU v5e trace of the search, each operation with the
    op_name the program's compiled scope map gives it: the trace reduction,
    the by-part sum and the readers give the shares that the rows' own
    op_names give, clipped to the window; the parts, other operations and
    idle time account for the window."""
    import collections
    rec = json.loads((DATA / "tpu_v5e_scoped_extract.json").read_text())
    rec = rec["search.opt-1.3b"]
    lo, hi = rec["window_ns"]
    rows = rec["rows"]
    window_s = (hi - lo) * 1e-9
    want = collections.defaultdict(float)
    for plane, line, name, start, dur, op_name in rows:
        a, b = max(start, lo), min(start + dur, hi)
        if line == trace.OPS_LINE and b > a and not trace.is_container(name):
            want[scopes.part_of(op_name) or "other"] += (b - a) * 1e-9
    red = trace.reduce([Event(*r[:5]) for r in rows], lo, hi)
    maps = {"jit_eval_body": {scopes.instruction(r[2]): r[5]
                              for r in rows if r[5]}}
    got = scopes.seconds_by_layer(red["ops"], maps, red["programs"])
    assert got == pytest.approx(dict(want), rel=1e-9)
    monkeypatch.setattr(scopes, "program_scopes", lambda: maps)
    layer = {"trace": red}
    shares = {m: harness.metric_reader(m)(layer) for m in SHARES}
    # inside one candidate's forward: attention and the FFN (with the tap
    # write fused into its down projection) lead, then the LM head and CE
    assert shares == pytest.approx({SHARES[0]: 40.876, SHARES[1]: 32.032,
                                    SHARES[2]: 19.604, SHARES[3]: 1.535},
                                   abs=1e-3)
    other = 100.0 * got["other"] / window_s
    assert sum(shares.values()) + other + 100.0 * red["idle_share"] == \
        pytest.approx(100.0, abs=0.5)
    fused = [r[5] for r in rows
             if r[2].startswith("%bitcast_dynamic-update-slice_fusion")]
    assert fused and all(scopes.part_of(op) == "ffn" for op in fused)
