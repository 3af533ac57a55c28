"""Trace reduction on hand-made events and on a small recorded TPU trace."""
import json
import pathlib

import pytest

from chipbench import trace
from chipbench.trace import Event

DEV = "/device:TPU:0"
HOST = "/host:CPU"
DATA = pathlib.Path(__file__).parent / "data"


def ev(name, start, dur, plane=DEV, line=trace.OPS_LINE):
    return Event(plane, line, name, float(start), float(dur))


def hand_made():
    """Window [0, 1000] ns: ops busy [100, 300] (two overlapping), [500,
    600], [900, 1100] (runs past the window); host spans around them."""
    return [
        ev("fusion.1", 100, 150), ev("fusion.2", 200, 100),
        ev("paged_decode", 500, 100), ev("fusion.1", 900, 200),
        ev("jit_step(7)", 100, 500, line=trace.MODULES_LINE),
        ev("jit_chunk_step(3)", 900, 200, line=trace.MODULES_LINE),
        ev("serve.decode_step", 50, 600, plane=HOST, line="python"),
        ev("serve.prefill", 620, 400, plane=HOST, line="python"),
        ev("bench.window", 0, 1000, plane=HOST, line="python"),
        ev("unrelated", 0, 1000, plane=HOST, line="python"),
    ]


def test_union_merges_overlaps_and_drops_empty():
    assert trace.union([(5, 7), (0, 2), (1, 3), (4, 4)]) == [(0, 3), (5, 7)]


def test_busy_idle_and_programs():
    r = trace.reduce(hand_made(), 0.0, 1000.0)
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: [100, 300] + [500, 600] + [900, 1000] = 400 ns
    assert r["busy_s"] == pytest.approx(400e-9)
    assert r["idle_share"] == pytest.approx(0.6)
    assert r["programs"]["jit_step"] == pytest.approx(500e-9)
    assert r["programs"]["jit_chunk_step"] == pytest.approx(100e-9)
    assert r["ops"]["fusion.1"] == pytest.approx(250e-9)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(250e-9)]


def test_idle_gaps_go_to_the_innermost_host_span():
    r = trace.reduce(hand_made(), 0.0, 1000.0)
    gaps = dict(r["idle_gaps"])
    # [0, 100] and [300, 500]: inside serve.decode_step (50..650)
    assert gaps["serve.decode_step"] == pytest.approx(300e-9)
    # [600, 900]: middle 750 inside serve.prefill (620..1020)
    assert gaps["serve.prefill"] == pytest.approx(300e-9)
    assert "unrelated" not in gaps


def test_span_window_and_no_device_is_an_error():
    events = hand_made()
    assert trace.span_window(events, "bench.window") == (0.0, 1000.0)
    host_only = [e for e in events if e.plane == HOST]
    with pytest.raises(ValueError):
        trace.reduce(host_only, 0.0, 1000.0)
    with pytest.raises(ValueError):
        trace.reduce(events, 2000.0, 3000.0)


def test_names_and_containers():
    assert trace.program_name("jit_chunk_step(123)") == "jit_chunk_step"
    assert trace.program_name("fusion.4") == "fusion.4"
    hlo = ("%paged_decode.9 = (f32[16,32,1,64]{3,2,1,0:T(1,128)S(1)}, "
           "f32[16,32,1,1]{3,2,1,0:T(1,128)}) custom-call(s32[16,64]{1,0} "
           "%p), custom_call_target=\"tpu_custom_call\"")
    assert trace.op_name(hlo) == "%paged_decode.9 custom-call"
    loop = ("%while.5 = (s32[]{:T(128)}, f32[16,1,2048]{2,0,1:T(8,128)S(1)})"
            " while((s32[]{:T(128)}, f32[16,1,2048]) %tuple), body=%b")
    assert trace.is_container(loop) and not trace.is_container(hlo)


def test_a_loop_around_idle_time_is_not_busy():
    loop = "%while.1 = (s32[]) while(s32[] %t), body=%b"
    events = [ev(loop, 0, 1000), ev("%fusion.2 = f32[8] fusion(f32[8] %x)",
                                    100, 100)]
    r = trace.reduce(events, 0.0, 1000.0)
    assert r["busy_s"] == pytest.approx(100e-9)
    assert [n for n, _ in r["device_ops"]] == ["%fusion.2 fusion"]


def _sweep_busy(events, lo, hi):
    """Busy ns inside [lo, hi] by a sweep over interval endpoints: an
    independent count of what ``trace.reduce`` calls busy."""
    points = []
    for e in events:
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b > a:
            points += [(a, 1), (b, -1)]
    busy, depth, last = 0.0, 0, None
    for t, d in sorted(points):
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


@pytest.mark.parametrize("cell, program", [
    ("serve.opt-1.3b.sessions", "jit_step"),
    ("search.opt-1.3b", "jit_build_units_body"),
])
def test_recorded_tpu_trace(cell, program):
    """5 ms of a TPU v5e trace of each cell (names shortened to the HLO name
    and kind): busy matches an independent sweep over the leaf operations,
    the loop that wraps them is left out, and the window is accounted for."""
    rows = json.loads((DATA / "tpu_v5e_trace_extract.json").read_text())
    events = [Event(*r) for r in rows[cell]]
    lo = min(e.start_ns for e in events if e.plane == DEV)
    hi = lo + 5e6
    r = trace.reduce(events, lo, hi)
    leaves = [e for e in events if e.plane == DEV
              and e.line == trace.OPS_LINE and not trace.is_container(e.name)]
    assert r["busy_s"] == pytest.approx(_sweep_busy(leaves, lo, hi) * 1e-9)
    assert 0 < r["busy_s"] < r["window_s"] == pytest.approx(5e-3)
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle + r["busy_s"] == pytest.approx(r["window_s"])
    assert program in r["programs"]
    assert not any(trace.is_container(n) for n in r["ops"])
