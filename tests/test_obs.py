"""Observability layer: registry semantics, exact merges, span tracing, and
the no-cross-run-leakage contract on a reused batcher.

The merge tests pin the property everything multi-host rests on: with FIXED
bucket edges a histogram merge is a bucket-wise integer add, so merging is
exact, associative and commutative — ``dist_snapshot`` can fold per-host
snapshots in any grouping and every host lands on the identical aggregate.
"""
import io
import json

import jax
import numpy as np
import pytest

from repro import obs
from repro.dist.fault import StepWatchdog
from repro.obs.registry import hist_quantile


def _registry_with(counter=0.0, gauges=(), hist_obs=()):
    r = obs.Registry()
    if counter:
        r.counter("c_total").inc(counter)
    g = r.gauge("g")
    for v in gauges:
        g.set(v)
    h = r.histogram("h_seconds", buckets=(0.1, 1.0, 10.0))
    for v in hist_obs:
        h.observe(v)
    return r


# ---------------------------------------------------------------------------
# merge semantics
# ---------------------------------------------------------------------------

def test_histogram_bucket_add_is_exact_and_associative():
    """Integer bucket counts add exactly; (a+b)+c == a+(b+c) == (a+c)+b."""
    snaps = [
        _registry_with(hist_obs=[0.05] * 3 + [5.0]).snapshot(),
        _registry_with(hist_obs=[0.5, 0.5, 100.0]).snapshot(),
        _registry_with(hist_obs=[0.2] * 7).snapshot(),
    ]
    m = obs.merge_snapshots
    ab_c = m(m(snaps[0], snaps[1]), snaps[2])
    a_bc = m(snaps[0], m(snaps[1], snaps[2]))
    ac_b = m(m(snaps[0], snaps[2]), snaps[1])

    def series(snap):
        s = snap["h_seconds"]["series"][0]
        return (s["counts"], s["count"])   # the integer part: EXACT

    assert series(ab_c) == series(a_bc) == series(ac_b)
    # the float sum is order-sensitive in the last ulp — approx only
    assert a_bc["h_seconds"]["series"][0]["sum"] == pytest.approx(
        ab_c["h_seconds"]["series"][0]["sum"])
    s = ab_c["h_seconds"]["series"][0]
    assert s["counts"] == [3, 9, 1, 1]     # per-bucket integer adds
    assert s["count"] == 14
    assert s["sum"] == pytest.approx(3 * 0.05 + 2 * 0.5 + 100.0 + 7 * 0.2 + 5.0)


def test_histogram_merge_rejects_mismatched_edges():
    a = obs.Registry()
    a.histogram("h_seconds", buckets=(0.1, 1.0)).observe(0.5)
    b = obs.Registry()
    b.histogram("h_seconds", buckets=(0.2, 2.0)).observe(0.5)
    with pytest.raises(ValueError, match="edges"):
        obs.merge_snapshots(a.snapshot(), b.snapshot())


def test_counter_and_gauge_merge():
    a = _registry_with(counter=3, gauges=[7.0]).snapshot()
    b = _registry_with(counter=4, gauges=[2.0]).snapshot()
    c = _registry_with(counter=5, gauges=[4.0]).snapshot()
    m = obs.merge_snapshots(obs.merge_snapshots(a, b), c)
    assert m["c_total"]["series"][0]["value"] == 12.0
    g = m["g"]["series"][0]
    assert (g["min"], g["max"], g["sum"], g["n"]) == (2.0, 7.0, 13.0, 3)


def test_counter_merge_keeps_label_series_separate():
    a = obs.Registry()
    a.counter("req_total").inc(2, route="x")
    b = obs.Registry()
    b.counter("req_total").inc(3, route="x")
    b.counter("req_total").inc(1, route="y")
    m = obs.merge_snapshots(a.snapshot(), b.snapshot())
    got = {tuple(s["labels"].items()): s["value"]
           for s in m["req_total"]["series"]}
    assert got == {(("route", "x"),): 5.0, (("route", "y"),): 1.0}


def test_merge_with_empty_is_identity():
    a = _registry_with(counter=3, gauges=[1.0], hist_obs=[0.5]).snapshot()
    assert obs.merge_snapshots(a, {}) == obs.merge_snapshots({}, a)
    assert obs.snapshot_json(obs.merge_snapshots(a, {})) == obs.snapshot_json(
        obs.merge_snapshots({}, a))


# ---------------------------------------------------------------------------
# quantiles + exposition
# ---------------------------------------------------------------------------

def test_quantile_within_one_bucket_width():
    edges = (0.01, 0.02, 0.05, 0.1, 0.5)
    r = obs.Registry()
    h = r.histogram("lat", buckets=edges)
    rng = np.random.default_rng(0)
    samples = rng.uniform(0.0, 0.4, size=500)
    for s in samples:
        h.observe(float(s))
    for q in (0.5, 0.9, 0.99):
        exact = float(np.quantile(samples, q))
        est = h.quantile(q)
        # the estimate interpolates within the rank's bucket, so it can be
        # off by at most that bucket's width
        widths = np.diff((0.0,) + edges)
        assert abs(est - exact) <= widths.max() + 1e-9


def test_hist_quantile_edge_cases():
    assert hist_quantile([0, 0, 0], (0.1, 1.0), 0.5) == 0.0   # empty
    # all mass in +Inf clamps to the largest finite edge
    assert hist_quantile([0, 0, 5], (0.1, 1.0), 0.5) == 1.0


def test_prometheus_exposition_format():
    r = _registry_with(counter=2, gauges=[3.0], hist_obs=[0.05, 0.5, 50.0])
    text = r.render_prometheus()
    assert "# TYPE c_total counter" in text
    assert "# TYPE h_seconds histogram" in text
    assert 'h_seconds_bucket{le="0.1"} 1' in text
    assert 'h_seconds_bucket{le="1.0"} 2' in text      # cumulative
    assert 'h_seconds_bucket{le="+Inf"} 3' in text
    assert "h_seconds_count 3" in text


# ---------------------------------------------------------------------------
# registry contracts
# ---------------------------------------------------------------------------

def test_registry_get_or_create_and_kind_guard():
    r = obs.Registry()
    c1 = r.counter("x_total")
    assert r.counter("x_total") is c1
    with pytest.raises(TypeError):
        r.gauge("x_total")
    r.histogram("h", buckets=(1.0, 2.0))
    with pytest.raises(ValueError):
        r.histogram("h", buckets=(1.0, 3.0))


def test_reset_zeroes_in_place_keeping_handles():
    r = obs.Registry()
    c = r.counter("x_total")
    h = r.histogram("h_seconds")
    c.inc(5)
    h.observe(0.1)
    r.reset()
    assert c.total() == 0.0 and h.count() == 0
    c.inc(2)       # the PRE-reset handle must still feed the registry
    assert r.snapshot()["x_total"]["series"][0]["value"] == 2.0


def test_counter_rejects_negative():
    with pytest.raises(ValueError):
        obs.Registry().counter("x_total").inc(-1)


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

def test_trace_span_writes_jsonl_and_observes_hist():
    r = obs.Registry()
    h = r.histogram("span_seconds")
    buf = io.StringIO()
    with obs.trace_to(buf):
        with obs.trace_span("unit", hist=h, k=1) as sp:
            pass
        obs.emit("ev", _print=False, a=2)
    events = [json.loads(line) for line in buf.getvalue().splitlines()]
    # every sink opens with the epoch anchor metadata event: event ts values
    # are monotonically derived, the anchor maps them back to wall time
    assert [e["ph"] for e in events] == ["M", "B", "E", "i"]
    assert events[0]["name"] == "clock_anchor"
    assert {"wall", "mono"} <= set(events[0])
    assert events[1]["attrs"] == {"k": 1}
    assert events[2]["dur_s"] == sp.dur and sp.dur >= 0.0
    # E.ts is derived from B.ts + dur, so spans can never overlap/reorder
    # under a wall-clock adjustment
    # abs tolerance: double precision at epoch magnitude is ~1e-7 s
    assert events[2]["ts"] - events[1]["ts"] == pytest.approx(sp.dur,
                                                              abs=1e-5)
    assert events[3]["a"] == 2
    assert h.count() == 1
    assert obs.get_trace_sink() is not buf    # trace_to restored the sink


def test_trace_span_records_error_and_no_sink_is_safe():
    buf = io.StringIO()
    with obs.trace_to(buf):
        with pytest.raises(RuntimeError):
            with obs.trace_span("boom"):
                raise RuntimeError("x")
    end = json.loads(buf.getvalue().splitlines()[-1])
    assert "error" in end and "RuntimeError" in end["error"]
    with obs.trace_span("quiet") as sp:   # no sink configured: still times
        pass
    assert sp.dur is not None


# ---------------------------------------------------------------------------
# snapshot files + single-process dist path
# ---------------------------------------------------------------------------

def test_write_snapshot_name_level_merge(tmp_path):
    p = tmp_path / "metrics.json"
    a = obs.Registry()
    a.counter("c_total").inc(3)
    obs.write_snapshot(obs.dist_snapshot(a), path=p)
    b = obs.Registry()
    b.gauge("other").set(1.0)
    b.counter("c_total").inc(9)           # same name: row-level REPLACE
    obs.write_snapshot(obs.dist_snapshot(b), path=p)
    d = json.loads(p.read_text())
    assert set(d) == {"c_total", "other"}
    assert d["c_total"]["series"][0]["value"] == 9.0


def test_dist_snapshot_single_process_normalizes():
    """The fast path must return the same mergeable schema the gather path
    does (gauges as min/max/sum/n), so downstream merges never special-case
    host count."""
    r = _registry_with(counter=2, gauges=[4.0], hist_obs=[0.5])
    snap = obs.dist_snapshot(r)
    g = snap["g"]["series"][0]
    assert (g["min"], g["max"], g["sum"], g["n"]) == (4.0, 4.0, 4.0, 1)
    assert obs.merge_snapshots(snap, snap)["c_total"]["series"][0][
        "value"] == 4.0
    assert jax.process_count() == 1       # the path this test pins


# ---------------------------------------------------------------------------
# instrumented components
# ---------------------------------------------------------------------------

def test_watchdog_exports_median_samples_and_trips():
    reg = obs.get_registry()
    reg.reset()
    wd = StepWatchdog(threshold=2.0, warmup=3)
    assert wd.median_step is None and wd.samples_seen == 0
    st = wd.stats()
    assert st["warmed_up"] is False and st["samples_seen"] == 0
    for _ in range(5):
        wd.observe(0.1)
    assert wd.observe(1.0) is True        # straggler
    assert wd.samples_seen == 5           # flagged samples stay out
    assert wd.stats()["warmed_up"] is True
    assert reg.counter("dist_watchdog_trips_total").total() == 1
    assert reg.gauge("dist_watchdog_median_step_seconds").value() == \
        pytest.approx(0.1)
    assert reg.gauge("dist_watchdog_samples_seen").value() == 5
    assert reg.histogram("dist_step_seconds").count() == 6  # ALL samples


def test_batcher_registry_reset_between_runs():
    """The satellite-6 bug: per-run latency state must not accumulate across
    ``run()`` calls on a reused batcher. After a registry reset, the TTFT
    histogram reflects ONLY the post-reset run."""
    from repro.configs import get_config
    from repro.core.quant import QuantConfig
    from repro.models import init_params
    from repro.quantized.qmodel import pack_model
    from repro.serving import ContinuousBatcher, PagedKVCache, PagedRequest

    cfg = get_config("opt-tiny").reduced(n_layers=1, d_model=32, d_ff=64,
                                         vocab_size=128, n_heads=2,
                                         n_kv_heads=2)
    params_q = pack_model(init_params(jax.random.PRNGKey(0), cfg),
                          QuantConfig(bits=2, group_size=32))
    cache = PagedKVCache(cfg, n_pages=16, page_size=8, max_pages_per_seq=4)
    reg = obs.get_registry()
    reg.reset()
    b = ContinuousBatcher(params_q, cfg, cache, max_batch=2)

    def reqs(n):
        rng = np.random.default_rng(7)
        return [PagedRequest(prompt=rng.integers(
            0, cfg.vocab_size, size=5).astype(np.int32), max_new=2)
            for _ in range(n)]

    b.run(reqs(3))
    assert b.obs["ttft"].count() == 3
    assert len(b.done) == 3
    steps_run1 = b.stats["steps"]
    reg.reset()
    b.run(reqs(2))               # reused batcher, pre-reset handles
    assert b.obs["ttft"].count() == 2, "TTFT leaked across runs"
    assert len(b.done) == 2, "done list leaked across runs"
    assert not b._t_submit, "submit stamps leaked across runs"
    # the counter was zeroed mid-lifetime, so it holds run 2 only, while the
    # legacy stats dict keeps accumulating — exactly the split we want
    assert reg.counter("serving_decode_steps_total").total() == \
        b.stats["steps"] - steps_run1
    assert b.stats["prefills"] == 5


def test_search_metrics_reconcile_with_stats():
    """Counters must reconcile EXACTLY with the engine's legacy stats dict
    (the acceptance criterion the launch driver also asserts inline)."""
    from repro.configs import get_config
    from repro.core.quant import QuantConfig
    from repro.core.search import SearchConfig
    from repro.models import init_params
    from repro.search import run as search_run

    reg = obs.get_registry()
    reg.reset()
    cfg = get_config("opt-tiny").reduced(n_layers=1, d_model=32, d_ff=64,
                                         vocab_size=128, n_heads=2,
                                         n_kv_heads=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    calib = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                               cfg.vocab_size)
    scfg = SearchConfig(steps=4, seed=0, n_match_layers=1, log_every=0,
                        population=2, islands=2, migrate_every=2)
    r = search_run(params, params, cfg, QuantConfig(bits=2, group_size=32),
                   calib, scfg)
    assert reg.counter("search_proposals_total").total() == \
        r.stats["proposals"] == 4 * 2 * 2
    assert reg.counter("search_uphill_accepts_total").total() == \
        r.stats["uphill_accepts"]
    assert reg.counter("search_migrations_total").total() == \
        r.stats["migrations"]
    assert reg.histogram("search_step_seconds").count() == 4
    assert reg.histogram("search_eval_seconds").count() == 4 * 2
    assert reg.gauge("search_objective_best").value() == \
        pytest.approx(r.final_loss)
