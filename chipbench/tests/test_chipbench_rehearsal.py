"""CPU rehearsals of the harness: both job drivers at a tiny OPT size print a
result line of the contract's shape, the measurement path refuses a device
that is not a TPU, and a new traffic file, metric or job is found by name."""
import json
import math

import pytest

import tiny
from chipbench import harness

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "checks"}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    tiny.make_checkout(root)
    return root


def _shape(out, metrics):
    assert set(out) == RESULT_KEYS
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == set(metrics)
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert math.isfinite(m["value"]) and m["value"] > 0
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for c in out["checks"].values():
        assert c["limit_is"] in ("min", "max")
        assert (c["value"] >= c["limit"] if c["limit_is"] == "min"
                else c["value"] <= c["limit"])
    json.dumps(out)


@pytest.mark.parametrize("workload, metrics", [
    ("search.opt-1.3b", {"search_proposals_per_s", "setup_s"}),
    (tiny.SERVE, {"ttft_p90_ms", "itl_p95_ms",
                                 "output_tokens_per_s", "setup_s"}),
])
def test_job_prints_a_contract_line(checkout, workload, metrics):
    _shape(tiny.run(checkout, workload, seed=2**31 + 12345), metrics)


def test_measurement_path_refuses_a_cpu(capsys):
    rc = harness.main(["--workload", "search.opt-1.3b", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == harness.EXIT_NO_CHIP
    assert out.out == ""
    assert "not a TPU" in out.err


def test_device_info_counts_chips():
    with pytest.raises(harness.NoChip):
        harness.device_info(chips=2, require_tpu=False)
    assert harness.device_info(chips=1, require_tpu=False)["count"] == 1


def test_a_new_traffic_file_is_found_by_name(tmp_path):
    mix = dict(tiny.SESSIONS, session_rate_per_s=3.0)
    (tmp_path / "bursty_chat.json").write_text(json.dumps(mix))
    (tmp_path / "sessions.py").write_text(
        (harness.BENCH_DIR / "traffic" / "sessions.py").read_text())
    traffic = harness.load_traffic("bursty_chat", tmp_path)
    assert traffic["name"] == "bursty_chat"
    gen = harness.traffic_generator(traffic, tmp_path)
    turns = gen.make(traffic, tiny.MODEL, seed=5, seconds=4.0)
    assert turns and all(t.due_s < 4.0 for t in turns)


def test_a_new_metric_and_job_are_found_by_name(tmp_path):
    (tmp_path / "queue_wait_ms.py").write_text(
        "def read(ctx):\n    return ctx.get('wait')\n")
    read = harness.metric_reader("queue_wait_ms", tmp_path)
    assert read({"wait": 3.0}) == 3.0 and read({}) is None
    (tmp_path / "offline.py").write_text("def run(ctx):\n    return ctx\n")
    assert harness.job_module("offline", tmp_path).run(1) == 1


def test_every_benchmark_file_is_found_by_name():
    bench = harness.load_benchmark()
    for w in bench["workloads"]:
        _, c = harness.find_workload(bench, w["name"])
        assert (harness.ROOT / c["file"]).is_file()
        t = harness.load_traffic(w["traffic"])
        harness.traffic_generator(t)
        assert hasattr(harness.job_module(t["job"]), "run")
    for m in bench["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
    for w in bench["workloads"]:
        e2e = harness.cell_metrics(bench, w["name"], trace=False)
        layer = harness.cell_metrics(bench, w["name"], trace=True)
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert layer and all(w["name"] in m["workloads"] for m in layer)
    assert harness.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v9 imaginary")


def test_sessions_give_every_seed_the_same_schedule():
    t = harness.load_traffic("sessions")
    gen = harness.traffic_generator(t)
    model = {"vocab_size": 50272}
    a = gen.make(t, model, seed=1, seconds=50.0)
    b = gen.make(t, model, seed=2**33 + 1, seconds=50.0)
    assert a and [(r.due_s, len(r.prompt), r.max_new) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new) for r in b]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    for tr in a:
        assert len(tr.prompt) + tr.max_new <= t["context"]
    # later turns repeat their session's system prompt and history
    by = {}
    for r in a:
        by.setdefault(r.session, []).append(r)
    for turns in by.values():
        for x, y in zip(turns, turns[1:]):
            assert (y.prompt[:len(x.prompt)] == x.prompt).all()


def test_sessions_scale_only_the_start_rate():
    t = harness.load_traffic("sessions")
    gen = harness.traffic_generator(t)
    model = {"vocab_size": 50272}
    slow = gen.make(t, model, seed=3, seconds=1e5, rate=0.1)
    fast = gen.make(t, model, seed=3, seconds=1e5, rate=0.4)

    def shape(turns):
        return sorted((r.session, r.turn, len(r.prompt), r.max_new)
                      for r in turns)
    assert shape(slow) == shape(fast)
    first = {r.session: r.due_s for r in slow if r.turn == 0}
    for r in fast:
        if r.turn == 0:
            assert r.due_s == pytest.approx(first[r.session] / 4)


def test_seed_key_keeps_high_bits():
    assert not (harness.seed_key(5) == harness.seed_key(2**33 + 5)).all()
    assert (harness.seed_key(7) == harness.seed_key(7)).all()


def test_percentile():
    assert harness.percentile([1, 2, 3, 4], 50) == 2.5
    assert harness.percentile([1, 2, 3, math.inf], 90) == math.inf
    assert harness.percentile([5.0], 95) == 5.0
