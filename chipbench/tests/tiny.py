"""A tiny OPT cell of each job, laid out as a benchmark checkout, for CPU
rehearsals of the harness (the job drivers, readers and references run
unchanged; only the sizes shrink)."""
from __future__ import annotations

import argparse
import json
import pathlib
import time

from chipbench import harness

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}

MODEL = {
    "arch": "opt-1.3b", "num_hidden_layers": 2, "hidden_size": 64,
    "ffn_dim": 128, "num_attention_heads": 4, "vocab_size": 512,
    "max_position_embeddings": 256, "activation_function": "relu",
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "bits": 2, "group_size": 32,
}

SEARCH = {"job": "search", "kind": "calibration", "n_seqs": 4, "seq_len": 32,
          "method": "rtn", "objective": "ce", "ce_weight": 10.0,
          "population": 2, "islands": 1, "temperature": 0.0,
          "install": "unit", "warmup_steps": 2, "min_steps": 3}

SESSIONS = {
    "job": "serve", "kind": "sessions", "schedule_seed": 3, "sessions": 8,
    "session_rate_per_s": 2.0,
    "turns": [1, 3], "turn_gap_mean_s": 0.3, "system_prompts": 2,
    "system_prompt_tokens": 16, "system_zipf": 1.1,
    "user_tokens": {"median": 6, "min": 2, "max": 20, "sigma": 0.5},
    "answer_tokens": {"median": 4, "min": 2, "max": 8, "sigma": 0.5},
    "context": 96,
    "server": {"max_batch": 4, "page_size": 8, "n_pages": 40,
               "prefill_chunk_pages": 2, "context": 96},
    "drain_s": 30, "check_tokens": 12, "trace_start_s": 0.2,
    "trace_seconds": 0.6}


SERVE = "serve.opt-1.3b.sessions"


def _metric(name, unit, better, source, **kw):
    return dict(name=name, unit=unit, better=better, source=source,
                workloads=[SERVE], **kw)


# The serving job's metrics, as a serving cell's entries in BENCHMARK.json
# would name them (no cell of the benchmark serves yet).
SERVE_END_TO_END = [
    _metric("ttft_p90_ms", "ms", "lower", "host_clock", bound=0.25),
    _metric("itl_p95_ms", "ms", "lower", "host_clock", bound=0.25),
    _metric("output_tokens_per_s", "tokens/s", "higher", "host_clock",
            bound=0.25)]
SERVE_PER_LAYER = [
    _metric("prefix_hit_share", "%", "higher", "program_counter",
            layer="batcher prefix cache", moves="ttft_p90_ms"),
    _metric("prefill_us_per_token", "us", "lower", "device_trace",
            layer="paged prefill", moves="ttft_p90_ms"),
    _metric("decode_step_ms", "ms", "lower", "program_span",
            layer="paged decode step", moves="itl_p95_ms"),
    _metric("paged_decode_roofline", "%", "higher", "device_trace",
            layer="paged-decode kernel", moves="itl_p95_ms"),
    _metric("decode_mfu", "%", "higher", "program_span",
            layer="paged decode step", moves="itl_p95_ms"),
    _metric("device_idle_share.serve", "%", "lower", "device_trace",
            layer="device", moves="itl_p95_ms")]


def make_checkout(root: pathlib.Path, search=SEARCH, sessions=SESSIONS):
    """BENCHMARK.json with one tiny cell per job under ``root``; returns the
    traffic directory."""
    real = harness.load_benchmark()
    (root / "configs").mkdir(parents=True, exist_ok=True)
    traffic = root / "traffic"
    traffic.mkdir(exist_ok=True)
    (root / "configs" / "tiny.json").write_text(json.dumps(MODEL))
    (traffic / "tiny_search.json").write_text(json.dumps(search))
    (traffic / "tiny_sessions.json").write_text(json.dumps(sessions))
    for kind in ("calibration", "sessions"):
        src = harness.BENCH_DIR / "traffic" / f"{kind}.py"
        (traffic / f"{kind}.py").write_text(src.read_text())
    bench = dict(real)
    bench["configs"] = [{"name": "tiny", "source": "tiny",
                         "file": "configs/tiny.json", "reduced": [],
                         "why": "rehearsal"}]
    bench["workloads"] = [
        {"name": "search.opt-1.3b", "config": "tiny",
         "traffic": "tiny_search", "chips": 1, "why": "rehearsal"},
        {"name": SERVE, "config": "tiny", "traffic": "tiny_sessions",
         "chips": 1, "why": "rehearsal"}]
    bench["end_to_end"] = real["end_to_end"] + SERVE_END_TO_END
    bench["per_layer"] = real["per_layer"] + SERVE_PER_LAYER
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return traffic


def run(root: pathlib.Path, workload: str, seed: int = 7,
        seconds: float = 1.0, trace: int = 0) -> dict:
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace, t0=time.monotonic())
    return harness.run_cell(args, require_tpu=False, root=root,
                            traffic_dir=root / "traffic", peaks=PEAKS,
                            compile_cache=False)
