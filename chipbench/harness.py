"""The benchmark's core: finds a cell's files by name and runs it once.

Everything that belongs to one configuration, traffic mix, job or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

- configuration  ``chipbench/configs/<config>.json`` (path from the entry)
- traffic mix    ``chipbench/traffic/<traffic>.json``; its ``kind`` names
                 the generator ``chipbench/traffic/<kind>.py`` and its
                 ``job`` the driver ``chipbench/jobs/<job>.py``
- metric         ``chipbench/metrics/<name>.py`` with ``read(ctx)``
- peaks          ``chipbench/peaks.json``, keyed by ``device_kind``

A run prints diagnostics on stderr, then the compared numbers beside their
limits as the last stderr lines, and one JSON result as the last stdout
line. A run on anything but a TPU, or on fewer chips than the cell asks for,
exits non-zero and prints no result.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import pathlib
import sys
import time
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CACHE_DIR = BENCH_DIR / ".jax_cache"
TRACE_DIR = BENCH_DIR / ".traces"

EXIT_NO_CHIP = 3


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Finding a cell's files by name
# ---------------------------------------------------------------------------

def load_json(path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def find_workload(bench: dict, name: str):
    """(workload entry, configuration entry) for the cell ``name``."""
    for w in bench["workloads"]:
        if w["name"] == name:
            for c in bench["configs"]:
                if c["name"] == w["config"]:
                    return w, c
            raise KeyError(f"workload {name!r} names unknown config "
                           f"{w['config']!r}")
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_module(path: pathlib.Path, name: str):
    """Import a file by path (file names may hold dots, as metric names do)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_traffic(name: str, traffic_dir: pathlib.Path = BENCH_DIR / "traffic"
                 ) -> dict:
    traffic = load_json(traffic_dir / f"{name}.json")
    traffic.setdefault("name", name)
    return traffic


def traffic_generator(traffic: dict,
                      traffic_dir: pathlib.Path = BENCH_DIR / "traffic"):
    return load_module(traffic_dir / f"{traffic['kind']}.py",
                       f"chipbench_traffic_{traffic['kind']}")


def job_module(job: str, jobs_dir: pathlib.Path = BENCH_DIR / "jobs"):
    return load_module(jobs_dir / f"{job}.py", f"chipbench_job_{job}")


def metric_reader(name: str,
                  metrics_dir: pathlib.Path = BENCH_DIR / "metrics"
                  ) -> Callable:
    mod = load_module(metrics_dir / f"{name}.py",
                      "chipbench_metric_" + name.replace(".", "_")
                      .replace("-", "_"))
    return mod.read


def cell_metrics(bench: dict, workload: str, trace: bool) -> List[dict]:
    """The metric entries a run of ``workload`` reports: its end-to-end
    metrics without a trace, its per-layer metrics with one."""
    if not trace:
        return [m for m in bench["end_to_end"]
                if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in cell_metrics(bench, workload, False)}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])
            and m["moves"] in reported]


def load_peaks(device_kind: str,
               path: pathlib.Path = BENCH_DIR / "peaks.json") -> dict:
    table = load_json(path)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} has no entry in "
                       f"{path.name}; add its published peaks and source")
    return table[device_kind]


# ---------------------------------------------------------------------------
# Device, compile cache, compile counting, memory
# ---------------------------------------------------------------------------

def setup_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set (JAX reads it itself), else a fixed directory inside the checkout.
    Every program is cached, however quickly it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise NoChip(f"JAX platform is {d.platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    import jax
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.local_devices()]
    return max(peaks) if peaks else 0


class CompileCounter:
    """Counts programs compiled (or loaded from the persistent cache) and
    functions traced, through JAX's monitoring events."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    TRACE = "/jax/core/compile/jaxpr_trace_duration"

    def __init__(self):
        import jax
        self.compiles = 0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self.BACKEND:
            self.compiles += 1
        elif event == self.TRACE:
            self.traces += 1

    def mark(self):
        return (self.compiles, self.traces)

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def seed_key(seed: int):
    """A JAX key from any non-negative seed, 64 bits and more included (a
    plain ``PRNGKey`` keeps only the low 32 bits)."""
    import jax
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    hi = seed >> 32
    while hi:
        key = jax.random.fold_in(key, hi & 0xFFFFFFFF)
        hi >>= 32
    return key


# ---------------------------------------------------------------------------
# Statistics
# ---------------------------------------------------------------------------

def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (q in [0, 100]); inf if any value is."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    if math.isinf(xs[hi]):
        return math.inf if pos > lo or math.isinf(xs[lo]) else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit: at most the
    limit, or at least it where ``at_least`` is set."""
    name: str
    value: float
    limit: float
    at_least: bool = False

    @property
    def ok(self) -> bool:
        if not math.isfinite(self.value):
            return False
        return (self.value >= self.limit if self.at_least
                else self.value <= self.limit)

    def report(self) -> dict:
        return {"value": self.value, "limit": self.limit,
                "limit_is": "min" if self.at_least else "max"}


@dataclasses.dataclass
class RunContext:
    workload: dict
    config: dict
    traffic: dict
    generator: Any
    seed: int
    seconds: float
    trace: bool
    t_process: float                  # monotonic clock at process start
    peaks: Optional[dict] = None
    compiles: Optional[CompileCounter] = None


@dataclasses.dataclass
class JobResult:
    """What a job driver hands back. ``end_to_end`` holds the host-clock
    metrics; ``layer`` holds what the per-layer readers read (spans,
    counters, the reduced trace, the counts of operations and bytes)."""
    end_to_end: Dict[str, float]
    layer: Dict[str, Any]
    attempted: int
    failed: int
    checks: List[Check]
    setup_s: float
    memory_peak_bytes: int
    trace: Optional[dict] = None      # reduced trace: busy_s, window_s, ...
    artifacts: Optional[dict] = None  # what the check compared (control.py)


def run_job(argv_args, require_tpu: bool = True,
            root: pathlib.Path = ROOT,
            traffic_dir: pathlib.Path = BENCH_DIR / "traffic",
            peaks: Optional[dict] = None, compile_cache: bool = True):
    """Run one cell's job; returns (benchmark, workload, device, context,
    ``JobResult``). Raises ``NoChip`` before any work where the chip is
    missing. The CPU rehearsals pass ``require_tpu=False``, their own peaks
    and ``compile_cache=False``."""
    bench = load_benchmark(root)
    workload, cfg_entry = find_workload(bench, argv_args.workload)
    config = load_json(root / cfg_entry["file"])
    traffic = load_traffic(workload["traffic"], traffic_dir)

    cache = setup_compile_cache() if compile_cache else "off"
    device = device_info(int(workload["chips"]), require_tpu=require_tpu)
    if peaks is None:
        peaks = load_peaks(device["kind"])
    log(f"[device] {device['kind']} x {device['count']} "
        f"({device['platform']}); compile cache {cache}")

    compiles = CompileCounter()
    ctx = RunContext(workload=workload, config=config, traffic=traffic,
                     generator=traffic_generator(traffic, traffic_dir),
                     seed=int(argv_args.seed),
                     seconds=float(argv_args.seconds),
                     trace=bool(argv_args.trace), t_process=argv_args.t0,
                     peaks=peaks, compiles=compiles)
    try:
        res: JobResult = job_module(traffic["job"]).run(ctx)
    finally:
        compiles.close()
    return bench, workload, device, ctx, res


def run_cell(argv_args, **kw) -> dict:
    """Run one cell; returns the result line as a dict (see ``run_job``)."""
    bench, workload, device, ctx, res = run_job(argv_args, **kw)

    metrics = {}
    for m in cell_metrics(bench, workload["name"], ctx.trace):
        if ctx.trace:
            value = metric_reader(m["name"])(res.layer)
        elif m["name"] == "setup_s":
            value = res.setup_s
        else:
            value = res.end_to_end.get(m["name"])
        if value is None:
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    dev = dict(device, memory_peak_bytes=int(res.memory_peak_bytes))
    out = {"correct": all(c.ok for c in res.checks) and bool(res.checks),
           "attempted": int(res.attempted), "failed": int(res.failed),
           "metrics": metrics, "device": dev}
    if ctx.trace and res.trace is not None:
        dev["busy_s"] = res.trace["busy_s"]
        dev["window_s"] = res.trace["window_s"]
        out["breakdown"] = {"device_ops": res.trace["device_ops"],
                            "idle_gaps": res.trace["idle_gaps"]}
    out["checks"] = {c.name: c.report() for c in res.checks}
    return out


def main(argv=None, t0: Optional[float] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    args.t0 = time.monotonic() if t0 is None else t0
    try:
        out = run_cell(args)
    except NoChip as e:
        log(f"chipbench: {e}; nothing runs")
        return EXIT_NO_CHIP
    for name, c in out["checks"].items():
        log(f"check {name} = {c['value']!r} ({c['limit_is']} "
            f"{c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


def program_model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file: the program's
    own architecture entry with every size taken from the file, refused where
    the architecture's structure differs from what the file states."""
    import dataclasses as dc

    from repro.configs import get_config
    base = get_config(config["arch"])
    cfg = dc.replace(
        base, n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_attention_heads"], d_ff=config["ffn_dim"],
        vocab_size=config["vocab_size"],
        max_seq_len=config["max_position_embeddings"], head_dim=0)
    stated = {"activation": config["activation_function"],
              "norm": "layernorm", "pos_emb": "learned", "use_bias": True,
              "gated_mlp": False, "block_pattern": "dense",
              "tie_embeddings": config["tie_word_embeddings"],
              "param_dtype": config["torch_dtype"],
              "compute_dtype": config["torch_dtype"]}
    wrong = {k: (getattr(cfg, k), v) for k, v in stated.items()
             if getattr(cfg, k) != v}
    if wrong or cfg.padded_vocab != cfg.vocab_size:
        raise ValueError(f"program architecture {config['arch']!r} differs "
                         f"from the configuration file: {wrong}")
    return cfg
