"""Launch driver for the population × island discrete search.

    PYTHONPATH=src python -m repro.launch.search --arch opt-tiny \
        --steps 40 --population 4 --islands 2 --bits 2 --group 32

Builds the local mesh, shards the calibration batch over the data axis,
runs the RTN→InvarExplore pipeline through ``repro.search.engine``, and
merges a proposals/sec row into ``artifacts/benchmarks/BENCH_search.json``
so CI accumulates a search-perf trajectory next to ``BENCH_kernels.json``.
With ``--mapped`` the islands run one-per-device-shard
(``SearchConfig(mapped=True)``; ``--islands`` must equal the device count)
and the row lands under the ``search_mapped_islands/`` family — bench-smoke
asserts both families are present. With ``--measure-mem`` the row carries
``peak_live_bytes`` (the ``jax.live_arrays()`` delta over the run) and lands
under ``search_unit_install/`` or ``search_stack_install/`` per
``--install``, so CI can assert the O(unit) memory model at K=8.

Configs run in their ``.reduced()`` form unless ``--full`` asks for the
published widths; ``--layers N`` then cuts the depth to N blocks (a printed
``reduced:`` line records the cut) so the search's resident state — fp and
base models, FFN base and fake-quant stacks, calibration logits — fits one
device. Weights are random, made from ``--seed``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import time

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs import get_config
from repro.core.pipeline import quantize_model
from repro.core.quant import QuantConfig
from repro.core.search import SearchConfig
from repro.data.calib import calibration_tokens
from repro.dist.sharding import ShardingRules, data_spec
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import init_params

ART = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "benchmarks"

__all__ = ["run_search_bench", "main"]


def _merge_rows(out: pathlib.Path, row: dict):
    """Accumulate rows by name (so the engine and mapped-islands benches land
    side by side in one BENCH_search.json across invocations)."""
    rows = []
    if out.exists():
        try:
            rows = [r for r in json.loads(out.read_text())
                    if r.get("name") != row["name"]]
        except (ValueError, KeyError):
            rows = []
    rows.append(row)
    out.write_text(json.dumps(rows, indent=1))


def search_config(arch: str, *, full: bool = False, n_layers: int = None):
    """``arch`` at its published widths (``full``) or ``.reduced()``, with
    the depth cut to ``n_layers`` blocks when given (the cut is printed)."""
    cfg = get_config(arch) if full else get_config(arch).reduced()
    if n_layers is not None and n_layers != cfg.n_layers:
        print(f"reduced: n_layers {cfg.n_layers} -> {n_layers} "
              f"({arch}, widths unchanged)", flush=True)
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    return cfg


def run_search_bench(arch: str = "opt-tiny", *, full: bool = False,
                     n_layers: int = None, steps: int = 40,
                     population: int = 4, islands: int = 1,
                     temperature: float = 0.0, anneal: str = "geometric",
                     migrate_every: int = 25, fused: bool = False,
                     mapped: bool = False, objective: str = "ce",
                     install: str = "unit", tabu: int = 0,
                     shard_calib: bool = False, measure_mem: bool = False,
                     bits: int = 2, group: int = 32, n_seqs: int = 4,
                     seq_len: int = 128, seed: int = 0,
                     out: pathlib.Path = None,
                     metrics_out: str = obs.DEFAULT_METRICS_PATH):
    """Run one search through ``quantize_model``; returns the bench row
    (also merged into ``out``) and the ``SearchResult``."""
    cfg = search_config(arch, full=full, n_layers=n_layers)
    params = init_params(jax.random.PRNGKey(seed), cfg)

    mesh = make_local_mesh()
    rules = ShardingRules(mesh, cfg)
    calib = jnp.asarray(calibration_tokens(cfg.vocab_size, n_seqs=n_seqs,
                                           seq_len=seq_len))
    if not mapped:  # mapped mode replicates the calib batch to every island
        calib = jax.device_put(calib, jax.sharding.NamedSharding(
            mesh, data_spec(rules, calib.shape[0])))

    scfg = SearchConfig(steps=steps, seed=seed, n_match_layers=2, log_every=0,
                        population=population, islands=islands,
                        temperature=temperature, anneal=anneal,
                        migrate_every=migrate_every, fused_kernel=fused,
                        mapped=mapped, objective=objective, install=install,
                        tabu=tabu, shard_calib=shard_calib,
                        measure_memory=measure_mem)
    qcfg = QuantConfig(bits=bits, group_size=group)

    prop_before = obs.counter(
        "search_proposals_total", "Candidate transforms proposed").total()
    t0 = time.monotonic()
    result = quantize_model(params, cfg, qcfg, method="rtn",
                            calib_tokens=calib, search=scfg)
    dt = time.monotonic() - t0
    sr = result.search
    proposals = sr.stats["proposals"] if sr.stats else steps
    # the registry must reconcile exactly with the legacy stats dict — a
    # drift here means an instrumentation hook was moved off the hot path
    prop_delta = obs.counter("search_proposals_total", "").total() - prop_before
    if sr.stats and not mapped and prop_delta != proposals:
        raise AssertionError(
            f"obs/stats divergence: search_proposals_total grew by "
            f"{prop_delta} but stats['proposals'] == {proposals}")
    if measure_mem:
        # memory-model benchmark rows: bench-smoke asserts the unit-install
        # peak live bytes stay below the K-full-stacks lane at the same K
        family = ("search_unit_install" if install == "unit"
                  else "search_stack_install")
    elif mapped:
        family = "search_mapped_islands"
    else:
        family = "search/engine"
    row = {
        "name": (f"{family}/{arch}s{steps}p{population}i{islands}"
                 f"b{bits}g{group}" + ("fused" if fused else "")
                 + (f"-full{cfg.n_layers}L" if full else "")
                 + (f"-{objective}" if objective != "ce" else "")),
        "us_per_call": round(dt * 1e6 / max(proposals, 1), 1),
        "derived": (f"proposals_per_sec={proposals / max(dt, 1e-9):.2f} "
                    f"loss={sr.initial_loss:.4f}->{sr.final_loss:.4f} "
                    f"accept={sr.accept_rate:.2%} "
                    f"migrations={sr.stats['migrations'] if sr.stats else 0} "
                    f"objective={sr.stats.get('objective', objective)} "
                    f"install={sr.stats.get('install', install)} "
                    f"tabu_hits={sr.stats.get('tabu_hits', 0)}"),
    }
    if measure_mem and sr.stats and "peak_live_bytes" in sr.stats:
        row["peak_live_bytes"] = int(sr.stats["peak_live_bytes"])
        row["stack_bytes"] = int(sr.stats["stack_bytes"])
        row["candidate_batch_bytes"] = int(sr.stats["candidate_batch_bytes"])
    print(f"{row['name']},{row['us_per_call']},{row['derived']}")
    out = pathlib.Path(out) if out else ART / "BENCH_search.json"
    out.parent.mkdir(parents=True, exist_ok=True)
    _merge_rows(out, row)
    if metrics_out:
        obs.write_snapshot(path=metrics_out)
    return row, sr


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="opt-tiny")
    ap.add_argument("--full", action="store_true",
                    help="published widths (default: .reduced())")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to N blocks (printed as reduced:)")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--population", type=int, default=4)
    ap.add_argument("--islands", type=int, default=1)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--anneal", default="geometric")
    ap.add_argument("--migrate-every", type=int, default=25)
    ap.add_argument("--fused", action="store_true",
                    help="fused transform+fake-quant kernel hot path")
    ap.add_argument("--mapped", action="store_true",
                    help="one island per mesh shard (requires --islands == "
                         "device count; see README 'Multi-host')")
    ap.add_argument("--objective", default="ce",
                    choices=["ce", "kl", "swd_actmatch", "saliency_ce"],
                    help="search objective (registry name)")
    ap.add_argument("--install", default="unit", choices=["unit", "stack"],
                    help="candidate install mode: 'unit' = stack + K x unit "
                         "dynamic-slice buffers; 'stack' = K full stacks")
    ap.add_argument("--tabu", type=int, default=0,
                    help="tried-point memory capacity (0 disables)")
    ap.add_argument("--shard-calib", action="store_true",
                    help="each island climbs on its own calibration slice")
    ap.add_argument("--measure-mem", action="store_true",
                    help="sample jax.live_arrays() peaks; rows land under "
                         "search_unit_install/search_stack_install")
    ap.add_argument("--bits", type=int, default=2)
    ap.add_argument("--group", type=int, default=32)
    ap.add_argument("--seqs", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--metrics-out", default=obs.DEFAULT_METRICS_PATH,
                    help="merged metrics snapshot path ('' disables)")
    ap.add_argument("--trace-out", default="",
                    help="span/event JSONL sink path ('' disables)")
    args = ap.parse_args(argv)
    if args.trace_out:
        obs.set_trace_sink(args.trace_out)
    setup_compile_cache()
    run_search_bench(args.arch, full=args.full, n_layers=args.layers,
                     steps=args.steps, population=args.population,
                     islands=args.islands, temperature=args.temperature,
                     anneal=args.anneal, migrate_every=args.migrate_every,
                     fused=args.fused, mapped=args.mapped,
                     objective=args.objective, install=args.install,
                     tabu=args.tabu, shard_calib=args.shard_calib,
                     measure_mem=args.measure_mem, bits=args.bits,
                     group=args.group, n_seqs=args.seqs,
                     seq_len=args.seq_len, seed=args.seed, out=args.out,
                     metrics_out=args.metrics_out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
