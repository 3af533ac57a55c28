import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any jax import/initialization (device count locks on first
# init). The dry-run compiles on 512 fake CPU devices and never takes an
# accelerator; its --all children inherit both settings.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all          # all cells, both meshes
    PYTHONPATH=src python -m repro.launch.dryrun --all --jobs-file cells.txt

Per cell this proves: the sharding config is coherent (SPMD partitioning
succeeds), the program fits (memory_analysis), and yields the roofline inputs
(cost_analysis + Δ-trick per-layer rates + collective-bytes parse).
Results append to artifacts/dryrun/<cell>.json.
"""
import argparse
import dataclasses
import json
import pathlib
import subprocess
import sys
import time
import traceback

import jax

from repro.configs import get_config, list_archs
from repro.dist.sharding import (ShardingRules, param_specs, opt_state_specs,
                                 cache_specs, data_spec, to_shardings)
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (collective_bytes, extrapolate, roofline_terms,
                                   model_flops, HW)
from repro.launch.steps import (SHAPES, shape_applicable, make_train_step,
                                make_serve_step, make_prefill_step, input_specs)
from repro.models.config import ModelConfig

ART = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _scaled_cfg(cfg: ModelConfig, n_layers: int, seq: int) -> ModelConfig:
    """Δ-trick config: L layers with EVERY scan fully unrolled so XLA cost
    analysis counts each iteration (while bodies are otherwise counted once —
    see launch/roofline.py). Memory/schedule still come from the real
    (scanned) full-depth compile.

    The attention KV-chunk is raised so at most 64 chunks unroll — attention
    FLOPs are chunk-size-invariant (only the online-softmax correction ops
    scale with chunk count), so this caps compile time without distorting the
    measurement. The SSD chunk stays at its deployed size (its intra-chunk
    quadratic DOES depend on chunk) — its inter-chunk recurrence body is a
    tiny state update, cheap to unroll fully.
    """
    kw = {"n_layers": n_layers, "unroll_layers": True, "unroll_inner": True,
          "attn_chunk": max(cfg.attn_chunk, (seq + 63) // 64)}
    if cfg.is_enc_dec:
        kw["encoder_layers"] = n_layers
    return dataclasses.replace(cfg, **kw)


def _delta_layers(cfg: ModelConfig):
    if cfg.block_pattern == "hybrid":
        return cfg.hybrid_period, 2 * cfg.hybrid_period
    return 2, 3


def _shardings_for(kind, rules, structs, cfg, batch):
    """in_shardings tuple matching the step args."""
    if kind == "train":
        params_s, opt_s, batch_s = structs
        pspec = param_specs(rules, params_s)
        ospec = opt_state_specs(rules, params_s)
        bspec = {"tokens": data_spec(rules, batch)}
        if "vision_embeds" in batch_s:
            bspec["vision_embeds"] = jax.sharding.PartitionSpec(*data_spec(rules, batch), None)
        if "enc_embeds" in batch_s:
            bspec["enc_embeds"] = jax.sharding.PartitionSpec(*data_spec(rules, batch), None)
        return (pspec, ospec, bspec)
    if kind == "prefill":
        params_s, batch_s = structs
        pspec = param_specs(rules, params_s)
        bspec = {"tokens": data_spec(rules, batch)}
        if "vision_embeds" in batch_s:
            bspec["vision_embeds"] = jax.sharding.PartitionSpec(*data_spec(rules, batch), None)
        if "enc_embeds" in batch_s:
            bspec["enc_embeds"] = jax.sharding.PartitionSpec(*data_spec(rules, batch), None)
        return (pspec, bspec)
    # decode
    params_s, tok_s, cache_s, idx_s = structs
    pspec = param_specs(rules, params_s)
    cspec = cache_specs(rules, cfg, batch)
    if isinstance(cache_s, dict) and "cross" in cache_s and "cross" not in cspec:
        cspec = dict(cspec)
        cspec["cross"] = (jax.sharding.PartitionSpec(), jax.sharding.PartitionSpec())
    tspec = data_spec(rules, batch)
    return (pspec, tspec, cspec, jax.sharding.PartitionSpec())


def _compile_once(cfg: ModelConfig, shape: str, mesh, rules, *, want_text=False,
                  accum: int = 1):
    kind, structs = input_specs(cfg, shape)
    info = SHAPES[shape]
    if kind == "train":
        cfg_t = dataclasses.replace(cfg, remat=True)
        step = make_train_step(cfg_t, accum_steps=accum)
    elif kind == "prefill":
        # VLM prefill holds frontend_len patch positions + seq tokens
        extra = cfg.frontend_len if cfg.frontend == "vision" else 0
        step = make_prefill_step(cfg, max_len=info["seq"] + extra)
    else:
        step = make_serve_step(cfg)
    in_sh = _shardings_for(kind, rules, structs, cfg, info["batch"])
    in_sh = to_shardings(mesh, in_sh)
    t0 = time.monotonic()
    with mesh:
        lowered = jax.jit(step, in_shardings=in_sh).lower(*structs)
        compiled = lowered.compile()
    dt = time.monotonic() - t0
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    txt = compiled.as_text() if want_text else None
    coll = collective_bytes(compiled.as_text())
    return {
        "kind": kind,
        "compile_s": round(dt, 2),
        "flops_dev": float(ca.get("flops", 0.0)),
        "bytes_dev": float(ca.get("bytes accessed", 0.0)),
        "coll_dev": coll,
        "memory": None if ma is None else {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "peak_bytes": ma.argument_size_in_bytes + ma.temp_size_in_bytes,
        },
        "hlo_text": txt,
    }


# §Perf hillclimb knobs: --opt a,b,c applies these config/rule overrides and
# writes the cell artifact under a suffixed name (baselines stay untouched).
OPTS = {
    "remat_dots": {"remat_policy": "dots"},
    "remat_dots_all": {"remat_policy": "dots_all"},
    "bf16_scores": {"attn_softmax_dtype": "bfloat16"},
    "repeat_kv": {"gqa_repeat_kv": True},
    "kv_int8": {"kv_cache_dtype": "int8"},
    "chunk4k": {"attn_chunk": 4096},
    "chunk8k": {"attn_chunk": 8192},
    "chunk32k": {"attn_chunk": 32768},
    "heads_shard": {},  # rules-level (long_decode_shard="heads")
    "cap1": {},         # moe capacity_factor 1.25 -> 1.0 (handled in run_cell)
    "accum4": {},       # 4x gradient accumulation (handled in run_cell)
}


def run_cell(arch: str, shape: str, *, multi_pod: bool, delta: bool = True,
             zero1: bool = False, keep_text: bool = False, opts=()) -> dict:
    cfg = get_config(arch)
    overrides = {}
    for o in opts:
        overrides.update(OPTS[o])
    if "cap1" in opts and cfg.moe is not None:
        overrides["moe"] = dataclasses.replace(cfg.moe, capacity_factor=1.0)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    rec = {"arch": arch, "shape": shape, "opts": list(opts),
           "mesh": "2x16x16" if multi_pod else "16x16", "ok": False}
    if not shape_applicable(cfg, shape):
        rec.update(ok=True, skipped=True,
                   note="long_500k skipped: pure full-attention arch (DESIGN.md)")
        return rec
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        rules = ShardingRules(
            mesh, cfg, zero1=zero1,
            long_decode_shard="heads" if "heads_shard" in opts else "seq")
        n_chips = 512 if multi_pod else 256
        accum = 4 if "accum4" in opts else 1
        full = _compile_once(cfg, shape, mesh, rules, want_text=keep_text,
                             accum=accum)
        rec.update(ok=True, kind=full["kind"], compile_s=full["compile_s"],
                   memory=full["memory"], coll_schedule=full["coll_dev"])

        if delta and not multi_pod:
            l2, l3 = _delta_layers(cfg)
            seq = SHAPES[shape]["seq"]
            r2 = _compile_once(_scaled_cfg(cfg, l2, seq), shape, mesh, rules, accum=accum)
            r3 = _compile_once(_scaled_cfg(cfg, l3, seq), shape, mesh, rules, accum=accum)
            lf = cfg.n_layers
            flops_dev = extrapolate(r2["flops_dev"], r3["flops_dev"], l2, l3, lf)
            bytes_dev = extrapolate(r2["bytes_dev"], r3["bytes_dev"], l2, l3, lf)
            c2 = sum(r2["coll_dev"].values())
            c3 = sum(r3["coll_dev"].values())
            coll_dev = extrapolate(c2, c3, l2, l3, lf)
            terms = roofline_terms(flops_dev, bytes_dev, coll_dev)
            info = SHAPES[shape]
            n_tokens = info["batch"] * (info["seq"] if full["kind"] != "decode" else 1)
            mf = model_flops(cfg, n_tokens, train=(full["kind"] == "train"))
            terms["model_flops_global"] = mf
            terms["hlo_flops_global"] = flops_dev * n_chips
            terms["useful_ratio"] = mf / max(flops_dev * n_chips, 1.0)
            step_time = max(terms["compute_s"], terms["memory_s"], terms["collective_s"])
            terms["mfu_bound"] = (mf / n_chips / HW["peak_flops"]) / max(step_time, 1e-12)
            rec.update(flops_dev=flops_dev, bytes_dev=bytes_dev, coll_dev=coll_dev,
                       roofline=terms, delta_layers=[l2, l3])
    except Exception as e:  # noqa: BLE001 — a failed cell is a bug to record
        rec.update(ok=False, error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
    return rec


def _cell_path(arch, shape, multi_pod, opts=()):
    mesh = "2x16x16" if multi_pod else "16x16"
    suffix = ("__opt-" + "-".join(opts)) if opts else ""
    return ART / f"{arch}__{shape}__{mesh}{suffix}.json"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--no-delta", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape x mesh) cell in subprocesses")
    ap.add_argument("--opt", default="",
                    help="comma-separated perf knobs: " + ",".join(OPTS))
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args(argv)
    opts = tuple(o for o in args.opt.split(",") if o)
    ART.mkdir(parents=True, exist_ok=True)

    if args.all:
        cells = [(a, s, mp)
                 for a in list_archs()
                 for s in SHAPES
                 for mp in (False, True)]
        todo = [(a, s, mp) for a, s, mp in cells
                if args.force or not _cell_path(a, s, mp).exists()]
        print(f"[dryrun] {len(todo)}/{len(cells)} cells to run")
        for i, (a, s, mp) in enumerate(todo):
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", a, "--shape", s] + (["--multi-pod"] if mp else [])
            t0 = time.monotonic()
            r = subprocess.run(cmd, capture_output=True, text=True,
                               env={**os.environ}, timeout=3600)
            ok = "?"
            p = _cell_path(a, s, mp)
            if p.exists():
                ok = json.loads(p.read_text()).get("ok")
            print(f"[dryrun {i+1}/{len(todo)}] {a} {s} mp={mp} ok={ok} "
                  f"({time.monotonic()-t0:.0f}s)", flush=True)
            if r.returncode != 0:
                print(r.stderr[-1500:], flush=True)
        return

    rec = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   delta=not args.no_delta, zero1=args.zero1, opts=opts)
    out = _cell_path(args.arch, args.shape, args.multi_pod, opts)
    out.write_text(json.dumps(rec, indent=1, default=str))
    if rec.get("memory"):
        print(f"memory_analysis: {rec['memory']}")
    if rec.get("roofline"):
        rl = {k: v for k, v in rec["roofline"].items()
              if isinstance(v, (int, float))}
        print(f"roofline: {rl}")
    print(json.dumps({k: rec[k] for k in ("arch", "shape", "mesh", "ok") if k in rec}))
    if not rec["ok"] and "error" in rec:
        print(rec["error"])
        print(rec.get("trace", ""))
        sys.exit(1)


if __name__ == "__main__":
    main()
