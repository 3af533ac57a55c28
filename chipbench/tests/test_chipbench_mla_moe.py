"""CPU rehearsals of the DeepSeek-V3 search job (``jobs/search_mla_moe.py``)
and of the mapped-island job (``jobs/search_mapped.py``) at tiny sizes.

The search job's result line has the contract's shape and is correct; each
fault planted in the program's expert layer, and the float8 control in the
program's place, turns ``correct`` false. Runs use ``seconds=0``: the step
count is the traffic's ``min_steps``, whatever the host's load."""
import argparse
import dataclasses
import json
import os
import pathlib
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

import tiny
from chipbench import harness

MLA = "search.moonlight-16b-a3b.ep8"
MAPPED = "search.opt-1.3b.islands4"

MODEL = {
    "arch": "moonshot-v1-16b-a3b", "model_type": "deepseek_v3",
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 256,
    "kv_lora_rank": 32, "max_position_embeddings": 256,
    "moe_intermediate_size": 128, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 4, "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 2,
    "num_hidden_layers": 3, "num_key_value_heads": 4,
    "num_nextn_predict_layers": 0, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-5,
    "rope_theta": 50000, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 16,
    "vocab_size": 512, "torch_dtype": "float32", "router_experts": 8,
    "experts_held_start": 4, "bits": 2, "group_size": 32}

SEARCH = dict(tiny.SEARCH, job="search_mla_moe", min_steps=12)
ISLANDS = dict(tiny.SEARCH, job="search_mapped", islands=4, migrate_every=2,
               min_steps=6)


def make_checkout(root: pathlib.Path):
    """The tiny checkout with the two cells of these jobs added, on tiny
    configurations: the DeepSeek-V3 cell as ``BENCHMARK.json`` adds it, and
    the mapped-island cell (not in ``BENCHMARK.json``: at the OPT cell's
    sizes it does not fit one chip's memory, PERF.md) reporting
    ``search_proposals_per_s`` and ``setup_s``."""
    traffic = tiny.make_checkout(root)
    (root / "configs" / "tiny_mla.json").write_text(json.dumps(MODEL))
    (traffic / "tiny_mla.json").write_text(json.dumps(SEARCH))
    (traffic / "tiny_islands.json").write_text(json.dumps(ISLANDS))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny_mla", "source": "tiny",
                             "file": "configs/tiny_mla.json", "reduced": [],
                             "why": "rehearsal"})
    bench["workloads"] += [
        {"name": MLA, "config": "tiny_mla", "traffic": "tiny_mla",
         "chips": 1, "why": "rehearsal"},
        {"name": MAPPED, "config": "tiny", "traffic": "tiny_islands",
         "chips": 4, "why": "rehearsal"}]
    for m in bench["end_to_end"]:
        if m["name"] == "search_proposals_per_s":
            m["workloads"] = m["workloads"] + [MAPPED]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return traffic


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout_mla")
    make_checkout(root)
    return root


def _job(root, workload, seed):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=0.0,
                              trace=0, t0=time.monotonic())
    return harness.run_job(args, require_tpu=False, root=root,
                           traffic_dir=root / "traffic", peaks=tiny.PEAKS,
                           compile_cache=False)


def test_job_prints_a_correct_contract_line(checkout):
    out = tiny.run(checkout, MLA, seed=2**31 + 15, seconds=0.0)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"search_proposals_per_s", "setup_s"}


def test_job_reports_paths_flips_and_counts(checkout):
    from chipbench import counts_mla_moe
    from repro import obs
    # the counters are the process's: other tests may have counted before
    capacity = obs.counter("moe_dispatch_total").value(path="capacity")
    _, _, _, ctx, res = _job(checkout, MLA, 16)
    layer = res.layer
    assert layer["moe_dispatch_total"]["grouped"] > 0
    assert layer["moe_dispatch_total"]["capacity"] == capacity
    assert layer["attention_path_total"]["blocked"] > 0   # the CPU's path
    assert 0.0 <= layer["routing_flip_share"] < 0.05
    # the held experts get about their uniform share of the slots
    uniform = counts_mla_moe.expected_held_slots(ctx.config, 4, 32)
    assert 0.5 * uniform < layer["held_slots"] < 1.5 * uniform
    assert layer["flops_per_proposal"] == counts_mla_moe.forward_flops(
        ctx.config, 4, 32, layer["held_slots"])


def _softmax_router(orig):
    def route(p, cfg, x):
        moe = dataclasses.replace(cfg.moe, router="softmax_topk")
        return orig(p, dataclasses.replace(cfg, moe=moe), x)
    return route


def _bias_in_weights(orig):
    def route(p, cfg, x):
        w, idx = orig(p, cfg, x)
        bias = jnp.take(p["router_bias"], idx)
        return w + bias.astype(w.dtype) * cfg.moe.routed_scaling_factor, idx
    return route


@pytest.mark.parametrize("fault", ["softmax_router", "bias_in_weights",
                                   "no_shared_experts", "capacity_drops"])
def test_planted_fault_fails(checkout, monkeypatch, fault):
    from repro.models import layers as L
    if fault == "softmax_router":
        monkeypatch.setattr(L, "moe_route", _softmax_router(L.moe_route))
    elif fault == "bias_in_weights":
        monkeypatch.setattr(L, "moe_route", _bias_in_weights(L.moe_route))
    elif fault == "no_shared_experts":
        orig = L.moe_ffn
        monkeypatch.setattr(L, "moe_ffn", lambda p, cfg, x: orig(
            {k: v for k, v in p.items() if k != "shared"}, cfg, x))
    else:   # a buffer of half the even share (capacity factor 0.5): the
        # slots past it are dropped (the balanced bias leaves a buffer of the
        # whole even share nearly nothing to drop)
        monkeypatch.setattr(L, "_dispatch_rows",
                            lambda t, k, held: t * k * held // 16)
    out = tiny.run(checkout, MLA, seed=41, seconds=0.0)
    assert out["correct"] is False, out["checks"]


def test_fp8_control_fails_the_limits(checkout):
    _, _, _, ctx, res = _job(checkout, MLA, 43)
    assert all(c.ok for c in res.checks)
    job = harness.job_module("search_mla_moe")
    from chipbench import weights_mla_moe
    calib = jnp.asarray(ctx.generator.make(ctx.traffic, ctx.config,
                                           ctx.seed))
    params = weights_mla_moe.make_weights(ctx.config,
                                          harness.seed_key(ctx.seed), calib)
    art = res.artifacts
    low = job.reference_terms(ctx.config, params, calib, art["transforms"],
                              art["n_match"], matmul_dtype=jnp.float8_e4m3fn)
    ce, mse = job._BASE.rel_gaps(low, art["reference"])
    assert ce > job.LIMITS["ce_gap"] or mse > job.LIMITS["mse_gap"], (ce, mse)


def test_parent_program_is_refused_cleanly():
    """A program whose architecture lacks latent attention (as before this
    configuration) is refused before any work."""
    job = harness.job_module("search_mla_moe")
    from repro.configs import get_config
    cfg = get_config("moonshot-v1-16b-a3b")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr("repro.configs.get_config",
                   lambda arch: dataclasses.replace(cfg, mla=None))
        with pytest.raises(ValueError, match="latent attention"):
            job.program_model_config(MODEL)


_MAPPED_SCRIPT = """
import json, pathlib, sys
sys.path[:0] = [{root!r}, {src!r}, {tests!r}]
import tiny, test_chipbench_mla_moe as t
root = pathlib.Path({ck!r})
t.make_checkout(root)
out = tiny.run(root, t.MAPPED, seed=2**31 + 5, seconds=0.0)
print(json.dumps(out))
"""


def test_mapped_islands_run_on_four_cpu_devices(tmp_path):
    here = pathlib.Path(__file__).resolve().parent
    script = _MAPPED_SCRIPT.format(root=str(harness.ROOT),
                                   src=str(harness.ROOT / "src"),
                                   tests=str(here), ck=str(tmp_path / "ck"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out["checks"]
    assert out["device"]["count"] == 4
    assert set(out["metrics"]) == {"search_proposals_per_s", "setup_s"}


def test_moe_shares_read_their_scopes_and_nothing_else(monkeypatch):
    """The three MoE readers sum the window's device seconds under their
    scopes; those seconds are ``scopes``'s "other", so the MoE shares, the
    four model parts and the rest add up to the busy time."""
    from chipbench import scopes
    maps = {"eval": {
        "fusion.1": "jit(eval_body)/blocks/while/body/moe.experts/ragged_dot",
        "fusion.2": "jit(eval_body)/blocks/while/body/attn/dot_general",
        "fusion.3": "jit(eval_body)/blocks/while/body/moe.router/dot_general",
        "fusion.4": "jit(eval_body)/blocks/while/body/moe.dispatch/sort",
        "fusion.5": "jit(eval_body)/blocks/while/body/moe.shared/dot_general",
        "fusion.6": "jit(eval_body)/blocks/while/body/add"}}
    ops = {"%fusion.1 fusion": 1.0, "%fusion.2 fusion": 2.0,
           "%fusion.3 fusion": 0.25, "%fusion.4 fusion": 0.75,
           "%fusion.5 fusion": 1.5, "%fusion.6 fusion": 0.5}
    layer = {"trace": {"window_s": 10.0, "ops": ops, "programs": {}}}
    monkeypatch.setattr(scopes, "program_scopes", lambda: maps)
    share = {n: harness.metric_reader(f"search_device_share.{n}")(layer)
             for n in ("moe_experts", "moe_routing", "moe_shared",
                       "attention", "ffn")}
    assert share == pytest.approx({"moe_experts": 10.0, "moe_routing": 10.0,
                                   "moe_shared": 15.0, "attention": 20.0,
                                   "ffn": 0.0})
    by = scopes.seconds_by_layer(ops, maps, {})
    assert by["other"] == pytest.approx(1.0 + 1.0 + 1.5 + 0.5)
    assert harness.metric_reader("search_device_share.moe_experts")({}) \
        is None


def test_correction_bias_evens_the_loads():
    """The balanced ``e_score_correction_bias`` gives each of the router's
    experts its even share of the calibration batch's slots in every MoE
    layer, so every seed sends the held experts the same work."""
    import numpy as np

    from chipbench import reference_mla_moe, weights_mla_moe
    calib = jax.random.randint(jax.random.PRNGKey(3), (4, 32), 0,
                               MODEL["vocab_size"], jnp.int32)
    params = weights_mla_moe.make_weights(MODEL, jax.random.PRNGKey(5), calib)
    with jax.default_matmul_precision("highest"):
        _, idx = reference_mla_moe.forward(params, calib, MODEL,
                                           with_routing=True)
    E = MODEL["router_experts"]
    even = calib.size * MODEL["num_experts_per_tok"] / E
    loads = np.stack([np.bincount(np.asarray(i).ravel(), minlength=E)
                      for i in idx])
    assert loads.shape == (MODEL["num_hidden_layers"] - 1, E)
    # one slot either way: a near tie the reference's scan may order apart
    assert np.abs(loads - even).max() <= 1, loads
