"""Search job for a DeepSeek-V3 configuration (Moonlight-16B-A3B's share of
an expert-parallel deployment): the paper's search through
``quantize_model``, measured as ``jobs/search.py`` measures OPT.

This job runs ``jobs/search.py``'s ``run`` itself, on a private copy of that
module whose four configuration-specific names are this file's: the
program's ``ModelConfig`` and the weights (``make_inputs``), the plain
reference (``reference_terms``), the operation counts and the limits. The
window, the spans, the trace and the checks are that job's, unchanged.

Besides its checks a run reports, on stderr and in its per-layer context:
``attention_path_total`` and ``moe_dispatch_total`` (which attention kernel
and which expert dispatch the traced programs hold), and the routing flip
share: the token-layer pairs whose six chosen experts differ between the
program's float32 forward (at the configuration's matmul precision) and the
reference's, over the calibration batch. Those flips are part of what the
limits absorb.
"""
from __future__ import annotations

import dataclasses
import types

from chipbench import counts_mla_moe, harness
from chipbench import reference_mla_moe as reference
from chipbench import weights_mla_moe as weights

ROW_BLOCK = 4      # calibration rows per reference call
# Limits of the relative gaps between the search's reported terms and the
# reference, set from chip readings of sound runs and of the float8 control
# (PERF.md section 6). Near-tie routing flips between the program's
# bfloat16-input forward and the float32 reference move the sound runs' CE
# (at most 1.48e-4) into the control's range (2.2e-4 to 5.1e-4), so the
# activation MSE is the limit that separates them: sound at most 9.9e-5,
# control at least 3.7e-4. The least gain of the reference's objective and
# the accepted steps are jobs/search.py's.
LIMITS = {"ce_gap": 2.5e-4, "mse_gap": 2e-4, "objective_gain": 1e-3,
          "accepted_steps": 1}

_BASE = harness.load_module(harness.BENCH_DIR / "jobs" / "search.py",
                            "chipbench_job_search_base_mla_moe")
_STATE: dict = {}


def program_model_config(conf: dict):
    """The program's ``ModelConfig`` for the configuration file: the
    program's own architecture entry with every size taken from the file,
    refused where the architecture's structure differs from the file's."""
    from repro.configs import get_config
    from repro.models.config import MLAConfig

    base = get_config(conf["arch"])
    if base.mla is None or base.moe is None:
        raise ValueError(f"program architecture {conf['arch']!r} has no "
                         f"latent attention or experts")
    moe = dataclasses.replace(
        base.moe, num_experts=conf["router_experts"],
        top_k=conf["num_experts_per_tok"],
        n_shared_experts=conf["n_shared_experts"],
        norm_topk_prob=conf["norm_topk_prob"],
        routed_scaling_factor=conf["routed_scaling_factor"],
        experts_held=(conf["experts_held_start"], conf["n_routed_experts"]))
    cfg = dataclasses.replace(
        base, n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["moe_intermediate_size"],
        dense_d_ff=conf["intermediate_size"],
        first_k_dense=conf["first_k_dense_replace"],
        vocab_size=conf["vocab_size"],
        max_seq_len=conf["max_position_embeddings"],
        rope_theta=float(conf["rope_theta"]), norm_eps=conf["rms_norm_eps"],
        param_dtype=conf["torch_dtype"], compute_dtype=conf["torch_dtype"],
        moe=moe, mla=MLAConfig(kv_lora_rank=conf["kv_lora_rank"],
                               qk_nope_head_dim=conf["qk_nope_head_dim"],
                               qk_rope_head_dim=conf["qk_rope_head_dim"],
                               v_head_dim=conf["v_head_dim"]))
    stated = {"activation": conf["hidden_act"], "norm": "rmsnorm",
              "pos_emb": "rope", "use_bias": conf["attention_bias"],
              "gated_mlp": True, "block_pattern": "moe",
              "tie_embeddings": conf["tie_word_embeddings"]}
    wrong = {k: (getattr(cfg, k), v) for k, v in stated.items()
             if getattr(cfg, k) != v}
    rule = (conf["scoring_func"], conf["topk_method"], conf["n_group"],
            conf["topk_group"], conf["q_lora_rank"])
    if rule != ("sigmoid", "noaux_tc", 1, 1, None):
        wrong["routing"] = rule
    if moe.router != "sigmoid_noaux" or moe.dispatch != "grouped":
        wrong["moe"] = (moe.router, moe.dispatch)
    if wrong or cfg.padded_vocab != cfg.vocab_size:
        raise ValueError(f"program architecture {conf['arch']!r} differs "
                         f"from the configuration file: {wrong}")
    return cfg


def make_inputs(ctx):
    """(program config, weights, calibration batch, quant config)."""
    import jax
    import jax.numpy as jnp

    from repro.core.quant import QuantConfig
    from repro.models import init_params

    conf = ctx.config
    cfg = program_model_config(conf)
    calib = jnp.asarray(ctx.generator.make(ctx.traffic, conf, ctx.seed))
    params = weights.make_weights(conf, harness.seed_key(ctx.seed), calib)
    want = weights.tree_shapes(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    if weights.tree_shapes(params) != want:
        raise ValueError("the program's parameter layout changed: "
                         f"{sorted(want)}")
    qcfg = QuantConfig(bits=conf["bits"], group_size=conf["group_size"])
    _STATE.update(cfg=cfg, params=params, calib=calib)
    return cfg, params, calib, qcfg


def reference_terms(conf: dict, params, calib, transforms, n_match: int,
                    matmul_dtype=None):
    """[(ce, mse) of the start, (ce, mse) of ``transforms``] by the plain
    reference, in blocks of calibration rows; the float32 pass also leaves
    its routing and the slots sent to held experts in ``_STATE``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n_dense, n_shared, n_held = reference.unit_counts(conf)
    n_lead = conf["first_k_dense_replace"]
    n = (n_lead * n_dense
         + (conf["num_hidden_layers"] - n_lead) * (n_shared + n_held))
    F = conf["moe_intermediate_size"]
    ident = (jnp.tile(jnp.arange(F, dtype=jnp.int32), (n, 1)),
             jnp.ones((n, F), jnp.float32), jnp.zeros((n, F // 2), jnp.float32))
    final = tuple(jnp.asarray(x) for x in transforms)
    start, held = conf["experts_held_start"], conf["n_routed_experts"]

    def block(params, toks, t0, t1):
        _, h_fp, idx = reference.forward(params, toks, conf,
                                         matmul_dtype=matmul_dtype,
                                         collect_hidden=True,
                                         with_routing=True)
        out = []
        for t in (t0, t1):
            lg, h = reference.forward(
                params, toks, conf, matmul_dtype=matmul_dtype,
                collect_hidden=True,
                layer_fn=reference.searched_layer(conf, *t))
            nll = reference.token_nll(lg, toks, conf["vocab_size"]).sum()
            d = h[:n_match] - h_fp[:n_match]
            out.append((nll, jnp.sum(d * d)))
        slots = jnp.sum((idx >= start) & (idx < start + held))
        return out, idx, slots

    sums = np.zeros((2, 2))
    routing, slots = [], 0
    with jax.default_matmul_precision("highest"):
        f = jax.jit(block)
        for r in range(0, calib.shape[0], ROW_BLOCK):
            res, idx, s = f(params, calib[r:r + ROW_BLOCK], ident, final)
            sums += np.asarray([[float(a), float(b)] for a, b in res])
            routing.append(np.asarray(idx))
            slots += int(s)
    if matmul_dtype is None:
        _STATE.update(ref_routing=np.concatenate(routing, axis=1),
                      held_slots=slots)
    B, S = calib.shape
    n_tok = B * (S - 1)
    n_act = n_match * B * S * conf["hidden_size"]
    return [(sums[i, 0] / n_tok, sums[i, 1] / n_act) for i in range(2)]


def program_routing(cfg, params, calib):
    """The experts the program's float32 forward chooses in each MoE layer,
    (L_moe, B, S, k), computed by the program's own block and router at the
    configuration's matmul precision, in blocks of calibration rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import layers as L
    from repro.models import model as M

    def fn(params, toks):
        pos = jnp.arange(toks.shape[1])
        h = M.embed_tokens(params, cfg, toks, pos)

        def lead(h, pl):
            return M._dense_body(pl, cfg, h, pos)[0], None

        def body(h, pl):
            a_in = L.apply_norm(h, pl["ln1"], cfg.norm, cfg.norm_eps)
            a, _ = L.mla_attention(pl["attn"], cfg, a_in, pos)
            m_in = L.apply_norm(h + a, pl["ln2"], cfg.norm, cfg.norm_eps)
            _, idx = L.moe_route(pl["moe"], cfg, m_in)
            return M._dense_body(pl, cfg, h, pos)[0], idx
        h, _ = jax.lax.scan(lead, h, params["dense_blocks"])
        return jax.lax.scan(body, h, params["blocks"])[1]

    f = jax.jit(fn)
    return np.concatenate([np.asarray(f(params, calib[r:r + ROW_BLOCK]))
                           for r in range(0, calib.shape[0], ROW_BLOCK)],
                          axis=1)


def flip_share(a, b) -> float:
    """Share of token-layer pairs whose chosen expert sets differ."""
    import numpy as np
    return float(np.mean(np.any(np.sort(a, -1) != np.sort(b, -1), axis=-1)))


def run(ctx) -> harness.JobResult:
    from repro import obs

    _STATE.clear()
    _BASE.make_inputs = make_inputs
    _BASE.reference_terms = reference_terms
    _BASE.LIMITS = LIMITS
    _BASE.counts = types.SimpleNamespace(forward_flops=lambda *a: None)
    res = _BASE.run(ctx)

    conf, calib = ctx.config, _STATE["calib"]
    B, S = calib.shape
    res.layer["flops_per_proposal"] = counts_mla_moe.forward_flops(
        conf, B, S, _STATE["held_slots"])
    # traces of the searches' programs (the replay below traces its own)
    paths = {name: {p: obs.counter(name).value(path=p) for p in ps}
             for name, ps in (("attention_path_total", ("flash", "blocked")),
                              ("moe_dispatch_total", ("grouped", "capacity")))}
    flips = flip_share(program_routing(_STATE["cfg"], _STATE["params"], calib),
                       _STATE["ref_routing"])
    res.layer.update(routing_flip_share=flips, held_slots=_STATE["held_slots"],
                     **paths)
    res.artifacts.update(routing_flip_share=flips, **paths)
    harness.log(f"[search_mla_moe] routing flip share {flips:.6g}; held "
                f"slots {_STATE['held_slots']} (uniform "
                f"{counts_mla_moe.expected_held_slots(conf, B, S):.0f}); "
                f"{paths}")
    _STATE.clear()
    return res
