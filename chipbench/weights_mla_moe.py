"""Seeded random weights for a DeepSeek-V3 configuration (Moonlight), made on
the device in jitted calls, in the parameter layout the program reads.

The job checks the layout against the program's own ``init_params`` shapes
before use; the values are the benchmark's, drawn from ``--seed``
(``weights.make_weights`` does the same for OPT). The one trained quantity
that random draws cannot stand in for is each MoE layer's
``e_score_correction_bias``: ``noaux_tc`` training moves it until the
experts' loads are even, so it is set here the same way, over the
calibration batch. With random weights a bias balanced on another batch
does not carry over: the calibration batch has only 16 sequences, and each
sequence's tokens lean the same way (a balanced bias from a second 16 x 512
draw left loads of 27-204 slots where 96 were even, at a shrunk width on
the CPU). A seed's calibration batch would then send the held experts a
share of its slots of its own (5,000-44,000 against an even 24,576 in the
first chip runs), and so a different amount of work.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.weights import tree_shapes  # noqa: F401  (the job's check)


def shapes(m: dict) -> dict:
    """{path: shape} of the model from the configuration file."""
    D, H, V = m["hidden_size"], m["num_attention_heads"], m["vocab_size"]
    R, nope, rope = m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    dv, F = m["v_head_dim"], m["moe_intermediate_size"]
    E, held = m["router_experts"], m["n_routed_experts"]
    n_lead = m["first_k_dense_replace"]
    n_moe = m["num_hidden_layers"] - n_lead
    Fs = m["n_shared_experts"] * F
    out = {("embed", "tok"): (V, D), ("final_norm", "w"): (D,),
           ("lm_head",): (D, V)}
    for stack, L in (("dense_blocks", n_lead), ("blocks", n_moe)):
        out.update({
            (stack, "ln1", "w"): (L, D), (stack, "ln2", "w"): (L, D),
            (stack, "attn", "wq"): (L, D, H * (nope + rope)),
            (stack, "attn", "wkv_a"): (L, D, R + rope),
            (stack, "attn", "kv_norm", "w"): (L, R),
            (stack, "attn", "wkv_b"): (L, R, H * (nope + dv)),
            (stack, "attn", "wo"): (L, H * dv, D)})
    Fd = m["intermediate_size"]
    out.update({("dense_blocks", "mlp", "up"): (n_lead, D, Fd),
                ("dense_blocks", "mlp", "gate"): (n_lead, D, Fd),
                ("dense_blocks", "mlp", "down"): (n_lead, Fd, D),
                ("blocks", "moe", "router"): (n_moe, D, E),
                ("blocks", "moe", "router_bias"): (n_moe, E),
                ("blocks", "moe", "up"): (n_moe, held, D, F),
                ("blocks", "moe", "gate"): (n_moe, held, D, F),
                ("blocks", "moe", "down"): (n_moe, held, F, D),
                ("blocks", "moe", "shared", "up"): (n_moe, D, Fs),
                ("blocks", "moe", "shared", "gate"): (n_moe, D, Fs),
                ("blocks", "moe", "shared", "down"): (n_moe, Fs, D)})
    return out


BALANCE_STEPS = 400            # sign steps of the bias update
BALANCE_RATE = (1e-2, 1e-5)    # its first and last step, decaying geometrically


def _std(path, shape) -> float:
    """Fan-in scaled matrices (the router too), a small embedding, norm
    gains near one (the correction bias is replaced by the balanced one)."""
    if len(shape) >= 2 and path[0] != "embed":
        return float(shape[-2]) ** -0.5
    return 0.02


def balance_router_bias(params, m: dict, tokens):
    """Each MoE layer's correction bias (n_moe, E), set as ``noaux_tc``
    training sets it: the bias of an expert that the top-k of ``s + bias``
    chooses for more tokens than the even share steps down, of one chosen
    for fewer steps up, ``BALANCE_STEPS`` sign steps a layer over the tokens
    (B, S). Layer by layer through the plain reference, in float32 at
    HIGHEST; each layer's output (the held experts' part plus the shared
    experts, as on this chip) feeds the next layer's balance."""
    from chipbench import reference_mla_moe as ref

    eps, k = m["rms_norm_eps"], m["num_experts_per_tok"]
    E = m["router_experts"]
    target = tokens.size * k / E
    first, last = BALANCE_RATE
    rates = first * (last / first) ** (jnp.arange(BALANCE_STEPS)
                                       / (BALANCE_STEPS - 1))

    def attend(h, p):
        return h + ref.mla(ref._rms(h, p["ln1"]["w"], eps), p["attn"], m,
                           lambda x: x)

    def lead(h, p):
        a = attend(h, p)
        return a + ref.swiglu(ref._rms(a, p["ln2"]["w"], eps), p["mlp"],
                              lambda x: x), None

    def layer(h, p):
        a = attend(h, p)
        x = ref._rms(a, p["ln2"]["w"], eps)
        s = jax.nn.sigmoid(x @ p["moe"]["router"]).reshape(-1, E)

        def step(i, b):
            _, idx = jax.lax.top_k(s + b, k)
            load = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
            return b + rates[i] * jnp.sign(target - load)
        b = jax.lax.fori_loop(0, BALANCE_STEPS, step,
                              jnp.zeros((E,), jnp.float32))
        f, _ = ref.moe(x, dict(p["moe"], router_bias=b), m, lambda x: x,
                       m["experts_held_start"])
        return a + f, b

    def run(params, tokens):
        h = params["embed"]["tok"][tokens]
        h, _ = jax.lax.scan(lead, h, params["dense_blocks"])
        return jax.lax.scan(layer, h, params["blocks"])[1]

    with jax.default_matmul_precision("highest"):
        return jax.jit(run)(params, tokens)


def make_weights(m: dict, key, tokens):
    """The whole f32 model on the default device: random draws in one jitted
    call, then the correction biases balanced over ``tokens`` (B, S), the
    calibration batch."""
    table = shapes(m)

    def build(key):
        keys = jax.random.split(key, len(table))
        tree: dict = {}
        for k, (path, shape) in zip(keys, sorted(table.items())):
            x = jax.random.normal(k, shape, jnp.float32) * _std(path, shape)
            if path[-1] == "w":      # norm gains
                x = x + 1.0
            node = tree
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = x
        return tree

    params = jax.jit(build)(key)
    params["blocks"]["moe"]["router_bias"] = balance_router_bias(
        params, m, tokens)
    return params
