"""The benchmark's operation and byte counts against independent counts."""
import jax
import jax.numpy as jnp

from chipbench import counts

M = {"num_hidden_layers": 2, "hidden_size": 64, "ffn_dim": 256,
     "num_attention_heads": 4, "vocab_size": 512, "torch_dtype": "float32"}


def _dense_matmuls(x, w):
    """Every projection of every layer and the LM head, as the forward runs
    them (no attention)."""
    for layer in w["layers"]:
        q = x @ layer["wq"]
        k = x @ layer["wk"]
        v = x @ layer["wv"]
        o = (q + k + v) @ layer["wo"]
        x = o + jax.nn.relu(o @ layer["up"]) @ layer["down"]
    return x @ w["head"]


def test_forward_matmul_flops_match_xla_cost_analysis():
    B, S, D, F, V = 2, 16, M["hidden_size"], M["ffn_dim"], M["vocab_size"]
    s = jax.ShapeDtypeStruct
    layer = {"wq": s((D, D), jnp.float32), "wk": s((D, D), jnp.float32),
             "wv": s((D, D), jnp.float32), "wo": s((D, D), jnp.float32),
             "up": s((D, F), jnp.float32), "down": s((F, D), jnp.float32)}
    w = {"layers": [layer] * M["num_hidden_layers"],
         "head": s((D, V), jnp.float32)}
    cost = jax.jit(_dense_matmuls).lower(
        s((B * S, D), jnp.float32), w).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    dense = 2 * counts.matmul_params(M) * B * S
    # XLA also counts the elementwise adds and the relu: a few per element
    assert dense <= cost["flops"] <= dense * 1.02


def test_forward_attention_counts_the_causal_half():
    B, S = 3, 10
    pairs = sum(1 for q in range(S) for k in range(S) if k <= q)
    attn = counts.forward_flops(M, B, S) - 2 * counts.matmul_params(M) * B * S
    per_pair = 2 * 2 * M["hidden_size"]          # QK^T and PV over all heads
    assert attn == M["num_hidden_layers"] * B * pairs * per_pair


def test_decode_step_flops():
    lens = [1, 17, 300]
    f = counts.decode_step_flops(M, lens)
    L, D = M["num_hidden_layers"], M["hidden_size"]
    assert f == 2 * counts.matmul_params(M) * 3 + L * 4 * D * sum(lens)
    assert counts.decode_step_flops(M, []) == 0


def test_paged_decode_bytes_count_live_pages():
    # contexts of 1, 16, 17 and 40 tokens hold 1, 1, 2 and 3 pages of 16
    lens, psz = [1, 16, 17, 40], 16
    L, D, H = M["num_hidden_layers"], M["hidden_size"], 4
    pages = 1 + 1 + 2 + 3
    kv = pages * psz * H * (D // H) * 4 * 2
    qo = len(lens) * D * 4 * 2
    assert counts.paged_decode_bytes(M, lens, psz) == L * (kv + qo)
    gqa = dict(M, num_key_value_heads=1, torch_dtype="bfloat16")
    assert counts.paged_decode_bytes(gqa, lens, psz) == \
        L * (pages * psz * (D // H) * 2 * 2 + len(lens) * D * (2 + 4))
