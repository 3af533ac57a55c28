"""Plain reference of what the timed paths compute, independent of the program.

OPT (Zhang et al. 2022) as the configuration states it: learned positions,
pre-LayerNorm blocks with biases, multi-head causal attention, ReLU FFN, final
LayerNorm and an untied LM head. Weights are RTN-quantized asymmetrically in
groups along the input axis (the paper's Eqns. 1-4), and the search's FFN
transform is the paper's permutation / scaling / Givens rotation on the hidden
axis (Eqns. 21-22). Nothing here imports the program.

Everything is float32; callers run it under ``Precision.HIGHEST``. The
configuration's matmuls take their inputs in bfloat16 (JAX's default
precision for float32 on the TPU), so the control is this reference with
every matmul's inputs rounded to float8 e4m3 (``matmul_dtype``), the
precision below bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG = -1e30
QUANT_LEAVES = ("wq", "wk", "wv", "wo", "up", "down")


# ---------------------------------------------------------------------------
# Quantization and the FFN transform
# ---------------------------------------------------------------------------

def rtn(w, bits: int, group: int):
    """Asymmetric round-to-nearest fake quantization of ``w`` (K, N) in
    groups of ``group`` rows: s = (max - min) / (2^b - 1), z = round(-min /
    s), q = clip(round(w / s) + z), w' = s (q - z)."""
    K, N = w.shape
    qmax = (1 << bits) - 1
    g = w.reshape(K // group, group, N)
    lo, hi = g.min(axis=1, keepdims=True), g.max(axis=1, keepdims=True)
    s = jnp.maximum((hi - lo) / qmax, 1e-8)
    z = jnp.clip(jnp.round(-lo / s), 0, qmax)
    q = jnp.clip(jnp.round(g / s) + z, 0, qmax)
    return ((q - z) * s).reshape(K, N)


def _givens(x, phi, axis):
    """Rotate consecutive pairs (2i, 2i+1) along ``axis`` by angles phi:
    (a, b) -> (a cos - b sin, a sin + b cos)."""
    x = jnp.moveaxis(x, axis, 0)
    a, b = x[0::2], x[1::2]
    shape = (-1,) + (1,) * (x.ndim - 1)
    c, s = jnp.cos(phi).reshape(shape), jnp.sin(phi).reshape(shape)
    out = jnp.stack([c * a - s * b, s * a + c * b], axis=1).reshape(x.shape)
    return jnp.moveaxis(out, 0, axis)


def transform_ffn(up, b_up, down, pi, s, phi):
    """W_up' = P S R W_up and W_down' = W_down R^T S^-1 P^T, written for
    ``x @ up`` (D, F) and ``h @ down`` (F, D): rotate, scale, permute the F
    axis (the bias moves with up's columns)."""
    up = (_givens(up, phi, 1) * s[None, :])[:, pi]
    b_up = (_givens(b_up, phi, 0) * s)[pi]
    down = (_givens(down, phi, 0) / s[:, None])[pi, :]
    return up, b_up, down


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _ln(x, w, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * w + b


def _rounder(matmul_dtype):
    if matmul_dtype is None:
        return lambda x: x
    return lambda x: x.astype(matmul_dtype).astype(jnp.float32)


def _block(h, p, n_heads: int, r):
    """One pre-LN OPT block over h (B, S, D); ``r`` rounds matmul inputs."""
    B, S, D = h.shape
    dh = D // n_heads
    a = r(_ln(h, p["ln1"]["w"], p["ln1"]["b"]))
    at = p["attn"]

    def heads(x):
        return x.reshape(B, S, n_heads, dh)
    q = heads(a @ r(at["wq"]) + at["bq"])
    k = heads(a @ r(at["wk"]) + at["bk"])
    v = heads(a @ r(at["wv"]) + at["bv"])
    sc = jnp.einsum("bqhd,bkhd->bhqk", r(q), r(k)) * dh ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    sc = jnp.where(causal, sc, NEG)
    pr = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", r(pr), r(v)).reshape(B, S, D)
    h = h + (r(o) @ r(at["wo"]) + at["bo"])
    m = r(_ln(h, p["ln2"]["w"], p["ln2"]["b"]))
    f = jax.nn.relu(m @ r(p["mlp"]["up"]) + p["mlp"]["b_up"])
    return h + (r(f) @ r(p["mlp"]["down"]) + p["mlp"]["b_down"])


def forward(params, tokens, n_heads: int, *, matmul_dtype=None,
            layer_fn=None, collect_hidden: bool = False):
    """Logits (B, S, V) of tokens (B, S).

    ``layer_fn(i, block_params) -> block_params`` rewrites layer i's weights
    before use (quantization, the search's transform), so that only one
    layer's rewritten weights are held at a time. With ``collect_hidden`` the
    block outputs (L, B, S, D) come back too. ``matmul_dtype`` rounds every
    matmul's inputs (the control).
    """
    B, S = tokens.shape
    r = _rounder(matmul_dtype)
    h = params["embed"]["tok"][tokens] + params["embed"]["pos"][
        jnp.arange(S)][None]
    L = params["blocks"]["ln1"]["w"].shape[0]

    def body(h, xs):
        i, p = xs
        if layer_fn is not None:
            p = layer_fn(i, p)
        h = _block(h, p, n_heads, r)
        return h, (h if collect_hidden else None)

    h, hidden = jax.lax.scan(body, h, (jnp.arange(L), params["blocks"]))
    h = _ln(h, params["final_norm"]["w"], params["final_norm"]["b"])
    logits = r(h) @ r(params["lm_head"])
    return (logits, hidden) if collect_hidden else logits


def quantize_layer(bits: int, group: int):
    """``layer_fn`` that RTN-quantizes a layer's projection weights."""
    def fn(i, p):
        p = jax.tree.map(lambda x: x, p)
        for part in ("attn", "mlp"):
            p[part] = {k: (rtn(v, bits, group) if k in QUANT_LEAVES else v)
                       for k, v in p[part].items()}
        return p
    return fn


def searched_layer(bits: int, group: int, pi, s, phi):
    """``layer_fn`` of the searched model: attention RTN-quantized, the FFN
    transformed by layer i's (pi, s, phi) and then RTN-quantized (the FFN
    biases stay in full precision)."""
    def fn(i, p):
        p = jax.tree.map(lambda x: x, p)
        p["attn"] = {k: (rtn(v, bits, group) if k in QUANT_LEAVES else v)
                     for k, v in p["attn"].items()}
        mlp = dict(p["mlp"])
        up, b_up, down = transform_ffn(mlp["up"], mlp["b_up"], mlp["down"],
                                       pi[i], s[i], phi[i])
        mlp.update(up=rtn(up, bits, group), b_up=b_up,
                   down=rtn(down, bits, group))
        p["mlp"] = mlp
        return p
    return fn


def token_nll(logits, tokens, vocab: int):
    """Per-position next-token NLL (B, S-1) over the first ``vocab`` ids."""
    lg = logits[:, :-1, :vocab]
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
