"""Seeded random weights for an OPT configuration, made on the device in one
jitted call, in the parameter layout the program reads.

The layout (stacked ``blocks`` scanned by the program, ``x @ W`` weights as
``(in, out)``) is checked against the program's own ``init_params`` shapes by
the job before use; the values are the benchmark's, drawn from ``--seed``, so
the reference reads weights the program never made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def opt_shapes(m: dict) -> dict:
    """{path: shape} of an OPT model from the configuration's ``model``."""
    L, D, F = m["num_hidden_layers"], m["hidden_size"], m["ffn_dim"]
    V, P = m["vocab_size"], m["max_position_embeddings"]
    return {
        ("embed", "tok"): (V, D),
        ("embed", "pos"): (P, D),
        ("blocks", "ln1", "w"): (L, D), ("blocks", "ln1", "b"): (L, D),
        ("blocks", "attn", "wq"): (L, D, D), ("blocks", "attn", "bq"): (L, D),
        ("blocks", "attn", "wk"): (L, D, D), ("blocks", "attn", "bk"): (L, D),
        ("blocks", "attn", "wv"): (L, D, D), ("blocks", "attn", "bv"): (L, D),
        ("blocks", "attn", "wo"): (L, D, D), ("blocks", "attn", "bo"): (L, D),
        ("blocks", "ln2", "w"): (L, D), ("blocks", "ln2", "b"): (L, D),
        ("blocks", "mlp", "up"): (L, D, F), ("blocks", "mlp", "b_up"): (L, F),
        ("blocks", "mlp", "down"): (L, F, D),
        ("blocks", "mlp", "b_down"): (L, D),
        ("final_norm", "w"): (D,), ("final_norm", "b"): (D,),
        ("lm_head",): (D, V),
    }


def _std(path, shape) -> float:
    """Scale of each leaf: fan-in scaled matrices, small embeddings and
    biases, norm gains near one."""
    name = path[-1]
    if name in ("wq", "wk", "wv", "wo", "up", "down") or path == ("lm_head",):
        return float(shape[-2]) ** -0.5
    return 0.02


def make_weights(m: dict, key):
    """The whole f32 model in one jitted call on the default device."""
    shapes = opt_shapes(m)

    def build(key):
        keys = jax.random.split(key, len(shapes))
        tree: dict = {}
        for k, (path, shape) in zip(keys, sorted(shapes.items())):
            x = jax.random.normal(k, shape, jnp.float32) * _std(path, shape)
            if path[-1] == "w":      # norm gains
                x = x + 1.0
            node = tree
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = x
        return tree

    return jax.jit(build)(key)


def tree_shapes(tree) -> dict:
    """{path: shape} of a nested dict of arrays (or shape structs)."""
    out = {}

    def walk(node, path):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, path + (k,))
        else:
            out[path] = tuple(node.shape)
    walk(tree, ())
    return out
