"""Sharding rules: PartitionSpec trees over the ("data", "model") mesh.

One ``ShardingRules`` object holds the mesh geometry plus the model config and
answers every "where does this leaf live?" question:

  - ``param_specs``     — megatron-style tensor parallelism over "model":
                          attention QKV / MLP up+gate column-sharded, WO / MLP
                          down row-sharded, MoE experts sharded on the expert
                          dim, vocab-sharded embeddings. Head-aware: an arch
                          whose (kv-)head count does not divide the model axis
                          replicates those weights instead (internvl2's 14
                          heads on a model=16 axis).
  - ``opt_state_specs`` — param specs for m/v, plus ZeRO-1: the first free
                          (replicated) dim that divides the data axis is
                          sharded over "data".
  - ``cache_specs``     — decode KV caches: batch-sharded over "data" when the
                          batch divides it, otherwise sequence-sharded (the
                          long_500k batch-1 cell) or head-sharded
                          (``long_decode_shard="heads"``).
  - ``data_spec``       — token batches over "data", replicated fallback for
                          unshardable batch sizes.

Every proposed axis is divisibility-gated: a dim that does not divide its mesh
axis falls back to ``None`` (replication) rather than producing an invalid
partitioning. QTensor leaves get component-wise specs (packed / scale / zero
each re-gated against their own row counts — packed rows are K/vals_per_word,
scale rows K/group, and either may lose divisibility the logical K had).
"""
from __future__ import annotations

from jax.sharding import NamedSharding, PartitionSpec as P

import jax

from repro.core.quant import QTensor

__all__ = ["ShardingRules", "param_specs", "opt_state_specs", "cache_specs",
           "data_spec", "to_shardings"]


class ShardingRules:
    """Mesh geometry + model config -> sharding decisions.

    Works with a real ``jax.sharding.Mesh`` or anything exposing ``.shape``
    (axis name -> size mapping). Batch-parallel dims shard over every
    data-like axis present ("pod" and "data" on the multi-pod mesh).
    """

    def __init__(self, mesh, cfg, *, zero1: bool = False,
                 long_decode_shard: str = "seq"):
        if long_decode_shard not in ("seq", "heads"):
            raise ValueError(f"long_decode_shard must be 'seq' or 'heads', "
                             f"got {long_decode_shard!r}")
        shape = dict(mesh.shape)
        self.mesh = mesh
        self.cfg = cfg
        self.zero1 = zero1
        self.long_decode_shard = long_decode_shard
        self.model = int(shape.get("model", 1))
        self.has_model = "model" in shape and self.model > 1
        self.batch_axes = tuple(a for a in ("pod", "data") if a in shape)
        self.data = 1
        for a in self.batch_axes:
            self.data *= int(shape[a])
        self.has_data = self.data > 1

    @property
    def batch_entry(self):
        """The PartitionSpec entry for a batch-parallel dim."""
        if not self.batch_axes:
            return None
        return self.batch_axes[0] if len(self.batch_axes) == 1 else self.batch_axes

    # -- head gates: sharding a head-structured dim is only coherent when the
    #    head count itself divides the axis (else a head would straddle shards)
    @property
    def heads_ok(self) -> bool:
        return self.has_model and self.cfg.n_heads > 0 \
            and self.cfg.n_heads % self.model == 0

    @property
    def kv_heads_ok(self) -> bool:
        return self.has_model and self.cfg.n_kv_heads > 0 \
            and self.cfg.n_kv_heads % self.model == 0

    @property
    def ssm_heads_ok(self) -> bool:
        if not (self.has_model and self.cfg.ssm is not None):
            return False
        return self.cfg.ssm.n_heads(self.cfg.d_model) % self.model == 0


# ---------------------------------------------------------------------------
# Tree walking (dicts / tuples / QTensor nodes, paths preserved)
# ---------------------------------------------------------------------------

def _map_tree(fn, tree, path=()):
    if isinstance(tree, QTensor):
        return fn(path, tree)
    if isinstance(tree, dict):
        return {k: _map_tree(fn, v, path + (k,)) for k, v in tree.items()}
    if isinstance(tree, (tuple, list)):
        return tuple(_map_tree(fn, v, path + (i,)) for i, v in enumerate(tree))
    if tree is None:
        return None
    return fn(path, tree)


# ---------------------------------------------------------------------------
# Per-leaf tensor-parallel proposals
# ---------------------------------------------------------------------------

_MLP_COL = ("up", "gate", "b_up", "b_gate")   # output (N) dim sharded
_ATTN_Q = ("wq", "bq", "wkv_b")               # q-head-structured outputs
                                               # (MLA's W_kvb: k_nope, v)
_ATTN_KV = ("wk", "wv", "bk", "bv")            # kv-head-structured outputs


def _propose(rules: ShardingRules, path) -> int | None:
    """Negative trailing-dim index to shard over "model", or None."""
    if not rules.has_model:
        return None
    names = tuple(str(k) for k in path)
    leaf = names[-1]
    parent = names[-2] if len(names) > 1 else ""
    if parent in ("attn", "xattn"):
        if leaf in _ATTN_Q and rules.heads_ok:
            return -1
        if leaf in _ATTN_KV and rules.kv_heads_ok:
            return -1
        if leaf == "wo" and rules.heads_ok:
            return -2
        return None
    if parent in ("mlp", "shared"):   # MoE shared experts: one MLP
        if leaf in _MLP_COL:
            return -1
        if leaf == "down":
            return -2
        return None
    if parent == "moe":
        if leaf in ("up", "down", "gate"):
            return -3  # expert dim of (..., E, D, F)
        return None  # router (and its bias) replicated: tiny, feeds top_k
    if parent == "ssm":
        if leaf in ("w_z", "w_x") and rules.ssm_heads_ok:
            return -1
        if leaf == "out_proj" and rules.ssm_heads_ok:
            return -2
        return None
    if parent == "embed":
        return -2 if leaf == "tok" else None  # vocab-sharded embedding
    if leaf == "lm_head":
        return -1  # (D, V): vocab-sharded output projection
    return None


def _gated(rules: ShardingRules, ndim: int, shape, pos) -> P:
    """Full-rank spec with "model" at ``pos`` iff that dim divides the axis."""
    entries = [None] * ndim
    if pos is not None and -pos <= ndim and shape[pos] % rules.model == 0:
        entries[pos] = "model"
    return P(*entries)


def _leaf_spec(rules: ShardingRules, path, shape) -> P:
    return _gated(rules, len(shape), shape, _propose(rules, path))


def _qtensor_specs(rules: ShardingRules, path, qt: QTensor) -> QTensor:
    """Component specs for a packed QTensor leaf.

    The logical (K, N) weight decides the trailing axis; each component then
    re-gates on its OWN dim size at that position — packed rows (K/vpw) and
    scale rows (K/group) may not stay divisible even when K was.
    """
    pos = _propose(rules, path)
    if pos is not None and not (-pos <= len(qt.shape)
                                and qt.shape[pos] % rules.model == 0):
        pos = None  # logical weight itself unshardable -> replicate everywhere

    def comp(arr) -> P:
        return _gated(rules, len(arr.shape), arr.shape, pos)

    return QTensor(packed=comp(qt.packed), scale=comp(qt.scale),
                   zero=comp(qt.zero), bits=qt.bits, group_size=qt.group_size,
                   shape=qt.shape)


# ---------------------------------------------------------------------------
# Public spec builders
# ---------------------------------------------------------------------------

def param_specs(rules: ShardingRules, structs):
    """Spec tree mirroring a param (or packed-QTensor-param) struct tree."""
    def leaf(path, node):
        if isinstance(node, QTensor):
            return _qtensor_specs(rules, path, node)
        return _leaf_spec(rules, path, node.shape)
    return _map_tree(leaf, structs)


def _zero1_spec(rules: ShardingRules, spec: P, shape) -> P:
    """Shard the first free dim that divides the data axis over "data"."""
    if not rules.has_data:
        return spec
    entries = list(spec)
    for i, (ax, dim) in enumerate(zip(entries, shape)):
        if ax is None and dim % rules.data == 0:
            entries[i] = rules.batch_entry
            return P(*entries)
    return spec


def opt_state_specs(rules: ShardingRules, structs):
    """Specs for the AdamW state over PARAM structs: {"m", "v", "step"}.

    m/v mirror the param specs; with ``zero1=True`` each state leaf
    additionally shards one free axis over "data" (optimizer-state ZeRO-1 —
    params/grads stay data-replicated, only m/v split)."""
    def leaf(path, node):
        if isinstance(node, QTensor):
            return _qtensor_specs(rules, path, node)
        spec = _leaf_spec(rules, path, node.shape)
        if rules.zero1:
            spec = _zero1_spec(rules, spec, node.shape)
        return spec
    mv = _map_tree(leaf, structs)
    return {"m": mv, "v": mv, "step": P()}


def cache_specs(rules: ShardingRules, cfg, batch: int):
    """Decode-cache specs for ``init_cache(cfg, batch, max_len)`` trees.

    batch divides the data axis  -> batch-sharded (decode_32k: 128 over 16);
    otherwise                    -> sequence-sharded over "data" (long_500k:
                                    batch 1), or head-sharded when
                                    ``long_decode_shard="heads"``.
    KV head dims shard over "model" only when the kv-head count divides it.
    """
    batch_ok = rules.has_data and batch % rules.data == 0
    b_ax = rules.batch_entry if batch_ok else None
    seq_ax = None
    if not batch_ok and rules.has_data and rules.long_decode_shard == "seq":
        seq_ax = rules.batch_entry
    h_ax = "model" if rules.kv_heads_ok else None

    def dense_cache():
        if cfg.mla is not None:   # latent + shared roped key: no head axis
            lat = P(None, b_ax, seq_ax, None)
            return {"c_kv": lat, "k_pe": lat}
        kv = P(None, b_ax, seq_ax, h_ax, None)
        c = {"k": kv, "v": kv}
        if cfg.kv_cache_dtype == "int8":
            sc = P(None, b_ax, seq_ax, h_ax)
            c["k_scale"] = sc
            c["v_scale"] = sc
        return c

    def ssm_cache():
        sh_ax = "model" if rules.ssm_heads_ok else None
        return {
            "state": P(None, b_ax, sh_ax, None, None),   # (L, B, H, hd, N)
            "conv": {"x": P(None, b_ax, None, sh_ax),    # (L, B, W, di)
                     "B": P(None, b_ax, None, None),
                     "C": P(None, b_ax, None, None)},
        }

    if cfg.block_pattern in ("dense", "moe"):
        return dense_cache()
    if cfg.block_pattern == "ssm":
        return ssm_cache()
    if cfg.block_pattern == "hybrid":
        return {"ssm": ssm_cache(), "attn": dense_cache()}
    raise ValueError(cfg.block_pattern)


def data_spec(rules: ShardingRules, batch: int) -> P:
    """(B, S) token batches: batch over "data" when divisible, else replicate
    (an unshardable batch is a correctness fallback, not an error)."""
    ok = rules.has_data and batch % rules.data == 0
    return P(rules.batch_entry if ok else None, None)


def to_shardings(mesh, tree):
    """Map every PartitionSpec leaf to a NamedSharding on ``mesh`` (QTensor
    spec nodes flatten to their component specs)."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s) if isinstance(s, P) else s,
        tree, is_leaf=lambda x: isinstance(x, P))
