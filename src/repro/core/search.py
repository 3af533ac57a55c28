"""Adapters + front-end for the discrete search (paper Algorithm 1).

The search loop itself lives in ``repro.search.engine`` — a population ×
island annealed engine whose ``population=1, islands=1, temperature=0``
defaults reproduce the original single-chain hill climb bit-for-bit. This
module keeps what is model-family-specific: the *adapters* that expose a
family's transformable units (dense FFN, MoE expert, Mamba block, shared
hybrid FFN) plus the ``run_search`` entry point every caller already uses.

TPU-native execution model (DESIGN.md §3): the whole proposal evaluation —
transform → fake-quant → forward → loss — is ONE jitted function with the
unit index as a traced scalar, so a single XLA program serves every step.
Proposals come from counter-based ``jax.random`` keys: in a multi-host
setting every host replays the same proposal stream and the accept decision
derives from the (all-reduced) scalar loss, so hosts stay in lock-step with
zero extra communication.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import invariance as inv
from repro.core.quant import QuantConfig, fake_quant
from repro.models.config import ModelConfig

__all__ = ["SearchConfig", "SearchResult", "DenseFFNAdapter", "MoEAdapter",
           "MambaAdapter", "run_search"]


@dataclasses.dataclass(frozen=True)
class SearchConfig:
    steps: int = 2000
    seed: int = 0
    # registry name ("ce" Eqn. 23 | "kl" Algorithm-1 listing | "swd_actmatch"
    # | "saliency_ce") or a core.objective.Objective instance
    objective: Any = "ce"
    n_match_layers: int = 10       # activation-matching depth (paper Table 4)
    ce_weight: float = 10.0        # CE is 10x more important at step 0 (§4.1)
    proposal: inv.ProposalConfig = dataclasses.field(default_factory=inv.ProposalConfig)
    log_every: int = 200
    # --- engine scale-out (repro.search); defaults = legacy behavior ---
    population: int = 1            # candidates per step, one batched eval
    islands: int = 1               # independent chains (data-axis parallel)
    temperature: float = 0.0       # initial annealing T; 0 = greedy climb
    anneal: str = "geometric"      # schedule: constant | geometric | linear
    migrate_every: int = 50        # elite-migration cadence (0 = never)
    fused_kernel: bool = False     # kernels.transform_quant fused hot path
    mapped: bool = False           # one island per mesh shard (shard_map);
                                   # requires islands == global device count
    # --- v2 candidate-eval memory model + calibration sharding ---
    install: str = "unit"          # "unit": stack + K×unit dynamic-slice
                                   # install; "stack": v1 K full stacks
    tabu: int = 0                  # tried-point memory capacity (0 = off;
                                   # sequential lane only)
    shard_calib: bool = False      # per-island calibration slices
    measure_memory: bool = False   # sample jax.live_arrays() peaks into
                                   # stats["peak_live_bytes"] (slow; bench)


@dataclasses.dataclass
class SearchResult:
    params_q: dict                 # model with searched fake-quant weights installed
    transforms: inv.FFNTransform   # stacked per-unit transforms
    history: list                  # (step, loss, ce, mse, accepted)
    accept_rate: float
    final_loss: float
    initial_loss: float
    island_histories: Optional[list] = None  # per-island histories (engine)
    stats: Optional[dict] = None   # migrations / uphill accepts / proposals-per-sec


def _tree_slice(tree, i):
    return jax.tree.map(lambda x: x[i], tree)


def _tree_update(tree, i, new):
    return jax.tree.map(lambda x, n: x.at[i].set(n), tree, new)


# ---------------------------------------------------------------------------
# Adapters: expose a model family's transformable units to the search
# ---------------------------------------------------------------------------

class DenseFFNAdapter:
    """Dense decoder blocks: unit = one FFN (up[/gate]/down[,b_up,b_gate])."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.n_units = cfg.n_layers
        self.f_dim = cfg.d_ff

    def base_stack(self, params):
        mlp = params["blocks"]["mlp"]
        return {k: mlp[k] for k in ("up", "down", "gate", "b_up", "b_gate") if k in mlp}

    def transform_unit(self, base, t: inv.FFNTransform, u):
        b = _tree_slice(base, u)
        up, down, b_up, gate, b_gate = inv.apply_transform_ffn(
            t, b["up"], b["down"], b.get("b_up"), b.get("gate"), b.get("b_gate"))
        out = {"up": up, "down": down}
        if b_up is not None:
            out["b_up"] = b_up
        if gate is not None:
            out["gate"] = gate
        if b_gate is not None:
            out["b_gate"] = b_gate
        return out

    def quant_unit(self, unit, qcfg: QuantConfig):
        out = {}
        for k, v in unit.items():
            out[k] = fake_quant(v, qcfg) if v.ndim >= 2 else v
        return out

    def transform_quant_unit(self, base, t: inv.FFNTransform, u, qcfg: QuantConfig):
        """Fused hot path: (π, s, φ) + group fake-quant in ONE kernel pass per
        weight (``kernels.transform_quant``) instead of materializing the
        transformed fp32 weights and re-reading them to quantize. Biases are
        tiny and stay on the jnp path (they are never quantized)."""
        from repro.kernels import transform_quant
        b = _tree_slice(base, u)
        ident_s = jnp.ones_like(t.s)
        ident_phi = jnp.zeros_like(t.phi)
        out = {}
        for k, s_vec, phi_vec in (("up", t.s, t.phi),
                                  ("gate", ident_s, ident_phi)):
            if k in b:  # gate branch is permuted only (see apply_transform_ffn)
                out[k] = transform_quant(
                    b[k], t.pi, s_vec, phi_vec, bits=qcfg.bits,
                    group=qcfg.resolve_group(b[k].shape[0]), mode="up")[0]
        out["down"] = transform_quant(
            b["down"], t.pi, t.s, t.phi, bits=qcfg.bits,
            group=qcfg.resolve_group(b["down"].shape[0]), mode="down")[0]
        if "b_up" in b:
            out["b_up"] = (inv.apply_rotation_rows(b["b_up"], t.phi) * t.s)[t.pi]
        if "b_gate" in b:
            out["b_gate"] = b["b_gate"][t.pi]
        return out

    def install(self, params, fq_stack):
        params = dict(params)
        blocks = dict(params["blocks"])
        blocks["mlp"] = {**blocks["mlp"], **fq_stack}
        params["blocks"] = blocks
        return params


class MoEAdapter:
    """MoE stacks: every unit is one FFN of the expert width ``d_ff``, so the
    engine stacks one shape (under expert parallelism each shard searches
    its own experts).

    Units, in this order: each leading dense FFN split into column blocks of
    ``d_ff`` (``dense_d_ff / d_ff`` per layer); each shared expert (the
    shared MLP's ``d_ff`` column blocks) of every MoE layer; each held routed
    expert of every MoE layer. A unit's permutation stays inside its block,
    a restriction of the paper's search space for the dense and shared
    FFNs, which the paper would permute across their whole width."""

    KEYS = ("up", "gate", "down")

    def __init__(self, cfg: ModelConfig):
        from repro.models.model import n_lead_blocks
        self.cfg = cfg
        f = cfg.d_ff
        self.n_lead = n_lead_blocks(cfg)
        if self.n_lead and cfg.dense_d_ff % f:
            raise ValueError(f"dense FFN width {cfg.dense_d_ff} is not a "
                             f"multiple of the expert width {f}")
        self.lead_split = cfg.dense_d_ff // f if self.n_lead else 0
        self.n_moe = cfg.n_layers - self.n_lead
        self.n_shared = cfg.moe.n_shared_experts
        self.n_held = cfg.moe.held[1]
        self.sizes = (self.n_lead * self.lead_split, self.n_moe * self.n_shared,
                      self.n_moe * self.n_held)
        self.n_units = sum(self.sizes)
        self.f_dim = f

    def _split(self, mlp, n):
        """(L, D, n*F) / (L, n*F, D) leaves -> (L*n, D, F) / (L*n, F, D)."""
        f = self.f_dim
        out = {}
        for k in self.KEYS:
            if k not in mlp:
                continue
            x = mlp[k]
            if k == "down":
                out[k] = x.reshape((-1, f) + x.shape[2:])
            else:
                Lx, D = x.shape[:2]
                out[k] = x.reshape(Lx, D, n, f).transpose(0, 2, 1, 3).reshape(
                    Lx * n, D, f)
        return out

    def _join(self, units, n):
        """Inverse of ``_split``."""
        out = {}
        for k, x in units.items():
            if k == "down":
                out[k] = x.reshape((-1, n * self.f_dim) + x.shape[2:])
            else:
                D = x.shape[1]
                out[k] = x.reshape(-1, n, D, self.f_dim).transpose(
                    0, 2, 1, 3).reshape(-1, D, n * self.f_dim)
        return out

    def base_stack(self, params):
        moe = params["blocks"]["moe"]
        parts = []
        if self.n_lead:
            parts.append(self._split(params["dense_blocks"]["mlp"],
                                     self.lead_split))
        if self.n_shared:
            parts.append(self._split(moe["shared"], self.n_shared))
        # (L, E, ...) -> (L*E, ...) unit-major
        parts.append({k: moe[k].reshape((-1,) + moe[k].shape[2:])
                      for k in self.KEYS if k in moe})
        if len(parts) == 1:
            return parts[0]
        return {k: jnp.concatenate([p[k] for p in parts]) for k in parts[-1]}

    def transform_unit(self, base, t, u):
        b = _tree_slice(base, u)
        up, down, _, gate, _ = inv.apply_transform_ffn(
            t, b["up"], b["down"], None, b.get("gate"), None)
        out = {"up": up, "down": down}
        if gate is not None:
            out["gate"] = gate
        return out

    def quant_unit(self, unit, qcfg):
        return {k: fake_quant(v, qcfg) for k, v in unit.items()}

    # per-expert units carry the same up/down[/gate] layout as a dense FFN,
    # so the fused transform+fake-quant path applies unchanged
    transform_quant_unit = DenseFFNAdapter.transform_quant_unit

    def install(self, params, fq_stack):
        n_lead, n_shared, _ = self.sizes
        params = dict(params)
        blocks = dict(params["blocks"])
        moe = dict(blocks["moe"])

        def part(lo, hi):
            return {k: v[lo:hi] for k, v in fq_stack.items()}
        if self.n_lead:
            lead = dict(params["dense_blocks"])
            lead["mlp"] = {**lead["mlp"],
                           **self._join(part(0, n_lead), self.lead_split)}
            params["dense_blocks"] = lead
        if self.n_shared:
            moe["shared"] = {**moe["shared"], **self._join(
                part(n_lead, n_lead + n_shared), self.n_shared)}
        lo = n_lead + n_shared
        for k, v in fq_stack.items():
            v = v[lo:] if lo else v
            moe[k] = v.reshape((self.n_moe, self.n_held) + v.shape[1:])
        blocks["moe"] = moe
        params["blocks"] = blocks
        return params


class MambaAdapter:
    """Mamba2 blocks: unit = one block; permutation-only, block-structured
    within heads (exact invariance — DESIGN.md §Arch-applicability)."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        # hybrid stacks: only the mamba blocks are units of this adapter
        self.n_units = (cfg.hybrid_layout()[0] if cfg.block_pattern == "hybrid"
                        else cfg.n_layers)
        s = cfg.ssm
        self.di = s.d_inner(cfg.d_model)
        self.head_dim = s.head_dim
        self.f_dim = self.di

    def base_stack(self, params):
        ssm = params["blocks"]["ssm"]
        return {k: ssm[k] for k in ("w_z", "w_x", "conv_x", "conv_b_x",
                                    "norm_w", "out_proj")}

    def transform_unit(self, base, t: inv.FFNTransform, u):
        b = _tree_slice(base, u)
        pi = t.pi  # MUST be within-head block structured (self.propose)
        return {
            "w_z": b["w_z"][:, pi],
            "w_x": b["w_x"][:, pi],
            "conv_x": b["conv_x"][:, pi],
            "conv_b_x": b["conv_b_x"][pi],
            "norm_w": b["norm_w"][pi],
            "out_proj": b["out_proj"][pi, :],
        }

    def quant_unit(self, unit, qcfg):
        out = dict(unit)
        out["w_z"] = fake_quant(unit["w_z"], qcfg)
        out["w_x"] = fake_quant(unit["w_x"], qcfg)
        out["out_proj"] = fake_quant(unit["out_proj"], qcfg)
        return out

    def install(self, params, fq_stack):
        params = dict(params)
        blocks = dict(params["blocks"])
        blocks["ssm"] = {**blocks["ssm"], **fq_stack}
        params["blocks"] = blocks
        return params

    def propose(self, key, t: inv.FFNTransform, pcfg: inv.ProposalConfig) -> inv.FFNTransform:
        """Within-head partial shuffle: pick one head, shuffle a fraction."""
        hd = self.head_dim
        n_heads = self.di // hd
        n_move = max(2, int(round(pcfg.subset_frac * hd)))
        k1, k2, k3 = jax.random.split(key, 3)
        head = jax.random.randint(k1, (), 0, n_heads)
        pos_in_head = jax.random.permutation(k2, hd)[:n_move] + head * hd
        order = jax.random.permutation(k3, n_move)
        vals = t.pi[pos_in_head]
        pi = t.pi.at[pos_in_head].set(vals[order])
        return inv.FFNTransform(pi=pi, s=t.s, phi=t.phi)


class SharedFFNAdapter:
    """Hybrid (Zamba2): the ONE shared attention block's FFN as a single unit
    (its weights are shared across all applications, so one transform covers
    every application exactly)."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.n_units = 1
        self.f_dim = cfg.d_ff

    def base_stack(self, params):
        mlp = params["shared"]["mlp"]
        keep = {k: mlp[k] for k in ("up", "down", "gate", "b_up", "b_gate") if k in mlp}
        return jax.tree.map(lambda x: x[None], keep)  # stack dim of 1

    def transform_unit(self, base, t: inv.FFNTransform, u):
        b = _tree_slice(base, u)
        up, down, b_up, gate, b_gate = inv.apply_transform_ffn(
            t, b["up"], b["down"], b.get("b_up"), b.get("gate"), b.get("b_gate"))
        out = {"up": up, "down": down}
        if b_up is not None:
            out["b_up"] = b_up
        if gate is not None:
            out["gate"] = gate
        if b_gate is not None:
            out["b_gate"] = b_gate
        return out

    quant_unit = DenseFFNAdapter.quant_unit

    def install(self, params, fq_stack):
        params = dict(params)
        shared = dict(params["shared"])
        shared["mlp"] = {**shared["mlp"],
                         **jax.tree.map(lambda x: x[0], fq_stack)}
        params["shared"] = shared
        return params


def make_adapter(cfg: ModelConfig, phase: str = None):
    if cfg.block_pattern in ("dense",):
        return DenseFFNAdapter(cfg)
    if cfg.block_pattern == "moe":
        return MoEAdapter(cfg)
    if cfg.block_pattern == "ssm":
        return MambaAdapter(cfg)
    if cfg.block_pattern == "hybrid":
        # two-phase composite: "mamba" (within-head P, exact) then "shared"
        # (full P/S/R on the shared block's FFN) — see run_search_hybrid.
        if phase == "shared":
            return SharedFFNAdapter(cfg)
        return MambaAdapter(cfg)
    raise NotImplementedError(f"no search adapter for pattern {cfg.block_pattern!r}")


def _merge_phase_stats(s1, s2):
    """Sum the counters of two engine stats dicts; rates recombine so the
    merged proposals_per_sec reflects TOTAL proposals over TOTAL wall time."""
    if s1 is None or s2 is None:
        return s2 or s1
    out = dict(s2)
    for k in ("migrations", "uphill_accepts", "proposals"):
        out[k] = s1.get(k, 0) + s2.get(k, 0)
    t1 = s1.get("proposals", 0) / max(s1.get("proposals_per_sec", 0.0), 1e-9)
    t2 = s2.get("proposals", 0) / max(s2.get("proposals_per_sec", 0.0), 1e-9)
    out["proposals_per_sec"] = out["proposals"] / max(t1 + t2, 1e-9)
    out["fused"] = s1.get("fused", False) or s2.get("fused", False)
    return out


def run_search_hybrid(params_fp, params_base, cfg, qcfg, calib_tokens,
                      scfg: SearchConfig = SearchConfig(), forward_kwargs=None):
    """Deprecated: ``repro.search.run`` dispatches hybrid block patterns to
    the two-phase Mamba → shared-FFN composite automatically."""
    warnings.warn(
        "core.search.run_search_hybrid is deprecated; use "
        "repro.search.run(...) (hybrid configs two-phase automatically)",
        DeprecationWarning, stacklevel=2)
    from repro.search import run
    return run(params_fp, params_base, cfg, qcfg, calib_tokens, scfg,
               forward_kwargs=forward_kwargs, hybrid=True)


# ---------------------------------------------------------------------------
# The search entry point (Algorithm 1) — deprecated shim over repro.search
# ---------------------------------------------------------------------------

def run_search(
    params_fp: dict,
    params_base: dict,
    cfg: ModelConfig,
    qcfg: QuantConfig,
    calib_tokens: jnp.ndarray,
    scfg: SearchConfig = SearchConfig(),
    adapter=None,
    forward_kwargs: Optional[dict] = None,
) -> SearchResult:
    """Deprecated: call ``repro.search.run`` (same signature, one front door
    for single-phase, hybrid and population/island configurations).

    This shim preserves the legacy single-phase semantics exactly — on a
    hybrid config it searches only the Mamba blocks, as before (pass the
    config to ``repro.search.run`` without an adapter to get the two-phase
    composite instead). The default ``SearchConfig`` (population=1,
    islands=1, temperature=0) reproduces the original single-chain hill
    climb bit-for-bit.
    """
    warnings.warn(
        "core.search.run_search is deprecated; use repro.search.run(...)",
        DeprecationWarning, stacklevel=2)
    from repro.search import run
    return run(params_fp, params_base, cfg, qcfg, calib_tokens, scfg,
               adapter=adapter, forward_kwargs=forward_kwargs, hybrid=False)
