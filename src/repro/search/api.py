"""``repro.search.run`` — the ONE entry point to the discrete search.

The front door resolves the adapter from the model family, dispatches
hybrid block patterns to the two-phase composite automatically (an explicit
``adapter=`` runs one phase), and accepts the objective either on the
config (``SearchConfig(objective=...)``) or as the ``objective=`` keyword
(a registry name or an ``Objective`` instance — the keyword wins when both
are given).

The default configuration (population=1, islands=1, temperature=0, CE
objective, replicated calibration) reproduces the paper's single-chain hill
climb bit-for-bit through this entry point — pinned by
``tests/test_search_engine.py::test_front_door_matches_legacy_bitwise``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.search.engine import _run_engine

__all__ = ["run"]


def run(
    params_fp: dict,
    params_base: dict,
    cfg,
    qcfg,
    calib_tokens,
    scfg=None,
    *,
    objective=None,
    adapter=None,
    forward_kwargs: Optional[dict] = None,
):
    """Run the InvarExplore search; returns a ``core.search.SearchResult``.

    params_fp: original FP model (reference H₀ / KL targets / saliency).
    params_base: base-method-processed model — FFN weights in the
        continuous (dequantized) domain; every OTHER quantizable weight
        already fake-quantized (frozen during the search).
    scfg: ``core.search.SearchConfig`` (defaults reproduce the paper run).
    objective: registry name ("ce", "kl", "swd_actmatch", "saliency_ce") or
        an ``Objective`` instance; overrides ``scfg.objective``.
    adapter: explicit unit adapter. Without one, the adapter follows
        ``cfg.block_pattern`` and a hybrid pattern runs the two-phase
        Mamba → shared-FFN composite; with one, a single phase runs.
    """
    from repro.core.search import SearchConfig, make_adapter

    scfg = scfg if scfg is not None else SearchConfig()
    if objective is not None:
        scfg = dataclasses.replace(scfg, objective=objective)
    if cfg.block_pattern == "hybrid" and adapter is None:
        return _run_hybrid(params_fp, params_base, cfg, qcfg, calib_tokens,
                           scfg, forward_kwargs)
    return _run_engine(params_fp, params_base, cfg, qcfg, calib_tokens,
                       scfg, adapter=adapter or make_adapter(cfg),
                       forward_kwargs=forward_kwargs)


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


def _run_hybrid(params_fp, params_base, cfg, qcfg, calib_tokens, scfg,
                forward_kwargs):
    """Hybrid (Zamba2) InvarExplore: phase 1 hill-climbs the Mamba blocks'
    within-head permutations; phase 2 hill-climbs the shared FFN's P/S/R,
    starting from phase 1's quantized model. Phase 2 runs the REMAINDER
    ``steps - steps // 2`` so an odd budget is spent in full, and the
    returned histories/stats merge both phases."""
    from repro.core.search import MambaAdapter, SharedFFNAdapter

    n1 = scfg.steps // 2
    n2 = scfg.steps - n1
    r1 = _run_engine(params_fp, params_base, cfg, qcfg, calib_tokens,
                     dataclasses.replace(scfg, steps=n1),
                     adapter=MambaAdapter(cfg),
                     forward_kwargs=forward_kwargs)
    r2 = _run_engine(params_fp, r1.params_q, cfg, qcfg, calib_tokens,
                     dataclasses.replace(scfg, steps=n2),
                     adapter=SharedFFNAdapter(cfg),
                     forward_kwargs=forward_kwargs)
    r2.history = r1.history + r2.history
    r2.initial_loss = r1.initial_loss
    r2.accept_rate = (r1.accept_rate * n1 + r2.accept_rate * n2) \
        / max(scfg.steps, 1)
    if r1.island_histories and r2.island_histories:
        r2.island_histories = [h1 + h2 for h1, h2 in
                               zip(r1.island_histories, r2.island_histories)]
    r2.stats = _merge_phase_stats(r1.stats, r2.stats)
    return r2
