"""End-to-end PTQ pipeline: base method → (optional) InvarExplore search.

    params_q = quantize_model(params_fp, cfg, qcfg, method="awq",
                              calib_tokens=X, search=SearchConfig(...))

Contract between stages (DESIGN.md §1):
  * the base method produces FFN weights in the continuous (dequantized)
    domain — AWQ-scaled/clipped, GPTQ-compensated, OmniQuant-optimized, or
    plain θ₀ for RTN — and FINAL fake-quant weights for everything else
    (attention projections), which stay frozen during the search;
  * InvarExplore then hill-climbs fq(T(θ_base)) per unit (Algorithm 1);
  * without the search, the FFN weights are simply fake-quantized.

Spans: ``pipeline.quantize`` covers the whole call, ``pipeline.base`` the
base method and the freeze of the other weights (enqueued device work, not
synced); the search adds its own (``repro.search.engine``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional


from repro import obs
from repro.core.quant import QuantConfig, fake_quant
from repro.core.rtn import map_quantizable
from repro.core.awq import awq_process_dense
from repro.core.gptq import gptq_process_dense
from repro.core.omniquant import omniquant_process_dense
from repro.core.search import SearchConfig
from repro.search.api import run as run_invar_search
from repro.models.config import ModelConfig

__all__ = ["quantize_model", "PTQResult"]

# leaves the search transforms (kept continuous until the search quantizes
# them): dense/MoE FFNs plus the Mamba projections (within-head permutation
# targets — DESIGN.md §Arch-applicability)
_FFN_KEYS = ("up", "gate", "down", "w_z", "w_x", "out_proj")


def _is_ffn(path):
    return path[-1] in _FFN_KEYS


@dataclasses.dataclass
class PTQResult:
    params_q: dict
    method: str
    search: Optional[object]  # SearchResult when InvarExplore ran


def quantize_model(
    params_fp: dict,
    cfg: ModelConfig,
    qcfg: QuantConfig,
    method: str = "rtn",
    calib_tokens=None,
    search: Optional[SearchConfig] = None,
    forward_kwargs: Optional[dict] = None,
) -> PTQResult:
    if method != "rtn" and calib_tokens is None:
        raise ValueError(f"method {method!r} needs calib_tokens")
    with obs.trace_span("pipeline.quantize", method=method,
                        search=search is not None):
        with obs.trace_span("pipeline.base", method=method):
            params_base = _base_weights(params_fp, cfg, qcfg, method,
                                        calib_tokens)

        # 3) InvarExplore search or plain FFN fake-quant
        if search is not None:
            result = run_invar_search(params_fp, params_base, cfg, qcfg,
                                      calib_tokens, search,
                                      forward_kwargs=forward_kwargs)
            return PTQResult(result.params_q, method + "+invarexplore",
                             result)

        params_q = map_quantizable(
            params_base, lambda w, p: fake_quant(w, qcfg), only=_is_ffn)
        return PTQResult(params_q, method, None)


def _base_weights(params_fp, cfg, qcfg, method, calib_tokens):
    # 1) base-method processing (continuous-domain FFN weights)
    if method == "rtn":
        params_base = params_fp
    elif method == "awq":
        params_base = awq_process_dense(params_fp, cfg, calib_tokens, qcfg)
    elif method == "gptq":
        params_base = gptq_process_dense(params_fp, cfg, calib_tokens, qcfg)
    elif method == "omniquant":
        params_base, _ = omniquant_process_dense(params_fp, cfg, calib_tokens, qcfg)
    else:
        raise ValueError(f"unknown method {method!r}")

    # 2) freeze non-FFN quantizable weights at their fake-quant values
    return map_quantizable(
        params_base, lambda w, p: fake_quant(w, qcfg), only=lambda p: not _is_ffn(p))
