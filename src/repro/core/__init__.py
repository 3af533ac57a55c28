"""InvarExplore core: quantization, invariant transforms, discrete search,
and the RTN/GPTQ/AWQ/OmniQuant baselines it composes with.

search/pipeline are imported lazily (they depend on repro.models, which
depends on repro.core.quant — a direct import here would be circular).
"""
from repro.core.quant import QuantConfig, QTensor, fake_quant, quantize_tensor, bits_per_param
from repro.core.invariance import (
    FFNTransform, identity_transform, apply_transform_ffn, propose, ProposalConfig,
)

__all__ = [
    "QuantConfig", "QTensor", "fake_quant", "quantize_tensor", "bits_per_param",
    "FFNTransform", "identity_transform", "apply_transform_ffn", "propose",
    "ProposalConfig", "SearchConfig", "SearchResult", "make_adapter",
    "quantize_model", "PTQResult",
]

_LAZY = {
    "SearchConfig": "repro.core.search",
    "SearchResult": "repro.core.search",
    "make_adapter": "repro.core.search",
    "quantize_model": "repro.core.pipeline",
    "PTQResult": "repro.core.pipeline",
}


def __getattr__(name):
    if name in _LAZY:
        import importlib
        mod = importlib.import_module(_LAZY[name])
        return getattr(mod, name)
    raise AttributeError(name)
