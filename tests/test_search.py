"""Discrete search (Algorithm 1): loss decreases, state stays valid,
un-quantized invariance is preserved by accepted transforms."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import QuantConfig
from repro.core.search import (SearchConfig, DenseFFNAdapter, MoEAdapter,
                               make_adapter)
from repro.models import forward
from repro.core.objective import calib_ce
from repro.search import run as search_run


@pytest.fixture(scope="module")
def searched(trained_tiny, calib):
    params, cfg = trained_tiny
    qcfg = QuantConfig(bits=2, group_size=32)
    scfg = SearchConfig(steps=120, n_match_layers=2, log_every=0, seed=0)
    res = search_run(params, params, cfg, qcfg, calib, scfg)
    return params, cfg, res


def test_search_monotone_improvement(searched):
    _, _, res = searched
    assert res.final_loss < res.initial_loss, "hill climbing must improve the loss"
    best_curve = []
    best = float("inf")
    for (_, loss, _, _, accepted) in res.history:
        if accepted:
            assert loss < best or best == float("inf")
            best = min(best, loss)
        best_curve.append(best)
    assert best_curve[-1] <= best_curve[1]


def test_search_accept_rate_positive(searched):
    _, _, res = searched
    assert 0.0 < res.accept_rate <= 1.0


def test_search_improves_calibration_ce(searched, calib):
    params, cfg, res = searched
    from repro.core.rtn import rtn_quantize
    qcfg = QuantConfig(bits=2, group_size=32)
    ce_rtn = float(calib_ce(forward(rtn_quantize(params, qcfg), cfg, calib),
                            calib, cfg.vocab_size))
    ce_search = float(calib_ce(forward(res.params_q, cfg, calib), calib,
                               cfg.vocab_size))
    assert ce_search < ce_rtn, (
        f"search ce {ce_search:.4f} must beat plain RTN {ce_rtn:.4f}")


def test_transforms_stay_valid(searched):
    _, cfg, res = searched
    pi = np.asarray(res.transforms.pi)
    for l in range(pi.shape[0]):
        assert sorted(pi[l].tolist()) == list(range(cfg.d_ff))
    assert bool(np.all(np.asarray(res.transforms.s) > 0))


def test_transform_preserves_unquantized_model(searched, calib):
    """Applying the accepted transforms WITHOUT quantization must leave the
    (ReLU) model's outputs unchanged up to tiny-rotation error (Eqn. 6)."""
    params, cfg, res = searched
    adapter = DenseFFNAdapter(cfg)
    base = adapter.base_stack(params)
    units = []
    from repro.core.search import _tree_slice
    import repro.core.invariance as inv
    for u in range(adapter.n_units):
        t = inv.FFNTransform(*_tree_slice(res.transforms, u))
        units.append(adapter.transform_unit(base, t, u))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *units)
    params_t = adapter.install(params, stacked)
    l0 = forward(params, cfg, calib)
    l1 = forward(params_t, cfg, calib)
    rel = float(jnp.max(jnp.abs(l1 - l0)) / (jnp.max(jnp.abs(l0)) + 1e-9))
    assert rel < 5e-3, f"invariance violated: rel err {rel:.2e}"


def test_moe_adapter_units():
    from repro.configs import get_config
    from repro.models import init_params
    cfg = get_config("phi3.5-moe-42b-a6.6b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    adapter = MoEAdapter(cfg)
    assert adapter.n_units == cfg.n_layers * cfg.moe.num_experts
    base = adapter.base_stack(params)
    assert base["up"].shape[0] == adapter.n_units
    # per-expert transform + install round-trips shapes
    import repro.core.invariance as inv
    t = inv.identity_transform(cfg.d_ff)
    unit = adapter.transform_unit(base, t, 3)
    fq = adapter.quant_unit(unit, QuantConfig(bits=2, group_size=32))
    assert fq["up"].shape == (cfg.d_model, cfg.d_ff)


def test_make_adapter_dispatch():
    from repro.configs import get_config
    assert type(make_adapter(get_config("yi-6b"))).__name__ == "DenseFFNAdapter"
    assert type(make_adapter(get_config("phi3.5-moe-42b-a6.6b"))).__name__ == "MoEAdapter"
    assert type(make_adapter(get_config("mamba2-2.7b"))).__name__ == "MambaAdapter"


def test_hybrid_two_phase_search():
    """Zamba2-style hybrid: Mamba within-head perms + shared-FFN P/S/R both
    hill-climb through the composite runner."""
    from repro.configs import get_config
    from repro.core.pipeline import quantize_model
    from repro.models import init_params
    import jax.numpy as jnp

    cfg = get_config("zamba2-7b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    calib = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab_size)
    qcfg = QuantConfig(bits=2, group_size=16)
    scfg = SearchConfig(steps=40, n_match_layers=0, log_every=0)
    r = quantize_model(params, cfg, qcfg, method="rtn", calib_tokens=calib,
                       search=scfg)
    assert r.search.final_loss <= r.search.initial_loss
    assert r.method == "rtn+invarexplore"


def test_mamba_search_end_to_end():
    """Pure-SSM model: permutation-only search must not crash and must not
    regress the calibration loss."""
    from repro.configs import get_config
    from repro.core.pipeline import quantize_model
    from repro.models import init_params

    cfg = get_config("mamba2-2.7b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    calib = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab_size)
    qcfg = QuantConfig(bits=2, group_size=16)
    scfg = SearchConfig(steps=40, n_match_layers=0, log_every=0)
    r = quantize_model(params, cfg, qcfg, method="rtn", calib_tokens=calib,
                       search=scfg)
    assert r.search.final_loss <= r.search.initial_loss
