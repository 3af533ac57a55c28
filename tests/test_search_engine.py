"""Population × island search engine (repro.search): legacy parity,
reproducibility, annealing, migration, and the fused kernel path.

The acceptance bar (ISSUE 3): at ``population=1, islands=1, temperature=0``
the engine must reproduce the legacy single-chain ``run_search`` trajectory
BIT-FOR-BIT on the OPT-paper-family config — ``_legacy_run_search`` below is
a verbatim transcription of the pre-engine loop and the histories are
compared exactly, not approximately.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import invariance as inv
from repro.core import objective as obj
from repro.core.quant import QuantConfig
from repro.core.search import (SearchConfig, run_search, make_adapter,
                               DenseFFNAdapter, _tree_slice, _tree_update)
from repro.models import forward, init_params
from repro.search import anneal
from repro.search.islands import IslandState, make_island_streams, migrate
from repro.search.population import candidate_keys


@pytest.fixture(scope="module")
def tiny_opt():
    cfg = get_config("opt-tiny").reduced(
        n_layers=2, d_model=64, d_ff=128, vocab_size=256, n_heads=4,
        n_kv_heads=4, max_seq_len=256)
    params = init_params(jax.random.PRNGKey(0), cfg)
    calib = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                               cfg.vocab_size)
    return params, cfg, calib


QCFG = QuantConfig(bits=2, group_size=32)


def _legacy_run_search(params_fp, params_base, cfg, qcfg, calib_tokens, scfg):
    """Verbatim transcription of the pre-engine core/search.py hill climb.

    The one change from the original: the base model, calibration batch and
    FP reference reach the jitted programs as arguments (``ctx``), as they do
    in the engine — closed over, jit bakes them into the program as
    constants, which no published-width model survives.
    """
    adapter = make_adapter(cfg)
    n_match = min(scfg.n_match_layers, cfg.n_layers)
    base = adapter.base_stack(params_base)
    proposer = getattr(adapter, "propose", None) or (
        lambda key, t, pcfg: inv.propose(key, t, pcfg))
    t0 = inv.identity_transform(adapter.f_dim)
    transforms = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (adapter.n_units,) + x.shape).copy(), t0)
    fq_stack = jax.vmap(lambda b: adapter.quant_unit(b, qcfg))(base)
    logits_fp, hidden_fp = forward(params_fp, cfg, calib_tokens,
                                   collect_hidden=True)
    hidden_fp = jax.lax.stop_gradient(hidden_fp[:n_match]) if n_match else None
    logits_fp = jax.lax.stop_gradient(logits_fp)
    ctx = (params_base, calib_tokens, logits_fp, hidden_fp)

    def eval_stack(ctx, fq):
        params_base, calib_tokens, logits_fp, hidden_fp = ctx
        params_q = adapter.install(params_base, fq)
        logits, hidden = forward(params_q, cfg, calib_tokens,
                                 collect_hidden=True)
        if scfg.objective == "kl":
            ce = obj.calib_kl(logits, logits_fp, cfg.vocab_size)
        else:
            ce = obj.calib_ce(logits, calib_tokens, cfg.vocab_size)
        mse = (obj.activation_mse(hidden, hidden_fp, n_match)
               if n_match else jnp.float32(0.0))
        return ce, mse

    ce0, mse0 = map(float, jax.jit(eval_stack)(ctx, fq_stack))
    alpha = obj.resolve_alpha(ce0, mse0, scfg.ce_weight) if n_match else 0.0
    best = ce0 + alpha * float(mse0)

    @jax.jit
    def step_fn(ctx, key, transforms, fq_stack, u):
        k_prop, _ = jax.random.split(key)
        t_u = _tree_slice(transforms, u)
        t_new = proposer(k_prop, inv.FFNTransform(*t_u), scfg.proposal)
        unit = adapter.transform_unit(adapter.base_stack(ctx[0]), t_new, u)
        unit_fq = adapter.quant_unit(unit, qcfg)
        fq_new = _tree_update(fq_stack, u, unit_fq)
        ce, mse = eval_stack(ctx, fq_new)
        loss = ce + alpha * mse
        return loss, ce, mse, fq_new, t_new

    rng = np.random.default_rng(scfg.seed)
    key = jax.random.PRNGKey(scfg.seed)
    history = [(0, best, ce0, float(mse0), True)]
    n_accept = 0
    for step in range(1, scfg.steps + 1):
        key, sub = jax.random.split(key)
        u = jnp.int32(rng.integers(adapter.n_units))
        loss, ce, mse, fq_new, t_new = step_fn(ctx, sub, transforms,
                                               fq_stack, u)
        loss = float(loss)
        accepted = loss < best
        if accepted:
            best = loss
            fq_stack = fq_new
            transforms = _tree_update(transforms, u, t_new)
            n_accept += 1
        history.append((step, loss, float(ce), float(mse), accepted))
    return history, transforms, best, n_accept


# ---------------------------------------------------------------------------
# Engine-vs-legacy parity (acceptance bar: bit-for-bit)
# ---------------------------------------------------------------------------

def test_engine_reproduces_legacy_bitwise(tiny_opt):
    params, cfg, calib = tiny_opt
    scfg = SearchConfig(steps=40, n_match_layers=2, log_every=0, seed=0)
    assert (scfg.population, scfg.islands, scfg.temperature) == (1, 1, 0.0)
    h_legacy, t_legacy, best_legacy, n_acc = _legacy_run_search(
        params, params, cfg, QCFG, calib, scfg)
    res = run_search(params, params, cfg, QCFG, calib, scfg)
    # exact float equality on every (step, loss, ce, mse, accepted) entry
    assert res.history == h_legacy
    assert np.array_equal(np.asarray(res.transforms.pi), np.asarray(t_legacy.pi))
    assert np.array_equal(np.asarray(res.transforms.s), np.asarray(t_legacy.s))
    assert np.array_equal(np.asarray(res.transforms.phi),
                          np.asarray(t_legacy.phi))
    assert res.final_loss == best_legacy
    assert res.accept_rate == n_acc / scfg.steps


def test_population_batched_eval_improves(tiny_opt):
    """K candidates per step through one vmapped forward: still a valid
    hill climb (loss improves, permutations stay permutations)."""
    params, cfg, calib = tiny_opt
    scfg = SearchConfig(steps=20, n_match_layers=2, log_every=0, population=3)
    res = run_search(params, params, cfg, QCFG, calib, scfg)
    assert res.final_loss < res.initial_loss
    assert res.stats["proposals"] == 20 * 3
    pi = np.asarray(res.transforms.pi)
    for u in range(pi.shape[0]):
        assert sorted(pi[u].tolist()) == list(range(cfg.d_ff))


# ---------------------------------------------------------------------------
# Reproducibility across island counts (satellite contract)
# ---------------------------------------------------------------------------

def test_island0_trajectory_invariant_to_island_count(tiny_opt):
    """Same seed + same population ⇒ island 0's accepted-transform trajectory
    is identical whether it runs alone or beside a second island (migration
    off: elite exchange is the ONLY coupling between islands)."""
    params, cfg, calib = tiny_opt
    s1 = SearchConfig(steps=15, n_match_layers=0, log_every=0, population=2,
                      migrate_every=0)
    s2 = dataclasses.replace(s1, islands=2)
    r1 = run_search(params, params, cfg, QCFG, calib, s1)
    r2 = run_search(params, params, cfg, QCFG, calib, s2)
    assert len(r1.island_histories) == 1 and len(r2.island_histories) == 2
    assert r2.island_histories[0] == r1.island_histories[0]
    # the second island explores a genuinely different stream
    assert r2.island_histories[1] != r2.island_histories[0]


def test_engine_rerun_is_deterministic(tiny_opt):
    params, cfg, calib = tiny_opt
    scfg = SearchConfig(steps=10, n_match_layers=0, log_every=0, population=2,
                        islands=2, migrate_every=4)
    r1 = run_search(params, params, cfg, QCFG, calib, scfg)
    r2 = run_search(params, params, cfg, QCFG, calib, scfg)
    assert r1.island_histories == r2.island_histories
    assert r1.final_loss == r2.final_loss


# ---------------------------------------------------------------------------
# Annealing
# ---------------------------------------------------------------------------

def test_anneal_schedules():
    g = anneal.temperature_schedule("geometric", 2.0, 100)
    assert g(1) < 2.0 and g(100) == pytest.approx(1e-4)
    assert all(g(s) >= g(s + 1) for s in range(1, 100))
    lin = anneal.temperature_schedule("linear", 1.0, 10)
    assert lin(10) == 0.0 and lin(5) == pytest.approx(0.5)
    const = anneal.temperature_schedule("constant", 0.7, 10)
    assert const(9) == 0.7
    zero = anneal.temperature_schedule("geometric", 0.0, 10)
    assert zero(3) == 0.0
    with pytest.raises(ValueError):
        anneal.temperature_schedule("bogus", 1.0, 10)


def test_accept_rule_t0_is_strict_hill_climb():
    assert anneal.accept(-1e-9, 0.0, None)
    assert not anneal.accept(0.0, 0.0, None)
    assert not anneal.accept(1e-9, 0.0, None)
    # Metropolis: uphill accepted iff uniform < exp(-delta/T)
    assert anneal.accept(0.5, 1.0, 0.5)      # exp(-0.5) ~ 0.607
    assert not anneal.accept(0.5, 1.0, 0.7)


def test_annealed_search_takes_uphill_moves_keeps_elite(tiny_opt):
    params, cfg, calib = tiny_opt
    scfg = SearchConfig(steps=20, n_match_layers=0, log_every=0,
                        temperature=10.0, anneal="constant")
    res = run_search(params, params, cfg, QCFG, calib, scfg)
    assert res.stats["uphill_accepts"] >= 1
    accepted = [h[1] for h in res.history if h[4]]
    assert any(b > a for a, b in zip(accepted, accepted[1:])), \
        "a hot chain must move uphill sometimes"
    # elitism: the returned state is the best-ever, never worse than start
    assert res.final_loss <= res.initial_loss
    assert res.final_loss == min(h[1] for h in res.history)


# ---------------------------------------------------------------------------
# Islands: migration + streams
# ---------------------------------------------------------------------------

def _mk_island(i, cur, best):
    rng, key = make_island_streams(0, i)
    return IslandState(index=i, rng=rng, key=key, transforms=f"t{i}",
                       fq_stack=f"fq{i}", current_loss=cur, best_loss=best,
                       best_transforms=f"bt{i}", best_fq=f"bfq{i}")


def test_migrate_moves_elite_to_worst():
    a = _mk_island(0, cur=1.0, best=0.5)
    b = _mk_island(1, cur=3.0, best=2.0)
    assert migrate([a, b])
    assert b.current_loss == 0.5 and b.fq_stack == "bfq0"
    assert b.best_loss == 0.5 and b.best_transforms == "bt0"
    # donor untouched
    assert a.current_loss == 1.0 and a.best_loss == 0.5


def test_migrate_noop_cases():
    assert not migrate([_mk_island(0, 1.0, 0.5)])           # single island
    # the elite island is ITSELF the worst-current chain: nothing to move
    a, b = _mk_island(0, 2.0, 0.1), _mk_island(1, 1.0, 0.5)
    assert not migrate([a, b])
    assert a.fq_stack == "fq0" and b.fq_stack == "fq1"


def test_island_streams_island0_is_legacy():
    rng0, key0 = make_island_streams(7, 0)
    assert rng0.integers(1 << 30) == np.random.default_rng(7).integers(1 << 30)
    assert np.array_equal(np.asarray(key0),
                          np.asarray(jax.random.PRNGKey(7)))
    rng1, key1 = make_island_streams(7, 1)
    assert not np.array_equal(np.asarray(key0), np.asarray(key1))


def test_candidate_keys_k1_matches_legacy_split():
    sub = jax.random.PRNGKey(123)
    legacy_k_prop, _ = jax.random.split(sub)
    assert np.array_equal(np.asarray(candidate_keys(sub, 1)[0]),
                          np.asarray(legacy_k_prop))


def test_elite_over_mesh_local():
    """Elite selection through the dist collective on the local mesh."""
    from jax import shard_map
    from repro.launch.mesh import make_local_mesh
    from repro.search.islands import elite_over_mesh
    import jax.sharding as shd
    mesh = make_local_mesh()
    n = len(jax.devices())
    losses = jnp.arange(n, 0, -1).astype(jnp.float32)  # min on the last shard
    f = shard_map(lambda x: elite_over_mesh(x[0], "data"),
                  mesh=mesh, in_specs=shd.PartitionSpec("data"),
                  out_specs=(shd.PartitionSpec(), shd.PartitionSpec()),
                  check_vma=False)
    best, idx = f(losses)
    assert float(best) == 1.0 and int(idx) == n - 1


# ---------------------------------------------------------------------------
# Fused transform+fake-quant path
# ---------------------------------------------------------------------------

def test_fused_adapter_unit_matches_unfused(tiny_opt):
    params, cfg, calib = tiny_opt
    adapter = DenseFFNAdapter(cfg)
    base = adapter.base_stack(params)
    key = jax.random.PRNGKey(5)
    t = inv.propose(key, inv.identity_transform(cfg.d_ff),
                    inv.ProposalConfig())
    want = adapter.quant_unit(adapter.transform_unit(base, t, 1), QCFG)
    got = adapter.transform_quant_unit(base, t, 1, QCFG)
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-5)


def test_fused_engine_run_improves(tiny_opt):
    params, cfg, calib = tiny_opt
    scfg = SearchConfig(steps=10, n_match_layers=0, log_every=0, population=2,
                        fused_kernel=True)
    res = run_search(params, params, cfg, QCFG, calib, scfg)
    assert res.final_loss < res.initial_loss
    assert res.stats["fused"] is True


def test_fused_downgrade_warns_and_is_recorded():
    """Regression (ISSUE 4): an adapter without ``transform_quant_unit``
    (MambaAdapter) must WARN when fused_kernel=True is silently unusable,
    and record stats["fused"] = False instead of dropping the request."""
    cfg = get_config("mamba2-2.7b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    calib = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0,
                               cfg.vocab_size)
    qcfg = QuantConfig(bits=2, group_size=16)
    scfg = SearchConfig(steps=2, n_match_layers=0, log_every=0,
                        fused_kernel=True)
    with pytest.warns(UserWarning, match="transform_quant_unit"):
        res = run_search(params, params, cfg, qcfg, calib, scfg)
    assert res.stats["fused"] is False


def test_fused_bias_and_gate_transform_ordering():
    """Regression (ISSUE 4): ``DenseFFNAdapter.transform_quant_unit`` must
    transform b_up as (rotate -> scale -> permute) and b_gate as
    permute-only — EXACTLY ``inv.apply_transform_ffn``'s ordering — on a
    gated + biased FFN (the seed cfgs exercise bias xor gate, never both)."""
    cfg = get_config("opt-tiny").reduced(
        n_layers=2, d_model=64, d_ff=128, vocab_size=256, n_heads=4,
        n_kv_heads=4, gated_mlp=True, use_bias=True)
    params = init_params(jax.random.PRNGKey(3), cfg)
    adapter = DenseFFNAdapter(cfg)
    base = adapter.base_stack(params)
    assert set(base) >= {"up", "down", "gate", "b_up", "b_gate"}
    t = inv.propose(jax.random.PRNGKey(9), inv.identity_transform(cfg.d_ff),
                    inv.ProposalConfig())
    got = adapter.transform_quant_unit(base, t, 0, QCFG)
    b = jax.tree.map(lambda x: x[0], base)
    _, _, b_up_ref, _, b_gate_ref = inv.apply_transform_ffn(
        t, b["up"], b["down"], b["b_up"], b["gate"], b["b_gate"])
    np.testing.assert_allclose(np.asarray(got["b_up"]),
                               np.asarray(b_up_ref), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got["b_gate"]),
                               np.asarray(b_gate_ref), rtol=0, atol=0)
    # and the fused weights still agree with the unfused composition
    want = adapter.quant_unit(adapter.transform_unit(base, t, 0), QCFG)
    for k in ("up", "gate", "down"):
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-5, err_msg=k)


# ---------------------------------------------------------------------------
# Stats correctness (ISSUE 4 satellites)
# ---------------------------------------------------------------------------

def test_uphill_accepts_counts_strict_uphill_as_int(tiny_opt):
    """``uphill_accepts`` must count accepted moves with delta STRICTLY > 0
    (delta == 0 is lateral) and be a Python int, never a numpy bool sum.
    Pinned by recomputing the count from the engine's own history."""
    params, cfg, calib = tiny_opt
    scfg = SearchConfig(steps=20, n_match_layers=0, log_every=0,
                        temperature=10.0, anneal="constant")
    res = run_search(params, params, cfg, QCFG, calib, scfg)
    assert type(res.stats["uphill_accepts"]) is int
    cur = res.history[0][1]
    strict_uphill = 0
    for _, loss, _, _, accepted in res.history[1:]:
        if accepted:
            strict_uphill += loss > cur
            cur = loss
    assert res.stats["uphill_accepts"] == strict_uphill
    # and a cold chain can never move uphill
    cold = run_search(params, params, cfg, QCFG, calib,
                      SearchConfig(steps=10, n_match_layers=0, log_every=0))
    assert cold.stats["uphill_accepts"] == 0


# ---------------------------------------------------------------------------
# The one front door (ISSUE 10 satellite: repro.search.run)
# ---------------------------------------------------------------------------

def test_front_door_matches_legacy_bitwise(tiny_opt):
    """``repro.search.run`` at the default config reproduces the legacy
    trajectory bit-for-bit, and the deprecated ``run_search`` shim returns
    the identical result under a DeprecationWarning."""
    import repro.search as search
    params, cfg, calib = tiny_opt
    scfg = SearchConfig(steps=12, n_match_layers=2, log_every=0, seed=0)
    h_legacy, t_legacy, best_legacy, n_acc = _legacy_run_search(
        params, params, cfg, QCFG, calib, scfg)
    res = search.run(params, params, cfg, QCFG, calib, scfg)
    assert res.history == h_legacy
    assert res.final_loss == best_legacy
    assert np.array_equal(np.asarray(res.transforms.pi),
                          np.asarray(t_legacy.pi))
    assert res.stats["objective"] == "ce"
    assert res.stats["install"] == "unit"
    with pytest.warns(DeprecationWarning, match="run_search is deprecated"):
        res_shim = run_search(params, params, cfg, QCFG, calib, scfg)
    assert res_shim.history == res.history
    assert res_shim.final_loss == res.final_loss


def test_front_door_objective_kwarg_overrides_config(tiny_opt):
    """``run(..., objective=...)`` wins over ``SearchConfig.objective`` and
    is recorded in the result stats."""
    import repro.search as search
    params, cfg, calib = tiny_opt
    scfg = SearchConfig(steps=3, n_match_layers=0, log_every=0,
                        objective="ce")
    res = search.run(params, params, cfg, QCFG, calib, scfg, objective="kl")
    assert res.stats["objective"] == "kl"
    assert all(np.isfinite(h[1]) for h in res.history)


def test_front_door_auto_dispatches_hybrid():
    """A hybrid block pattern routes through the two-phase composite with no
    explicit runner choice (the legacy run_search_hybrid semantics)."""
    import repro.search as search
    cfg = get_config("zamba2-7b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    calib = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0,
                               cfg.vocab_size)
    qcfg = QuantConfig(bits=2, group_size=16)
    res = search.run(params, params, cfg, qcfg, calib,
                     SearchConfig(steps=5, n_match_layers=0, log_every=0))
    # two phases: (2 steps + step-0) + (3 steps + step-0)
    assert len(res.history) == 5 + 2
    assert res.stats["proposals"] == 5


def test_run_population_search_shim_warns(tiny_opt):
    params, cfg, calib = tiny_opt
    from repro.core.search import make_adapter
    from repro.search.engine import run_population_search
    scfg = SearchConfig(steps=2, n_match_layers=0, log_every=0)
    with pytest.warns(DeprecationWarning, match="run_population_search"):
        res = run_population_search(params, params, cfg, QCFG, calib, scfg,
                                    adapter=make_adapter(cfg))
    assert res.final_loss <= res.initial_loss


def test_hybrid_search_spends_odd_step_budgets_fully():
    """Regression (ISSUE 4): ``run_search_hybrid`` with ODD steps must run
    ``steps // 2`` + ``steps - steps // 2`` (not halve twice), and merge
    histories/stats across both phases."""
    from repro.core.search import run_search_hybrid
    cfg = get_config("zamba2-7b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    calib = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0,
                               cfg.vocab_size)
    qcfg = QuantConfig(bits=2, group_size=16)
    res = run_search_hybrid(params, params, cfg, qcfg, calib,
                            SearchConfig(steps=7, n_match_layers=0,
                                         log_every=0))
    # two phases, each history = steps + 1 (the step-0 entry): 3+1 + 4+1
    assert len(res.history) == 7 + 2
    assert res.stats["proposals"] == 7, "odd budgets must be spent in full"
    assert len(res.island_histories) == 1
    assert len(res.island_histories[0]) == 7 + 2
    assert type(res.stats["uphill_accepts"]) is int
