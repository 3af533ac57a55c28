"""Search framework v2 (ISSUE 10): O(unit)-memory candidate install,
pluggable objectives, tried-point tabu memory, sharded per-island
calibration.

Property bars:
  * dynamic-slice install == indexed-update install — exact on Dense AND
    MoE unit stacks, and the ``lax.map`` candidate eval scores each
    candidate as its own full-stack install would;
  * objective registry round-trips strings and instances;
  * sharded-vs-replicated calibration is bitwise identical at 1 island;
  * the tabu memory never blocks an improving move and never perturbs the
    trajectory (hit replay is exact; no extra PRNG per skip).
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
from repro.core.search import (DenseFFNAdapter, MoEAdapter, SearchConfig,
                               _tree_update)
from repro.models import init_params
from repro.search import run as search_run
from repro.search.install import (eval_candidates_unit, stack_unit_batch,
                                  tree_bytes, tree_install_unit)
from repro.search.tabu import TabuMemory, transform_bytes

QCFG = QuantConfig(bits=2, group_size=32)


@pytest.fixture(scope="module")
def tiny_opt():
    cfg = get_config("opt-tiny").reduced(
        n_layers=2, d_model=64, d_ff=128, vocab_size=256, n_heads=4,
        n_kv_heads=4, max_seq_len=256)
    params = init_params(jax.random.PRNGKey(0), cfg)
    calib = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                               cfg.vocab_size)
    return params, cfg, calib


# ---------------------------------------------------------------------------
# install: dynamic-slice surgery == indexed-update surgery
# ---------------------------------------------------------------------------

def _install_equiv_on(adapter, params):
    base = adapter.base_stack(params)
    fq = jax.vmap(lambda b: adapter.quant_unit(b, QCFG))(base)
    rng = np.random.default_rng(0)
    for u in (0, adapter.n_units - 1, int(rng.integers(adapter.n_units))):
        unit = jax.tree.map(
            lambda x: x[u] + jnp.asarray(rng.normal(), x.dtype), fq)
        via_slice = tree_install_unit(fq, jnp.int32(u), unit)
        via_index = _tree_update(fq, u, unit)
        for a, b in zip(jax.tree.leaves(via_slice),
                        jax.tree.leaves(via_index)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # untouched units really are untouched
        for a, b in zip(jax.tree.leaves(via_slice), jax.tree.leaves(fq)):
            mask = np.ones(a.shape[0], bool)
            mask[u] = False
            np.testing.assert_array_equal(np.asarray(a)[mask],
                                          np.asarray(b)[mask])


def test_install_unit_equals_tree_update_dense(tiny_opt):
    params, cfg, _ = tiny_opt
    _install_equiv_on(DenseFFNAdapter(cfg), params)


def test_install_unit_equals_tree_update_moe():
    cfg = get_config("phi3.5-moe-42b-a6.6b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    adapter = MoEAdapter(cfg)
    assert adapter.n_units == cfg.n_layers * cfg.moe.num_experts
    _install_equiv_on(adapter, params)


def test_eval_candidates_unit_matches_stack(tiny_opt):
    """The ``lax.map`` lane scores each candidate as installing it into the
    full stack and evaluating that stack alone does (the eval here is a
    cheap deterministic reduction, so equality is exact)."""
    params, cfg, _ = tiny_opt
    adapter = DenseFFNAdapter(cfg)
    base = adapter.base_stack(params)
    fq = jax.vmap(lambda b: adapter.quant_unit(b, QCFG))(base)
    K, u = 3, 1
    units = [jax.tree.map(lambda x: x[u] * (1.0 + 0.1 * i), fq)
             for i in range(K)]
    batch = stack_unit_batch(units)

    def eval_fn(stack):
        flat = sum(jnp.sum(x) for x in jax.tree.leaves(stack))
        return flat, flat * 0.5

    p_u, a_u = jax.jit(
        lambda b: eval_candidates_unit(b, fq, u, eval_fn))(batch)
    one = jax.jit(lambda unit: eval_fn(_tree_update(fq, u, unit)))
    p_s, a_s = map(np.asarray, zip(*[one(unit) for unit in units]))
    assert p_u.shape == (K,)
    np.testing.assert_allclose(np.asarray(p_u), p_s, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(a_u), a_s, rtol=1e-6)
    # the candidate buffer really is K x unit, not K x stack
    assert tree_bytes(batch) * adapter.n_units == tree_bytes(fq) * K


@pytest.mark.parametrize("population,tabu", [(1, 0), (3, 0), (2, 8)])
def test_engine_programs_take_weights_as_arguments(tiny_opt, monkeypatch,
                                                   population, tabu):
    """Every jitted engine program receives the model, calibration batch and
    FP reference as arguments. Closed over, jit bakes them into the program
    as constants: gigabytes at published widths, and a mapped island could
    not read its own device's copy. No constant may be as large as the
    smallest weight matrix (64 x 64 f32 = 16 KiB)."""
    import re

    import repro.search.engine as engine

    texts = []

    class JitSpy:
        def __getattr__(self, name):
            return getattr(jax, name)

        @staticmethod
        def jit(fn, **kw):
            jitted = jax.jit(fn, **kw)
            seen = []

            def call(*args):
                if not seen:
                    seen.append(True)
                    texts.append(jitted.lower(*args).as_text())
                return jitted(*args)
            return call

    monkeypatch.setattr(engine, "jax", JitSpy())
    params, cfg, calib = tiny_opt
    search_run(params, params, cfg, QCFG, calib,
               SearchConfig(steps=3, n_match_layers=2, log_every=0,
                            population=population, tabu=tabu))
    assert len(texts) >= (2 if population == 1 else 4)
    blobs = [m for t in texts for m in re.findall(r'dense<"0x([0-9A-F]*)"', t)]
    largest = max((len(b) // 2 for b in blobs), default=0)
    assert largest < 64 * 64 * 4, f"{largest}-byte constant in a program"


@pytest.mark.parametrize("install", ["bogus", "stack"])
def test_engine_rejects_unknown_install(tiny_opt, install):
    params, cfg, calib = tiny_opt
    with pytest.raises(ValueError, match="install"):
        search_run(params, params, cfg, QCFG, calib,
                   SearchConfig(steps=1, log_every=0, install=install))


def test_measure_memory_unit_batch_smaller_than_stack(tiny_opt):
    """``measure_memory=True`` reports the memory model: the candidate
    buffer is K x unit, smaller than K full stacks by the unit count."""
    params, cfg, calib = tiny_opt
    K = 4
    r = search_run(params, params, cfg, QCFG, calib,
                   SearchConfig(steps=4, n_match_layers=0, log_every=0,
                                population=K, measure_memory=True))
    assert {"peak_live_bytes", "stack_bytes",
            "candidate_batch_bytes"} <= set(r.stats)
    assert r.stats["install"] == "unit"
    n_units = DenseFFNAdapter(cfg).n_units
    assert (r.stats["candidate_batch_bytes"] * n_units
            == r.stats["stack_bytes"] * K)


# ---------------------------------------------------------------------------
# objective registry
# ---------------------------------------------------------------------------

def test_objective_registry_round_trip():
    for name, cls in (("ce", obj.CEObjective), ("kl", obj.KLObjective),
                      ("swd_actmatch", obj.SWDActMatchObjective),
                      ("saliency_ce", obj.SaliencyCEObjective)):
        got = obj.get_objective(name)
        assert isinstance(got, cls) and got.name == name
        assert obj.objective_name(name) == name
        # instance pass-through: the SAME object comes back
        assert obj.get_objective(got) is got
        assert obj.objective_name(got) == name
    assert isinstance(obj.get_objective(None), obj.CEObjective)


def test_objective_registry_errors_and_register():
    with pytest.raises(ValueError, match="swd_actmatch"):
        obj.get_objective("nope")
    with pytest.raises(TypeError):
        obj.get_objective(42)
    with pytest.raises(ValueError, match="already registered"):
        obj.register_objective("ce", obj.CEObjective)

    class Custom(obj.Objective):
        name = "custom_t10"

    obj.register_objective("custom_t10", Custom)
    try:
        assert isinstance(obj.get_objective("custom_t10"), Custom)
        obj.register_objective("custom_t10", Custom, overwrite=True)
    finally:
        obj.OBJECTIVES.pop("custom_t10", None)


def test_objective_instance_through_config(tiny_opt):
    """SearchConfig.objective accepts an Objective INSTANCE, not only a
    registry name."""
    params, cfg, calib = tiny_opt
    scfg = SearchConfig(steps=3, n_match_layers=2, log_every=0,
                        objective=obj.KLObjective())
    res = search_run(params, params, cfg, QCFG, calib, scfg)
    assert res.stats["objective"] == "kl"
    assert res.final_loss <= res.initial_loss


@pytest.mark.parametrize("name", ["swd_actmatch", "saliency_ce"])
def test_new_objectives_run_end_to_end(tiny_opt, name):
    params, cfg, calib = tiny_opt
    scfg = SearchConfig(steps=6, n_match_layers=2, log_every=0,
                        objective=name, population=2)
    res = search_run(params, params, cfg, QCFG, calib, scfg)
    assert res.stats["objective"] == name
    assert np.isfinite(res.initial_loss) and np.isfinite(res.final_loss)
    assert res.final_loss <= res.initial_loss      # elitism
    assert all(np.isfinite(h[1]) for h in res.history)


def test_swd_is_permutation_invariant_and_discriminative(tiny_opt):
    """SWD over activation clouds: zero against itself under sample
    permutation, positive against a shifted cloud."""
    params, cfg, calib = tiny_opt
    swd = obj.SWDActMatchObjective(n_proj=16)
    env = obj.ObjectiveEnv(calib=calib, logits_fp=jnp.zeros((2, 4, 8)),
                           hidden_fp=jax.random.normal(
                               jax.random.PRNGKey(0), (2, 2, 16, 8)),
                           vocab_size=8, n_match=2)
    state = swd.prepare(env)
    x = env.hidden_fp.astype(jnp.float32).reshape(2, -1, 8)
    perm = jax.random.permutation(jax.random.PRNGKey(1), x.shape[1])

    def dist(cloud):
        proj = cloud @ state["dirs"]
        return float(jax.vmap(obj._swd_1d)(state["ref_sorted"], proj).mean())

    assert dist(x[:, perm]) == pytest.approx(0.0, abs=1e-9)
    assert dist(x + 3.0) > 1e-2


def test_saliency_weights_are_fp_confidence(tiny_opt):
    """saliency_ce weights = FP model's probability of the true next token,
    normalized to mean 1 — confident positions dominate the objective."""
    params, cfg, calib = tiny_opt
    from repro.models import forward
    logits_fp, hidden = forward(params, cfg, calib, collect_hidden=True)
    env = obj.ObjectiveEnv(calib=calib, logits_fp=logits_fp,
                           hidden_fp=hidden[:2], vocab_size=cfg.vocab_size,
                           n_match=2)
    sal = obj.SaliencyCEObjective()
    w = np.asarray(sal.prepare(env)["w"])
    assert w.shape == (calib.shape[0], calib.shape[1] - 1)
    assert np.all(w >= 0)
    assert np.mean(w) == pytest.approx(1.0, rel=1e-5)
    # evaluating the FP model itself reproduces a weighted CE, not garbage
    p, a = sal.evaluate(logits_fp, hidden, sal.prepare(env), env)
    assert np.isfinite(float(p)) and float(p) > 0


# ---------------------------------------------------------------------------
# sharded per-island calibration
# ---------------------------------------------------------------------------

def test_shard_calibration_slices():
    from repro.data.calib import shard_calibration
    calib = np.arange(24).reshape(6, 4)
    parts = shard_calibration(calib, 3)
    assert [p.shape for p in parts] == [(2, 4)] * 3
    np.testing.assert_array_equal(np.concatenate(parts), calib)
    assert shard_calibration(calib, 1)[0] is calib
    with pytest.raises(ValueError, match="divide"):
        shard_calibration(calib, 4)


def test_sharded_calib_one_island_is_bitwise_replicated(tiny_opt):
    """1 island => the shard IS the full batch: the sharded lane must
    reproduce the replicated lane exactly, per-entry."""
    params, cfg, calib = tiny_opt
    s = SearchConfig(steps=8, n_match_layers=2, log_every=0, population=2)
    r_rep = search_run(params, params, cfg, QCFG, calib, s)
    r_shd = search_run(params, params, cfg, QCFG, calib,
                       dataclasses.replace(s, shard_calib=True))
    assert r_shd.stats["shard_calib"] is True
    assert r_shd.history == r_rep.history
    assert r_shd.final_loss == r_rep.final_loss


def test_sharded_calib_islands_climb_their_own_slices(tiny_opt):
    """2 islands x 1-seq slices: both chains improve on their OWN data and
    migration still exchanges elites on the scalar estimates."""
    params, cfg, calib = tiny_opt
    s = SearchConfig(steps=10, n_match_layers=2, log_every=0, islands=2,
                     migrate_every=4, shard_calib=True)
    res = search_run(params, params, cfg, QCFG, calib, s)
    assert len(res.island_histories) == 2
    # per-slice baselines differ (different data!), and each history starts
    # at its own island's step-0 loss
    l0 = [h[0][1] for h in res.island_histories]
    assert l0[0] != l0[1]
    assert res.final_loss <= min(h0 for h0 in l0)
    assert res.initial_loss in l0


# ---------------------------------------------------------------------------
# tabu memory
# ---------------------------------------------------------------------------

def test_tabu_memory_unit():
    t = inv.identity_transform(8)
    b = transform_bytes(t)
    mem = TabuMemory(capacity=2)
    fp = mem.fingerprint(3, b)
    assert mem.lookup(fp) is None and mem.hits == 0
    mem.record(fp, 1.5, 1.0, 0.5)
    assert mem.lookup(fp) == (1.5, 1.0, 0.5) and mem.hits == 1
    # the digest advance invalidates every pre-accept fingerprint
    mem.advance(b)
    assert mem.fingerprint(3, b) != fp
    assert mem.lookup(mem.fingerprint(3, b)) is None
    # LRU capacity bound
    for i in range(4):
        mem.record(mem.fingerprint(i, b), float(i), 0.0, 0.0)
    assert len(mem) == 2
    # migration adoption re-keys the digest off the donor
    other = TabuMemory()
    other.advance(b)
    before = mem.fingerprint(0, b)
    mem.adopt_digest(other)
    assert mem.fingerprint(0, b) != before


class _ConstProposalAdapter(DenseFFNAdapter):
    """Proposal depends only on (state, unit) — every re-visit of an
    unaccepted state re-proposes the SAME point, forcing tabu hits."""

    def propose(self, key, t, pcfg):
        del key
        return inv.propose(jax.random.PRNGKey(7), t, pcfg)


def test_tabu_hits_do_not_perturb_the_trajectory(tiny_opt):
    """K=2 routes tabu=0 and tabu>0 through the SAME staged programs, so
    with a state-deterministic proposer the tabu run must (a) take hits,
    (b) replay them exactly — bit-identical histories — and (c) never block
    an improving move (the accepted-move set is identical)."""
    params, cfg, calib = tiny_opt
    adapter = _ConstProposalAdapter(cfg)
    s = SearchConfig(steps=12, n_match_layers=0, log_every=0, population=2)
    r_plain = search_run(params, params, cfg, QCFG, calib, s,
                         adapter=adapter)
    r_tabu = search_run(params, params, cfg, QCFG, calib,
                        dataclasses.replace(s, tabu=64), adapter=adapter)
    assert r_tabu.stats["tabu_hits"] > 0
    assert r_tabu.history == r_plain.history
    assert r_tabu.final_loss == r_plain.final_loss
    assert np.array_equal(np.asarray(r_tabu.transforms.pi),
                          np.asarray(r_plain.transforms.pi))


def test_tabu_with_random_proposals_is_transparent(tiny_opt):
    """With the real key-driven proposer, collisions are vanishingly rare:
    the tabu machinery must be a bit-exact no-op on the trajectory."""
    params, cfg, calib = tiny_opt
    s = SearchConfig(steps=6, n_match_layers=0, log_every=0, population=2)
    r_plain = search_run(params, params, cfg, QCFG, calib, s)
    r_tabu = search_run(params, params, cfg, QCFG, calib,
                        dataclasses.replace(s, tabu=64))
    assert r_tabu.history == r_plain.history
    assert r_tabu.stats["tabu_hits"] == 0


def test_tabu_annealed_accept_from_cache(tiny_opt):
    """T>0 with a state-deterministic proposer: cached (previously
    rejected) moves can be re-drawn and ACCEPTED by the Metropolis rule —
    the rebuild-from-cache path must produce a consistent run, and the
    PRNG/uniform streams stay aligned (rerun determinism)."""
    params, cfg, calib = tiny_opt
    adapter = _ConstProposalAdapter(cfg)
    s = SearchConfig(steps=15, n_match_layers=0, log_every=0, population=2,
                     temperature=5.0, anneal="constant", tabu=64)
    r1 = search_run(params, params, cfg, QCFG, calib, s, adapter=adapter)
    r2 = search_run(params, params, cfg, QCFG, calib, s, adapter=adapter)
    assert r1.history == r2.history
    assert r1.stats["tabu_hits"] == r2.stats["tabu_hits"]
    assert r1.final_loss <= r1.initial_loss        # elitism survives
    pi = np.asarray(r1.transforms.pi)
    for u in range(pi.shape[0]):                   # still permutations
        assert sorted(pi[u].tolist()) == list(range(cfg.d_ff))


def test_tabu_rejects_mapped(tiny_opt):
    params, cfg, calib = tiny_opt
    with pytest.raises(ValueError, match="tabu"):
        search_run(params, params, cfg, QCFG, calib,
                   SearchConfig(steps=1, log_every=0, tabu=8, mapped=True))
