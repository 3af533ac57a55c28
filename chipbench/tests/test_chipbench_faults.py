"""``correct`` must come out false when the timed path is broken underneath.

Each test drives a whole CPU rehearsal run of a tiny cell (the harness's look
for a chip skipped) with one fault planted in the program, and checks that
the comparison with the reference catches it. The last test puts the
float8 control in the program's place and sees it fail too."""
import jax.numpy as jnp
import pytest

import tiny
from chipbench import harness


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    tiny.make_checkout(root)
    return root


SEARCH = "search.opt-1.3b"
SERVE = tiny.SERVE


def _incorrect(root, workload):
    out = tiny.run(root, workload, seed=41, seconds=1.0)
    assert out["correct"] is False, out["checks"]
    return out


def test_search_state_left_unchanged_fails(checkout, monkeypatch):
    from repro.search import engine
    # accepted moves never reach the returned transforms
    monkeypatch.setattr(engine, "_tree_update", lambda tree, i, new: tree)
    _incorrect(checkout, SEARCH)


def test_search_that_never_accepts_fails(checkout, monkeypatch):
    from repro.search import anneal
    # every move is rejected: the search returns its start
    monkeypatch.setattr(anneal, "accept", lambda *a, **k: False)
    out = _incorrect(checkout, SEARCH)
    assert out["checks"]["accepted_steps"]["value"] == 0
    assert out["checks"]["objective_gain"]["value"] == 0.0


def test_search_half_batch_fails(checkout, monkeypatch):
    from repro.core import objective
    from repro.models.model import lm_loss

    def half_ce(logits, tokens, vocab_size):
        h = logits.shape[0] // 2
        return lm_loss(logits[:h, :-1], tokens[:h, 1:], vocab_size)
    monkeypatch.setattr(objective, "calib_ce", half_ce)
    _incorrect(checkout, SEARCH)


def test_search_altered_answer_fails(checkout, monkeypatch):
    from repro.core import objective
    plain = objective.CEObjective.evaluate

    def altered(self, logits, hidden, state, env):
        p, a = plain(self, logits, hidden, state, env)
        return p * 1.01, a
    monkeypatch.setattr(objective.CEObjective, "evaluate", altered)
    _incorrect(checkout, SEARCH)


def test_serve_step_leaving_the_cache_unchanged_fails(checkout, monkeypatch):
    from repro.serving import decode
    monkeypatch.setattr(decode, "_write_token",
                        lambda pool, phys, slot, val: pool)
    _incorrect(checkout, SERVE)


@pytest.mark.parametrize("fault", ["altered_token", "half_batch"])
def test_serve_wrong_tokens_fail(checkout, monkeypatch, fault):
    from repro.serving import batcher
    make = batcher.make_paged_decode_step

    def broken(cfg, **kw):
        step = make(cfg, **kw)

        def wrapped(*args):
            tok, pools = step(*args)
            if fault == "altered_token":
                tok = (tok + 1) % cfg.vocab_size
            else:      # half of the slots is never computed
                half = tok.shape[0] // 2
                tok = tok.at[:half].set(0)
            return tok, pools
        return wrapped
    monkeypatch.setattr(batcher, "make_paged_decode_step", broken)
    _incorrect(checkout, SERVE)


def _sound_job(checkout, workload, seed):
    import argparse
    import time
    args = argparse.Namespace(workload=workload, seed=seed, seconds=1.0,
                              trace=0, t0=time.monotonic())
    _, _, _, ctx, res = harness.run_job(
        args, require_tpu=False, root=checkout,
        traffic_dir=checkout / "traffic", peaks=tiny.PEAKS,
        compile_cache=False)
    assert all(c.ok for c in res.checks)
    from chipbench import weights
    params = weights.make_weights(ctx.config, harness.seed_key(ctx.seed))
    return ctx, res, params


def test_fp8_control_fails_the_search_limits(checkout):
    ctx, res, params = _sound_job(checkout, SEARCH, 43)
    search = harness.job_module("search")
    calib = jnp.asarray(ctx.generator.make(ctx.traffic, ctx.config,
                                           ctx.seed))
    art = res.artifacts
    low = search.reference_terms(ctx.config, params, calib,
                                 art["transforms"], art["n_match"],
                                 matmul_dtype=jnp.float8_e4m3fn)
    ce, mse = search.rel_gaps(low, art["reference"])
    assert ce > search.LIMITS["ce_gap"] or mse > search.LIMITS["mse_gap"], \
        (ce, mse)


def test_fp8_control_fails_the_serve_limit(checkout):
    """The control's first tokens at the served positions, read by the same
    ``served_gaps`` the run compares, pass the limit."""
    ctx, res, params = _sound_job(checkout, SERVE, 47)
    serve = harness.job_module("serve")
    art = res.artifacts
    low = serve.reference_logits(ctx.config, params, art["sample"],
                                 ctx.traffic["server"]["context"],
                                 matmul_dtype=jnp.float8_e4m3fn)
    gaps = serve.served_gaps(art["reference_logits"],
                             serve.control_sample(art["sample"], low))
    assert max(gaps) > serve.LIMITS["served_logit_gap"], max(gaps)
