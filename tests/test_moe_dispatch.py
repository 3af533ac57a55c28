"""The grouped MoE dispatch's ladder of row bounds.

``_grouped_experts`` sorts the slots routed to the experts held here into a
buffer of one of a few static row bounds (``_dispatch_ladder``), picked on
the device as the smallest that holds every live slot. Rows past the live
ones add exact zeros, so each bound must give, bit for bit, what the single
bound ``_dispatch_rows`` (the most slots the held experts can be sent)
gives. The shape here, 1024 tokens, 32 router experts, 4 held, top-6, has
three bounds: 1024, 2048 and 4096 rows.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.models import layers as L

B, S, E, TOP_K = 4, 256, 32, 6


def moe_cfg(held=(8, 4)):
    base = get_config("moonshot-v1-16b-a3b")
    return dataclasses.replace(
        base, d_model=64, d_ff=32, param_dtype="float32",
        compute_dtype="float32",
        moe=dataclasses.replace(base.moe, num_experts=E, top_k=TOP_K,
                                experts_held=held))


def routing(case, held):
    """(weights, expert ids), each (B, S, TOP_K): distinct experts a token."""
    start, count = held
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    pick = jnp.argsort(jax.random.uniform(k1, (B, S, E)), axis=-1)
    if case == "all_to_held":      # every token's first four: the held ones
        others = jnp.array([e for e in range(E)
                            if not start <= e < start + count])
        pick = jnp.concatenate(
            [jnp.broadcast_to(jnp.arange(start, start + count), (B, S, count)),
             others[pick[..., :TOP_K - count] % len(others)]], axis=-1)
        idx = pick
    elif case == "none_held":      # every slot to an expert held elsewhere
        others = jnp.array([e for e in range(E)
                            if not start <= e < start + count])
        idx = others[jnp.argsort(pick[..., :len(others)], axis=-1)
                     [..., :TOP_K]]
    elif case == "half_to_held":   # the first half as all_to_held, the rest
        # as none_held: exactly the middle bound's rows
        idx = jnp.concatenate([routing("all_to_held", held)[1][:B // 2],
                               routing("none_held", held)[1][B // 2:]])
    else:                          # random distinct experts: the even share
        idx = pick[..., :TOP_K]
    w = jax.random.uniform(k2, (B, S, TOP_K), minval=0.1, maxval=1.0)
    return w, idx.astype(jnp.int32)


@pytest.mark.parametrize("case,held,live_in", [
    ("balanced", (8, 4), (1, 1024)),        # the smallest bound
    ("half_to_held", (8, 4), (2048, 2048)),  # the middle bound, full
    ("all_to_held", (8, 4), (2049, 4096)),  # the largest bound
    ("none_held", (8, 4), (0, 0)),          # no live slot
    ("balanced", (0, E), (1, 6144)),        # all held: one bound, no switch
])
def test_ladder_matches_the_single_bound_bitwise(monkeypatch, case, held,
                                                 live_in):
    cfg = moe_cfg(held)
    T = B * S
    rungs = L._dispatch_ladder(T, TOP_K, held[1], E)
    assert rungs == ((1024, 2048, 4096) if held[1] == 4 else (6144,))
    p = L.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
    w, idx = routing(case, held)
    local = np.asarray(idx) - held[0]
    live = int(((local >= 0) & (local < held[1])).sum())
    assert live_in[0] <= live <= live_in[1], live

    fn = jax.jit(lambda p, x, w, i: L._grouped_experts(p, cfg, x, w, i))
    jaxpr = str(jax.make_jaxpr(
        lambda p, x, w, i: L._grouped_experts(p, cfg, x, w, i))(p, x, w, idx))
    assert ("cond[" in jaxpr) == (len(rungs) > 1)
    got = fn(p, x, w, idx)
    with monkeypatch.context() as mp:
        mp.setattr(L, "_dispatch_ladder",
                   lambda t, k, h, e: (L._dispatch_rows(t, k, h),))
        want = jax.jit(lambda p, x, w, i: L._grouped_experts(
            p, cfg, x, w, i))(p, x, w, idx)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if case == "none_held":
        assert not np.asarray(got).any()


@pytest.mark.parametrize("held,rows", [((8, 4), (1024, 2048, 4096)),
                                       ((0, E), (6144,))])
def test_rows_counter_counts_each_bound_once_a_trace(held, rows):
    cfg = moe_cfg(held)
    p = L.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.zeros((B, S, cfg.d_model))
    w, idx = routing("balanced", held)
    c = obs.counter("moe_dispatch_rows_total")
    before = {r: c.value(rows=r) for r in (1024, 2048, 4096, 6144)}
    jax.jit(lambda p, x, w, i: L._grouped_experts(p, cfg, x, w, i)).lower(
        p, x, w, idx)
    assert {r: c.value(rows=r) - before[r] for r in before} == \
        {r: float(r in rows) for r in before}


def test_branches_carry_their_bound_and_the_layer_scopes():
    """Each branch's compiled operations lie under ``moe.rows_<M>`` and
    under one of ``moe.dispatch`` or ``moe.experts``, never both, as the
    device-share readers count them."""
    cfg = moe_cfg()
    p = L.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.zeros((B, S, cfg.d_model))
    w, idx = routing("balanced", (8, 4))
    txt = jax.jit(lambda p, x, w, i: L._grouped_experts(
        p, cfg, x, w, i)).lower(p, x, w, idx).compile().as_text()
    by_rung = {M: [] for M in (1024, 2048, 4096)}
    for name in obs.scope_map(txt).values():
        parts = name.split("/")
        for M in by_rung:
            if f"moe.rows_{M}" in parts:
                by_rung[M].append(parts)
    for M, names in by_rung.items():
        assert names, M
        for parts in names:
            assert ("moe.dispatch" in parts) != ("moe.experts" in parts), parts
        assert any("moe.experts" in parts for parts in names), M
