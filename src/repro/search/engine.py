"""Population × island annealed discrete search (the Algorithm 1 engine).

One engine, three nested degrees of freedom, each defaulting to the paper's
single-chain hill climb:

- population K: K candidate transforms for the step's unit. The engine
  carries ONE fake-quant stack plus a K × *unit* candidate buffer and
  installs only the touched unit per evaluation via
  ``jax.lax.dynamic_update_slice`` tree surgery (``repro.search.install``)
  — peak memory is stack + K × unit.
- temperature T: Metropolis acceptance of the chosen candidate under an
  annealing schedule; T=0 is the strict accept-iff-better rule.
- islands: independent chains with per-island counter-based key streams and
  elite migration on a fixed cadence (``repro.search.islands``). With
  ``shard_calib=True`` each island climbs on its OWN contiguous slice of the
  calibration batch (``data.calib.shard_calibration``) — true data-parallel
  calibration; islands exchange only scalar objective estimates at
  migration.

Every step, at every K, runs one pipeline of four jitted programs:
``propose_body`` (K candidate transforms for the unit), ``build_units_body``
(transform + fake-quant of the K candidate units), ``eval_body`` (a
``lax.map`` over the candidates, each installed into the stack and run
through the objective forward ``eval_stack_fn``) and ``pick_body`` (the
best candidate and its installed stack). The stages are separate programs
so the K-candidate buffer is a real set of device arrays between them and
``measure_memory=True`` samples the memory model honestly.

The objective is pluggable (``core.objective``): ``SearchConfig.objective``
takes a registry name ("ce", "kl", "swd_actmatch", "saliency_ce") or an
``Objective`` instance; the engine combines ``(primary, aux)`` as
``loss = primary + α · aux`` with α resolved from the step-0 full-batch
values. A tried-point tabu memory (``SearchConfig(tabu=N)``,
``repro.search.tabu``) replays cached scalars for proposals already
evaluated at the current chain state instead of paying the device forward.

Bit-for-bit contract: at ``population=1, islands=1, temperature=0`` under
the default objective (tabu off, calibration replicated) the engine's
proposal keys, unit picks, losses and accept decisions are EXACTLY the
legacy single-chain loop's, so the accepted-move trajectory reproduces the
paper configuration unchanged (pinned by ``tests/test_search_engine.py``
through the ``repro.search.run`` front door).

Execution modes:

- sequential (default): islands run one after another in-process — the
  reference semantics, and the only mode a 1-device host can run.
- ``mapped=True``: one island per shard of a 1-D ("data",) mesh over ALL
  global devices. Every process replays every island's HOST streams (unit
  picks, accept draws — cheap scalars), so the accept logic stays on the
  host exactly as in sequential mode; only the expensive proposal
  evaluation runs on-device, one island per shard, and the per-migration
  traffic is one scalar ``argmin_allgather`` plus the winner's state via
  ``elite_broadcast``. The mapped trajectory is pinned BIT-FOR-BIT equal to
  the sequential island loop because both lanes call the SAME per-island
  step programs (the CI ``distributed`` lane drives 2 real processes
  through ``repro.launch.dist_smoke``).
"""
from __future__ import annotations

import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import invariance as inv
from repro.core import objective as obj
from repro.models.model import forward
from repro.search import anneal
from repro.search.install import (eval_candidates_unit, stack_unit_batch,
                                  tree_bytes, tree_install_unit)
from repro.search.islands import (IslandState, make_island_streams, migrate,
                                  migrate_on_mesh)
from repro.search.population import candidate_keys, stack_trees, take_tree
from repro.search.tabu import TabuMemory, transform_bytes


def _search_metrics():
    """Instrument handles on the process registry (get-or-create, so a
    registry ``reset()`` between runs keeps these valid)."""
    reg = obs.get_registry()
    return {
        "proposals": reg.counter(
            "search_proposals_total", "Candidate transforms proposed"),
        "accepts": reg.counter(
            "search_accepts_total", "Moves accepted by the Metropolis rule"),
        "uphill": reg.counter(
            "search_uphill_accepts_total",
            "Accepted strictly-worse (uphill) moves"),
        "migrations": reg.counter(
            "search_migrations_total", "Elite island migrations applied"),
        "tabu": reg.counter(
            "search_tabu_hits_total",
            "Proposals deduplicated by the tried-point memory"),
        "best": reg.gauge(
            "search_objective_best", "Best combined objective seen so far"),
        "temp": reg.gauge(
            "search_temperature", "Annealing temperature at the last step"),
        "step": reg.histogram(
            "search_step_seconds", "Wall time of one full search step"),
        "eval": reg.histogram(
            "search_eval_seconds",
            "Proposal evaluation latency (dispatch + loss sync)"),
    }


def _tree_slice(tree, i):
    return jax.tree.map(lambda x: x[i], tree)


def _tree_update(tree, i, new):
    return jax.tree.map(lambda x, n: x.at[i].set(n), tree, new)


def _elite_history(histories, best_loss):
    """The history that reached ``best_loss``: the first of ``histories``
    with an accepted entry at that loss. A migration can bring an island
    the best state another island reached; the returned history is then
    that island's, so it describes the state the search returns."""
    for h in histories:
        if any(acc and loss == best_loss for _, loss, _, _, acc in h):
            return h
    return histories[0]


def _live_bytes() -> int:
    return int(sum(getattr(a, "nbytes", 0) for a in jax.live_arrays()))


def _resolve_install(scfg) -> str:
    mode = str(getattr(scfg, "install", "unit"))
    if mode != "unit":
        raise ValueError(
            f"SearchConfig.install must be 'unit' (the engine installs one "
            f"unit per candidate; the 'stack' lane was removed), got {mode!r}")
    return mode


def _run_engine(
    params_fp: dict,
    params_base: dict,
    cfg,
    qcfg,
    calib_tokens: jnp.ndarray,
    scfg,
    adapter,
    forward_kwargs: Optional[dict] = None,
):
    """Run the engine; returns a ``core.search.SearchResult``.

    ``params_fp`` / ``params_base`` follow the ``repro.search.run``
    contract (FP reference model; base-method continuous-domain FFN weights
    with everything else already fake-quantized).
    """
    from repro.core.search import SearchResult  # front-end owns the dataclass
    from repro.data.calib import shard_calibration

    fwd_kw = forward_kwargs or {}
    n_match = min(scfg.n_match_layers, cfg.n_layers)
    K = max(int(getattr(scfg, "population", 1)), 1)
    n_islands = max(int(getattr(scfg, "islands", 1)), 1)
    migrate_every = int(getattr(scfg, "migrate_every", 0))
    mapped = bool(getattr(scfg, "mapped", False))
    fused = bool(getattr(scfg, "fused_kernel", False))
    install_mode = _resolve_install(scfg)
    tabu_cap = int(getattr(scfg, "tabu", 0))
    shard_calib = bool(getattr(scfg, "shard_calib", False))
    measure = bool(getattr(scfg, "measure_memory", False))
    objv = obj.get_objective(getattr(scfg, "objective", "ce"))
    if fused and not hasattr(adapter, "transform_quant_unit"):
        warnings.warn(
            f"fused_kernel=True but adapter {type(adapter).__name__} has no "
            f"transform_quant_unit; falling back to the unfused "
            f"transform->quantize path", stacklevel=2)
        fused = False
    if tabu_cap and mapped:
        raise ValueError(
            "tabu memory needs the host-synchronous sequential lane "
            "(candidate fingerprints are host state); mapped=True cannot "
            "combine with tabu>0")

    # search.prepare: everything before the first step, ending once the
    # step-0 baseline scalars are on the host (so the span is synced)
    with obs.trace_span("search.prepare", population=K,
                        islands=n_islands):
        base = adapter.base_stack(params_base)
        proposer = getattr(adapter, "propose", None) or (
            lambda key, t, pcfg: inv.propose(key, t, pcfg))

        # identity transforms + initial fake-quant of every unit (per-unit slices
        # hit quant_unit so the ndim>=2 "skip biases" check stays correct)
        t0 = inv.identity_transform(adapter.f_dim)
        transforms0 = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (adapter.n_units,) + x.shape).copy(), t0)
        fq0 = jax.vmap(lambda b: adapter.quant_unit(b, qcfg))(base)
        # an adapter may build the stack as a copy (MoEAdapter
        # concatenates its unit kinds); the step programs rebuild it inside
        # their own jit, so free it for the run
        del base

        # reference forward (FP model) on the FULL calibration batch; per-island
        # slices view into these (batch axis 0 for tokens/logits, axis 1 for the
        # (L, B, S, D) hidden taps)
        logits_fp, hidden_fp = forward(params_fp, cfg, calib_tokens,
                                       collect_hidden=True, **fwd_kw)
        hidden_fp = jax.lax.stop_gradient(hidden_fp[:n_match]) if n_match else None
        logits_fp = jax.lax.stop_gradient(logits_fp)

        def make_env(tokens, lfp, hfp):
            return obj.ObjectiveEnv(calib=tokens, logits_fp=lfp, hidden_fp=hfp,
                                    vocab_size=cfg.vocab_size, n_match=n_match,
                                    ce_weight=scfg.ce_weight)

        # Every array a step program reads besides the chain state — the base
        # model, the calibration slice, its FP reference and the objective's
        # prepared state — travels as an explicit argument, the island's
        # "context". Closed over, jit would bake these into the program as
        # constants (gigabytes at published widths), and a mapped island could
        # not read its own device's copy.
        def make_ctx(tokens, lfp, hfp):
            return {"params": params_base, "calib": tokens, "logits_fp": lfp,
                    "hidden_fp": hfp, "fwd_kw": fwd_kw,
                    "state": objv.prepare(make_env(tokens, lfp, hfp))}

        ctx_global = make_ctx(calib_tokens, logits_fp, hidden_fp)
        env_global = make_env(calib_tokens, logits_fp, hidden_fp)
        if shard_calib:
            slices = shard_calibration(calib_tokens, n_islands)
            bounds = np.cumsum([0] + [int(s.shape[0]) for s in slices])
            ctxs = [make_ctx(slices[i],
                             logits_fp[bounds[i]:bounds[i + 1]],
                             (hidden_fp[:, bounds[i]:bounds[i + 1]]
                              if n_match else None))
                    for i in range(n_islands)]
        else:
            ctxs = [ctx_global] * n_islands

        def eval_stack_fn(ctx, fq):
            env = make_env(ctx["calib"], ctx["logits_fp"], ctx["hidden_fp"])
            params_q = adapter.install(ctx["params"], fq)
            logits, hidden = forward(params_q, cfg, env.calib,
                                     collect_hidden=True, **ctx["fwd_kw"])
            return objv.evaluate(logits, hidden, ctx["state"], env)

        eval_jit = obs.scoped(jax.jit(eval_stack_fn))
        p0, a0 = map(float, eval_jit(ctx_global, fq0))
        alpha = float(objv.resolve_mix(p0, a0, env_global))
        loss0 = p0 + alpha * a0

        def quant_candidate(ctx, t_new, u):
            base = adapter.base_stack(ctx["params"])
            if fused:
                return adapter.transform_quant_unit(base, t_new, u, qcfg)
            unit = adapter.transform_unit(base, t_new, u)
            return adapter.quant_unit(unit, qcfg)

        # ---- step programs (shared by every island; the context is an arg) ---
        # propose / build / eval / pick are SEPARATE jitted stages so the
        # K-candidate buffer is a real set of device arrays between stages —
        # ``jax.live_arrays()`` then measures the memory model (stack + K ×
        # unit) honestly.
        peak = {"bytes": 0, "batch_bytes": 0}

        def propose_body(key, transforms, u):
            keys = candidate_keys(key, K)
            t_u = inv.FFNTransform(*_tree_slice(transforms, u))
            cands = [proposer(keys[i], t_u, scfg.proposal) for i in range(K)]
            return stack_trees(cands)

        propose_fn = obs.scoped(jax.jit(propose_body))

        def build_units_body(ctx, cands, u):
            units = [quant_candidate(
                ctx, inv.FFNTransform(*_tree_slice(cands, i)), u)
                for i in range(K)]
            return stack_unit_batch(units)

        def eval_body(ctx, batch, fq_stack, u):
            return eval_candidates_unit(batch, fq_stack, u,
                                        lambda fq: eval_stack_fn(ctx, fq))

        def pick_body(cands, batch, fq_stack, u, p_vec, a_vec):
            loss = p_vec + alpha * a_vec
            i = jnp.argmin(loss)
            fq_new = tree_install_unit(fq_stack, u, take_tree(batch, i))
            return (loss[i], p_vec[i], a_vec[i], fq_new,
                    inv.FFNTransform(*_tree_slice(cands, i)), loss)

        build_fn = obs.scoped(jax.jit(build_units_body))
        eval_fn = obs.scoped(jax.jit(eval_body))
        pick_fn = obs.scoped(jax.jit(pick_body))

        # host-callable step: (ctx, key, transforms, fq_stack, u) ->
        # (loss, primary, aux, fq_new, t_new, loss_vec, p_vec, a_vec)
        def step_fn(ctx, key, transforms, fq_stack, u):
            cands = propose_fn(key, transforms, u)
            batch = build_fn(ctx, cands, u)
            if measure:
                jax.block_until_ready(batch)
                peak["bytes"] = max(peak["bytes"], _live_bytes())
                peak["batch_bytes"] = max(peak["batch_bytes"], tree_bytes(batch))
            p_vec, a_vec = eval_fn(ctx, batch, fq_stack, u)
            out = pick_fn(cands, batch, fq_stack, u, p_vec, a_vec)
            return out[:5] + (out[5], p_vec, a_vec)

        if shard_calib:
            # per-island step-0 baselines on each island's OWN slice (1 island
            # == the full batch == bitwise the replicated baseline)
            loss0s, p0s, a0s = [], [], []
            for ctx in ctxs:
                pi0, ai0 = map(float, eval_jit(ctx, fq0))
                p0s.append(pi0)
                a0s.append(ai0)
                loss0s.append(pi0 + alpha * ai0)
        else:
            loss0s = [loss0] * n_islands
            p0s = [p0] * n_islands
            a0s = [a0] * n_islands

        schedule = anneal.temperature_schedule(
            getattr(scfg, "anneal", "geometric"),
            float(getattr(scfg, "temperature", 0.0)), scfg.steps)

        stats = {"migrations": 0, "uphill_accepts": 0,
                 "proposals": scfg.steps * K * n_islands, "fused": fused,
                 "mapped": mapped, "objective": objv.name,
                 "install": install_mode, "tabu_hits": 0,
                 "shard_calib": shard_calib}
        metrics = _search_metrics()
        metrics["best"].set(loss0)

    if mapped:
        return _run_mapped_islands(
            SearchResult, adapter, scfg, params_base, step_fn, ctxs, schedule,
            stats, transforms0, fq0, loss0s, p0s, a0s, n_islands,
            migrate_every, metrics, objv.name)

    if measure:
        baseline = _live_bytes()
        peak["bytes"] = baseline

    islands = []
    tabus = []
    for i in range(n_islands):
        rng, key = make_island_streams(scfg.seed, i)
        islands.append(IslandState(
            index=i, rng=rng, key=key, transforms=transforms0, fq_stack=fq0,
            current_loss=loss0s[i], best_loss=loss0s[i],
            best_transforms=transforms0, best_fq=fq0,
            history=[(0, loss0s[i], p0s[i], a0s[i], True)]))
        tabus.append(TabuMemory(tabu_cap) if tabu_cap else None)

    # on a full-K tabu hit the device eval is skipped; if the Metropolis rule
    # then ACCEPTS a cached (previously rejected, T>0) move, only its unit is
    # rebuilt and installed — one quant, no calibration forward
    def rebuild_body(ctx, cands, fq_stack, u, i):
        t_new = inv.FFNTransform(*_tree_slice(cands, i))
        fq_new = tree_install_unit(fq_stack, u,
                                   quant_candidate(ctx, t_new, u))
        return fq_new, t_new

    rebuild_fn = jax.jit(rebuild_body)

    def draw(isl):
        """The step's proposal key and unit (the legacy draw order)."""
        isl.key, sub = jax.random.split(isl.key)
        return sub, jnp.int32(isl.rng.integers(adapter.n_units))

    with obs.trace_span("search.run", mode="sequential",
                        islands=n_islands, population=K) as run_span:
        for step in range(1, scfg.steps + 1):
            T = schedule(step)
            with obs.trace_span("search.step", step=step,
                                hist=metrics["step"]):
                for isl in islands:
                    mem = tabus[isl.index]
                    skipped = False
                    cands = fps = None
                    if mem is not None:
                        sub, u = draw(isl)
                        cands = propose_fn(sub, isl.transforms, u)
                        cand_bytes = [
                            transform_bytes(_tree_slice(cands, i))
                            for i in range(K)]
                        fps = [mem.fingerprint(int(u), cb)
                               for cb in cand_bytes]
                        hits_before = mem.hits
                        cached = [mem.lookup(fp) for fp in fps]
                        new_hits = mem.hits - hits_before
                        if new_hits:
                            stats["tabu_hits"] += new_hits
                            metrics["tabu"].inc(new_hits)
                        skipped = all(c is not None for c in cached)
                    with obs.trace_span("search.eval",
                                        hist=metrics["eval"]):
                        if skipped:
                            # replay: no device eval, no extra PRNG draw
                            # (the step key was spent proposing, exactly as
                            # on the eval path)
                            ci = int(np.argmin([c[0] for c in cached]))
                            loss, p, a = cached[ci]
                            fq_new = t_new = None
                        else:
                            with obs.trace_span("search.dispatch"):
                                if mem is None:
                                    sub, u = draw(isl)
                                out = step_fn(ctxs[isl.index], sub,
                                              isl.transforms, isl.fq_stack,
                                              u)
                                loss, p, a, fq_new, t_new = out[:5]
                            with obs.trace_span("search.sync"):
                                loss = float(loss)   # the device sync
                                if mem is not None:
                                    # cache every candidate's device-
                                    # computed scalars for exact replay on a
                                    # later hit
                                    loss_vec = np.asarray(out[5], np.float32)
                                    p_vec = np.asarray(out[6], np.float32)
                                    a_vec = np.asarray(out[7], np.float32)
                                    for i in range(K):
                                        mem.record(fps[i],
                                                   float(loss_vec[i]),
                                                   float(p_vec[i]),
                                                   float(a_vec[i]))
                            if measure:
                                peak["bytes"] = max(peak["bytes"],
                                                    _live_bytes())
                    with obs.trace_span("search.accept"):
                        metrics["proposals"].inc(K, objective=objv.name)
                        delta = loss - isl.current_loss
                        uniform = isl.rng.random() if T > 0.0 else None
                        accepted = anneal.accept(delta, T, uniform)
                        if accepted:
                            if skipped:
                                fq_new, t_new = rebuild_fn(
                                    ctxs[isl.index], cands, isl.fq_stack, u,
                                    jnp.int32(ci))
                            # strictly-worse moves only (delta == 0 is
                            # lateral, not uphill), counted as a Python int
                            # — not an accumulated numpy bool
                            if delta > 0.0:
                                stats["uphill_accepts"] += 1
                                metrics["uphill"].inc()
                            metrics["accepts"].inc()
                            isl.current_loss = loss
                            isl.fq_stack = fq_new
                            isl.transforms = _tree_update(isl.transforms, u,
                                                          t_new)
                            isl.n_accept += 1
                            if mem is not None:
                                idx = ci if skipped else None
                                if idx is None:
                                    # which candidate won? match by bytes
                                    tb = transform_bytes(t_new)
                                    idx = cand_bytes.index(tb)
                                mem.advance(cand_bytes[idx])
                            if loss < isl.best_loss:
                                isl.best_loss = loss
                                isl.best_transforms = isl.transforms
                                isl.best_fq = isl.fq_stack
                        isl.history.append(
                            (step, loss, float(p), float(a), accepted))
                if migrate_every and n_islands > 1 \
                        and step % migrate_every == 0:
                    if tabu_cap:
                        src = min(islands, key=lambda s: s.best_loss)
                        dst = max(islands, key=lambda s: s.current_loss)
                        will = (src is not dst
                                and src.best_loss < dst.current_loss)
                    n_migrated = migrate(islands)
                    stats["migrations"] += n_migrated
                    metrics["migrations"].inc(n_migrated)
                    if tabu_cap and n_migrated and will:
                        tabus[dst.index].adopt_digest(tabus[src.index])
            metrics["best"].set(min(s.best_loss for s in islands))
            metrics["temp"].set(T)
            if scfg.log_every and step % scfg.log_every == 0:
                best = min(s.best_loss for s in islands)
                rate = sum(s.n_accept for s in islands) / (step * n_islands)
                obs.emit("search", step=step, best=f"{best:.5f}",
                         accept=f"{rate:.2%}", T=f"{T:.4g}",
                         elapsed_s=f"{run_span.elapsed():.1f}")

    elite = min(islands, key=lambda s: s.best_loss)
    # monotonic clock (run_span.dur): wall time steps backwards under NTP
    stats["proposals_per_sec"] = stats["proposals"] / max(run_span.dur, 1e-9)
    if measure:
        stats["peak_live_bytes"] = max(peak["bytes"] - baseline, 0)
        stats["stack_bytes"] = tree_bytes(fq0)
        stats["candidate_batch_bytes"] = peak["batch_bytes"]
    return SearchResult(
        params_q=adapter.install(params_base, elite.best_fq),
        transforms=elite.best_transforms,
        history=_elite_history([elite.history] + [s.history for s in islands],
                               elite.best_loss),
        accept_rate=elite.n_accept / max(scfg.steps, 1),
        final_loss=elite.best_loss,
        initial_loss=loss0s[elite.index],
        island_histories=[s.history for s in islands],
        stats=stats,
    )


# ---------------------------------------------------------------------------
# mapped mode: one island per shard of the ("data",) mesh
# ---------------------------------------------------------------------------

def _run_mapped_islands(SearchResult, adapter, scfg, params_base, step_fn,
                        ctxs, schedule, stats, transforms0, fq0, loss0s, p0s,
                        a0s, n_islands, migrate_every, metrics, obj_name):
    """The mapped island loop: one island per shard of the ("data",) mesh.

    Split of responsibilities, chosen so "bit-for-bit equal to sequential"
    is a property of the construction rather than a hope about the compiler:

    - the per-island STEP (propose → transform → fake-quant → forward → loss)
      runs the SAME step programs the sequential engine runs (the staged
      propose/build/eval/pick pipeline), with the island's state AND its
      context (base model, calibration slice, FP reference) committed to
      its shard's device — XLA generates identical code for identical
      programs, so the per-step scalars come out bit-identical island by
      island. (Running the step
      *inside* shard_map instead was measurably NOT bit-stable: the
      surrounding slice/gather graph perturbs how XLA fuses the loss
      reductions, and ``optimization_barrier`` does not fence it off.)
    - everything CROSS-island runs inside ``shard_map`` over the island axis
      and is pure data movement, which is exact: the per-step scalar
      exchange (an all-gather of each shard's (loss, primary, aux) row), and
      the per-migration elite exchange — ``argmin_allgather`` for the scalar
      race, ``elite_broadcast`` for the winner's state, a masked select for
      the splice (``islands.migrate_on_mesh``).
    - control stays on the host: every process replays every island's host
      streams (unit picks, accept uniforms — cheap scalars), so the accept
      logic and histories are computed identically everywhere, and each
      process steps only the islands whose shard devices it owns.

    Under ``shard_calib=True`` each island's context carries its own
    calibration slice (``ctxs[i]``), so the migration race compares
    per-slice objective estimates — the only cross-island objective traffic.

    Under a multi-process ``jax.distributed`` runtime the same loop runs
    unchanged: hosts step their local islands independently and meet only at
    the scalar exchange and migrations (the CI ``distributed`` lane pins 2
    processes against the single-process sequential result).
    """
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.dist import runtime
    from jax import shard_map
    from repro.dist.collectives import elite_broadcast
    from repro.search.islands import gather_island_states, scatter_island_states

    devs = jax.devices()
    if n_islands != len(devs):
        raise ValueError(
            f"mapped=True runs one island per device shard: islands="
            f"{n_islands} but the mesh has {len(devs)} global devices "
            f"(match --islands to the device count, or force devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    mesh = Mesh(np.array(devs), ("data",))
    shd = NamedSharding(mesh, P("data"))
    pid = jax.process_index()
    local = {i: d for i, d in enumerate(devs) if d.process_index == pid}
    multiproc = jax.process_count() > 1

    # per-LOCAL-island state, committed to the island's shard device (the
    # cross-host stacked layout only materializes for migrations/fetch)
    t_loc = {i: jax.device_put(transforms0, d) for i, d in local.items()}
    fq_loc = {i: jax.device_put(fq0, d) for i, d in local.items()}
    ctx_loc = {i: jax.device_put(ctxs[i], d) for i, d in local.items()}
    bt_loc = dict(t_loc)
    bfq_loc = dict(fq_loc)

    exchange = jax.jit(shard_map(
        lambda rows: jax.lax.all_gather(rows[0], "data"),
        mesh=mesh, in_specs=(P("data"),), out_specs=P(), check_vma=False))

    migrate_mapped = jax.jit(shard_map(
        lambda bl, cl, t, fq, bt, bfq: migrate_on_mesh(
            bl, cl, t, fq, bt, bfq, "data"),
        mesh=mesh,
        in_specs=(P("data"),) * 6,
        out_specs=((P("data"),) * 4) + (P(),),
        check_vma=False))

    def put_shd(x):
        return runtime.global_put(x, shd)

    streams = [make_island_streams(scfg.seed, i) for i in range(n_islands)]
    rngs = [s[0] for s in streams]
    keys = [s[1] for s in streams]
    cur = list(loss0s)
    best = list(loss0s)
    n_accept = [0] * n_islands
    histories = [[(0, loss0s[i], p0s[i], a0s[i], True)]
                 for i in range(n_islands)]
    K = max(int(getattr(scfg, "population", 1)), 1)

    pid0 = jax.process_index() == 0
    run_span = obs.trace_span("search.run", mode="mapped",
                              islands=n_islands).__enter__()
    for step in range(1, scfg.steps + 1):
        T = schedule(step)
        step_span = obs.trace_span("search.step", step=step,
                                   hist=metrics["step"]).__enter__()
        subs = [None] * n_islands
        us = [None] * n_islands
        for i in range(n_islands):
            # replay EVERY island's streams so hosts stay in lock-step; only
            # the local islands are evaluated
            keys[i], sub = jax.random.split(keys[i])
            subs[i] = sub
            us[i] = int(rngs[i].integers(adapter.n_units))
        with obs.trace_span("search.eval", hist=metrics["eval"]):
            outs = {}
            u_dev = {}
            with obs.trace_span("search.dispatch"):
                for i, d in local.items():   # dispatch all, then fetch
                    u_dev[i] = jax.device_put(jnp.int32(us[i]), d)
                    outs[i] = step_fn(ctx_loc[i],
                                      jax.device_put(subs[i], d),
                                      t_loc[i], fq_loc[i], u_dev[i])
            with obs.trace_span("search.sync"):
                scal = np.zeros((n_islands, 3), np.float32)
                for i, out in outs.items():
                    scal[i] = [float(out[0]), float(out[1]), float(out[2])]
        # each host counts only its LOCAL islands, so the dist_snapshot sum
        # over hosts reconciles with the global stats["proposals"]
        metrics["proposals"].inc(len(outs) * K, objective=obj_name)
        if multiproc:
            scal = np.asarray(exchange(put_shd(scal)))
        with obs.trace_span("search.accept"):
            for i in range(n_islands):
                loss = float(scal[i, 0])
                delta = loss - cur[i]
                uniform = rngs[i].random() if T > 0.0 else None
                accepted = anneal.accept(delta, T, uniform)
                if accepted:
                    if delta > 0.0:
                        stats["uphill_accepts"] += 1
                        if i in outs:
                            metrics["uphill"].inc()
                    if i in outs:   # count local islands only (see proposals)
                        metrics["accepts"].inc()
                    cur[i] = loss
                    n_accept[i] += 1
                    if i in outs:
                        fq_loc[i] = outs[i][3]
                        t_loc[i] = _tree_update(t_loc[i], u_dev[i], outs[i][4])
                    if loss < best[i]:
                        best[i] = loss
                        if i in outs:
                            bt_loc[i] = t_loc[i]
                            bfq_loc[i] = fq_loc[i]
                histories[i].append((step, loss, float(scal[i, 1]),
                                     float(scal[i, 2]), accepted))
        if migrate_every and n_islands > 1 and step % migrate_every == 0:
            t_st = gather_island_states(t_loc, mesh, n_islands)
            fq_st = gather_island_states(fq_loc, mesh, n_islands)
            bt_st = gather_island_states(bt_loc, mesh, n_islands)
            bfq_st = gather_island_states(bfq_loc, mesh, n_islands)
            t_st, fq_st, bt_st, bfq_st, did = migrate_mapped(
                put_shd(np.asarray(best, np.float32)),
                put_shd(np.asarray(cur, np.float32)),
                t_st, fq_st, bt_st, bfq_st)
            t_loc = scatter_island_states(t_st, local)
            fq_loc = scatter_island_states(fq_st, local)
            bt_loc = scatter_island_states(bt_st, local)
            bfq_loc = scatter_island_states(bfq_st, local)
            if bool(np.asarray(did)):
                # replay the decision on the host floats (identical f32
                # comparisons to the ones the device just made)
                src = int(np.argmin(np.asarray(best, np.float32)))
                dst = int(np.argmax(np.asarray(cur, np.float32)))
                cur[dst] = best[src]
                if best[src] < best[dst]:
                    best[dst] = best[src]
                stats["migrations"] += 1
                if pid0:   # every host replays the decision; count it once
                    metrics["migrations"].inc()
        step_span.__exit__(None, None, None)
        metrics["best"].set(min(best))
        metrics["temp"].set(T)
        if scfg.log_every and step % scfg.log_every == 0:
            rate = sum(n_accept) / (step * n_islands)
            obs.emit("search", step=step, best=f"{min(best):.5f}",
                     accept=f"{rate:.2%}", T=f"{T:.4g}",
                     elapsed_s=f"{run_span.elapsed():.1f}", mode="mapped")

    elite = int(np.argmin(np.asarray(best, np.float32)))
    bt_st = gather_island_states(bt_loc, mesh, n_islands)
    bfq_st = gather_island_states(bfq_loc, mesh, n_islands)

    def fetch_body(bt, bfq):
        strip = lambda tr: jax.tree.map(lambda x: x[0], tr)  # noqa: E731
        return (elite_broadcast(strip(bt), elite, "data"),
                elite_broadcast(strip(bfq), elite, "data"))

    best_t, best_fq = jax.jit(shard_map(
        fetch_body, mesh=mesh, in_specs=(P("data"),) * 2,
        out_specs=(P(), P()), check_vma=False))(bt_st, bfq_st)
    # localize: the result contract is host-local arrays, same as sequential
    best_t = jax.tree.map(lambda x: jnp.asarray(np.asarray(x)), best_t)
    best_fq = jax.tree.map(lambda x: jnp.asarray(np.asarray(x)), best_fq)

    run_span.__exit__(None, None, None)
    # monotonic clock (run_span.dur): wall time steps backwards under NTP
    stats["proposals_per_sec"] = stats["proposals"] / max(run_span.dur, 1e-9)
    return SearchResult(
        params_q=adapter.install(params_base, best_fq),
        transforms=best_t,
        history=_elite_history([histories[elite]] + histories, best[elite]),
        accept_rate=n_accept[elite] / max(scfg.steps, 1),
        final_loss=best[elite],
        initial_loss=loss0s[elite],
        island_histories=histories,
        stats=stats,
    )
