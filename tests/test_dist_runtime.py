"""Multi-host runtime surface: initialize fallback, psum barrier, device
introspection, elite-state broadcast, mapped-mode guardrails, and the
preemption-signal → checkpoint-and-barrier hook.

These run on the real 1-device backend (tests/conftest.py); the genuinely
multi-device/multi-process behavior is exercised by tests/test_dist_smoke.py
via child processes and by the CI ``distributed`` lane.
"""
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.checkpoint import CheckpointManager, latest_step
from repro.dist import runtime
from repro.dist.fault import PreemptionGuard, run_resilient


# ---------------- runtime ----------------

def test_initialize_single_process_is_noop(monkeypatch):
    monkeypatch.delenv("REPRO_COORDINATOR", raising=False)
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    assert runtime.initialize() is False
    assert runtime.is_distributed() is False
    assert runtime.process_index() == 0
    assert runtime.process_count() == 1


def test_initialize_rejects_coordinator_without_world_size(monkeypatch):
    """A configured coordinator with no num_processes must raise — silently
    degrading to 0-of-1 on every rank would split-brain the fleet."""
    monkeypatch.delenv("REPRO_NUM_PROCESSES", raising=False)
    with pytest.raises(ValueError, match="num_processes"):
        runtime.initialize(coordinator="127.0.0.1:9999")
    with pytest.raises(ValueError, match="coordinator"):
        runtime.initialize(process_id=1)


def test_device_summary_shape():
    s = runtime.device_summary()
    assert s["process_count"] == 1
    assert s["local_device_count"] == len(jax.local_devices())
    assert s["global_device_count"] == jax.device_count()
    assert s["platform"] == "cpu"


def test_barrier_runs_the_psum_single_process():
    # single-process: same psum code path, degenerate mesh — must not raise
    runtime.barrier("test")
    runtime.barrier("test-again")  # cached compiled fn


def test_global_put_replicated_roundtrip():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()), ("data",))
    x = np.arange(12, dtype=np.float32).reshape(3, 4)
    g = runtime.global_put(x, NamedSharding(mesh, P()))
    np.testing.assert_array_equal(np.asarray(g), x)
    t = runtime.replicated({"a": x, "b": None}, mesh)
    np.testing.assert_array_equal(np.asarray(t["a"]), x)


# ---------------- collectives ----------------

def test_elite_broadcast_selects_owner_tree():
    import functools
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.dist.collectives import elite_broadcast
    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()), ("data",))

    @functools.partial(shard_map, mesh=mesh, in_specs=(P("data"),),
                       out_specs=P(), check_vma=False)
    def f(x):
        tree = {"v": x[0], "w": x[0] * 2.0}
        out = elite_broadcast(tree, jnp.int32(0), "data")
        return out["v"], out["w"]

    v, w = f(jnp.asarray([3.0]))
    assert float(v) == 3.0 and float(w) == 6.0


# ---------------- mapped-mode guardrails ----------------

def test_mapped_requires_island_per_device(tiny_cfg):
    """islands != device count must fail fast with an actionable message
    (this pytest process has exactly 1 device by design)."""
    from repro.core.quant import QuantConfig
    from repro.core.search import SearchConfig, run_search
    from repro.models import init_params

    params = init_params(jax.random.PRNGKey(0), tiny_cfg)
    calib = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                               tiny_cfg.vocab_size)
    scfg = SearchConfig(steps=1, islands=jax.device_count() + 1, mapped=True,
                        n_match_layers=2, log_every=0)
    with pytest.raises(ValueError, match="one island per device"):
        run_search(params, params, tiny_cfg, QuantConfig(bits=2, group_size=32),
                   calib, scfg)


def test_mapped_single_island_single_device(tiny_cfg):
    """The degenerate mapped run (1 island on the 1 local device) must agree
    with sequential bit-for-bit in-process — the n-device version of this
    contract is pinned by tests/test_dist_smoke.py."""
    import dataclasses
    from repro.core.quant import QuantConfig
    from repro.core.search import SearchConfig, run_search
    from repro.models import init_params

    params = init_params(jax.random.PRNGKey(0), tiny_cfg)
    calib = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                               tiny_cfg.vocab_size)
    scfg = SearchConfig(steps=3, islands=1, n_match_layers=2, log_every=0)
    qcfg = QuantConfig(bits=2, group_size=32)
    r_seq = run_search(params, params, tiny_cfg, qcfg, calib, scfg)
    r_map = run_search(params, params, tiny_cfg, qcfg, calib,
                       dataclasses.replace(scfg, mapped=True))
    assert r_seq.history == r_map.history
    assert r_seq.final_loss == r_map.final_loss
    np.testing.assert_array_equal(np.asarray(r_seq.transforms.pi),
                                  np.asarray(r_map.transforms.pi))


# ---------------- preemption hook ----------------

def test_preemption_guard_drains_to_checkpoint(tmp_path):
    mgr = CheckpointManager(tmp_path)

    with PreemptionGuard(signals=(signal.SIGUSR1,)) as guard:
        def step_fn(state, step):
            if step == 3:
                signal.raise_signal(signal.SIGUSR1)  # "eviction notice"
            return {"w": state["w"] + 1}

        state, events = run_resilient(step_fn, {"w": jnp.zeros(())},
                                      n_steps=100, ckpt=mgr, save_every=50,
                                      preemption=guard)
    kinds = [e[0] for e in events]
    assert ("preempted", 4) in events, events
    assert "saved" in kinds
    assert float(state["w"]) == 4.0, "must stop at the next step boundary"
    assert latest_step(tmp_path) == 4, "the drain checkpoint must be durable"
    # the next incarnation resumes exactly where the drain left off
    tree, manifest = mgr.restore()
    assert manifest["step"] == 4 and float(tree["w"]) == 4.0


def test_preemption_guard_restores_previous_handler():
    prev = signal.getsignal(signal.SIGUSR1)
    with PreemptionGuard(signals=(signal.SIGUSR1,)) as g:
        assert signal.getsignal(signal.SIGUSR1) != prev
        assert not g.preempted
    assert signal.getsignal(signal.SIGUSR1) == prev


def test_run_resilient_without_preemption_unchanged(tmp_path):
    """preemption=None keeps the original contract (no early return)."""
    state, events = run_resilient(lambda s, i: {"w": s["w"] + 1},
                                  {"w": jnp.zeros(())}, n_steps=5)
    assert float(state["w"]) == 5.0 and events == []
