"""Multi-host smoke: real ``jax.distributed`` bring-up, mapped-island parity,
sharded-checkpoint re-mesh. The CI ``distributed`` lane runs TWO of these as
real OS processes against one localhost coordinator:

    XLA_FLAGS=--xla_force_host_platform_device_count=2 \\
    PYTHONPATH=src python -m repro.launch.dist_smoke \\
        --coordinator 127.0.0.1:12355 --num-processes 2 --process-id 0 \\
        --ckpt-dir /tmp/dist_ckpt &
    ... same with --process-id 1 ...

Each process asserts, and exits non-zero on any failure:

  1. bring-up: ``dist.runtime.initialize`` + a psum ``barrier()`` across all
     global devices (2 procs x 2 forced CPU devices = 4);
  2. mapped-island parity: a small ``mapped=True`` search over the 4-shard
     global mesh must reproduce the sequential engine's trajectory
     BIT-FOR-BIT (histories compared exactly — the sequential run is pure
     process-local compute, so it doubles as the single-process reference);
  3. obs aggregation: each process fills a registry with pid-skewed values;
     ``obs.dist_snapshot()`` must merge them (counters summed, gauges
     min/max/sum, histogram buckets added) into byte-identical snapshots on
     every host, with process 0 writing the merged report;
  4. sharded checkpoint: a tree (dense + QTensor leaves) sharded over a
     ("data", "model") mesh is saved with each process writing ONLY its
     addressable shards, then restored onto a DIFFERENT mesh shape (1-D
     ("data",)) and onto plain host-local arrays; both must match the
     original values exactly.

``--num-processes 1`` (the default) runs the same checks single-process on
however many local devices exist — that is what ``tests/test_dist_smoke.py``
drives under a forced 2-device CPU topology.
"""
from __future__ import annotations

import argparse
import sys
import tempfile


def _check_mapped_parity(steps: int, migrate_every: int, population: int):
    import dataclasses

    import jax

    from repro.configs import get_config
    from repro.core.quant import QuantConfig
    from repro.core.search import SearchConfig
    from repro.models import init_params
    from repro.search import run as search_run

    cfg = get_config("opt-tiny").reduced(
        n_layers=2, d_model=64, d_ff=128, vocab_size=256, n_heads=4,
        n_kv_heads=4, max_seq_len=256)
    params = init_params(jax.random.PRNGKey(0), cfg)
    calib = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                               cfg.vocab_size)
    qcfg = QuantConfig(bits=2, group_size=32)
    n_islands = jax.device_count()
    scfg = SearchConfig(steps=steps, seed=0, n_match_layers=2, log_every=0,
                        islands=n_islands, migrate_every=migrate_every,
                        population=population)

    r_seq = search_run(params, params, cfg, qcfg, calib, scfg)
    r_map = search_run(params, params, cfg, qcfg, calib,
                       dataclasses.replace(scfg, mapped=True))
    if r_seq.island_histories != r_map.island_histories:
        for i, (a, b) in enumerate(zip(r_seq.island_histories,
                                       r_map.island_histories)):
            for ea, eb in zip(a, b):
                if ea != eb:
                    raise AssertionError(
                        f"mapped-island divergence at island {i}: "
                        f"sequential {ea} vs mapped {eb}")
        raise AssertionError("mapped-island histories differ in length")
    assert r_seq.final_loss == r_map.final_loss
    assert r_seq.stats["migrations"] == r_map.stats["migrations"]
    import numpy as np
    np.testing.assert_array_equal(np.asarray(r_seq.transforms.pi),
                                  np.asarray(r_map.transforms.pi))
    print(f"[dist_smoke] mapped parity OK: {n_islands} islands x "
          f"{steps} steps, {r_map.stats['migrations']} migrations, "
          f"loss {r_map.initial_loss:.4f}->{r_map.final_loss:.4f}",
          flush=True)


def _check_obs_aggregation(metrics_out: str = None):
    """Multi-host metric aggregation: every process contributes a pid-skewed
    registry; ``dist_snapshot()`` must produce the SAME merged snapshot on
    every host, with counters summed, gauges min/max/sum-merged and
    histogram buckets added exactly. Process 0 commits the report."""
    import json

    import jax

    from repro import obs

    pid, nproc = jax.process_index(), jax.process_count()
    reg = obs.Registry()
    # pid-dependent values so a "merge" that is secretly a local snapshot
    # (or that double-counts a host) cannot pass the sum checks
    reg.counter("smoke_widgets_total", "per-host counter").inc(10 + pid)
    reg.counter("smoke_labelled_total", "labelled counter").inc(
        2, host=f"h{pid}")
    reg.gauge("smoke_depth", "per-host gauge").set(float(pid))
    h = reg.histogram("smoke_lat_seconds", "per-host histogram",
                      buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5 + pid)     # pid 0 lands in bucket 1, pid>=1 in +Inf

    snap = obs.dist_snapshot(reg, force_gather=(nproc == 1))
    blob = obs.snapshot_json(snap)

    want_widgets = sum(10 + p for p in range(nproc))
    got_widgets = snap["smoke_widgets_total"]["series"][0]["value"]
    assert got_widgets == want_widgets, \
        f"counter merge: {got_widgets} != {want_widgets}"
    assert len(snap["smoke_labelled_total"]["series"]) == nproc, \
        "labelled series lost in the merge"
    g = snap["smoke_depth"]["series"][0]
    assert (g["min"], g["max"], g["n"]) == (0.0, float(nproc - 1), nproc), \
        f"gauge merge: {g}"
    hs = snap["smoke_lat_seconds"]["series"][0]
    assert hs["count"] == 2 * nproc and hs["counts"][0] == nproc, \
        f"histogram merge: {hs}"

    # cross-host identity: all-gather each host's JSON of the MERGED snapshot
    # and require byte equality (single-process: trivially one payload)
    from repro.obs.aggregate import _exchange_payload
    peers = set(_exchange_payload(blob.encode()))
    assert len(peers) == 1, "merged snapshots differ across hosts"

    if metrics_out:
        p = obs.write_snapshot(snap, path=metrics_out)
        if p is not None:   # process 0 only
            back = json.loads(p.read_text())
            assert back["smoke_widgets_total"]["series"][0]["value"] == \
                want_widgets
    print(f"[dist_smoke] obs aggregation OK: {nproc} process(es), "
          f"widgets={int(got_widgets)}, identical snapshots on all hosts",
          flush=True)


def _check_sharded_ckpt(ckpt_dir: str):
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.ckpt.checkpoint import (restore_sharded_checkpoint,
                                       save_sharded_checkpoint)
    from repro.core.quant import QTensor, QuantConfig, quantize_tensor
    from repro.dist import runtime

    devs = np.array(jax.devices())
    n = len(devs)
    if n % 2 == 0 and n >= 4:
        save_mesh = Mesh(devs.reshape(2, n // 2), ("data", "model"))
        w_spec = P("data", "model")
        qt_spec = P(None, "model")
    else:
        save_mesh = Mesh(devs, ("data",))
        w_spec = P("data", None)
        qt_spec = P(None, "data")
    load_mesh = Mesh(devs, ("data",))

    rng = np.random.default_rng(7)
    w_full = rng.normal(size=(8, 16)).astype(np.float32)
    qt_src = rng.normal(size=(64, 8)).astype(np.float32)
    qt = quantize_tensor(jax.numpy.asarray(qt_src),
                         QuantConfig(bits=2, group_size=32))
    qt_full = jax.tree.map(np.asarray, qt)
    tree = {
        "w": runtime.global_put(w_full, NamedSharding(save_mesh, w_spec)),
        "qt": jax.tree.map(
            lambda x: runtime.global_put(
                np.asarray(x), NamedSharding(save_mesh, qt_spec)), qt),
        "t": (runtime.global_put(np.arange(n, dtype=np.float32),
                                 NamedSharding(save_mesh, P("data"))), None),
    }
    save_sharded_checkpoint(ckpt_dir, 1, tree)
    runtime.barrier("ckpt-saved")

    def verify_shards(arr, full):
        for s in arr.addressable_shards:
            np.testing.assert_array_equal(np.asarray(s.data), full[s.index])

    # re-mesh: restore onto the 1-D ("data",) mesh
    shardings = {
        "w": NamedSharding(load_mesh, P("data", None)),
        "qt": QTensor(NamedSharding(load_mesh, P(None, "data")),
                      NamedSharding(load_mesh, P(None, "data")),
                      NamedSharding(load_mesh, P(None, "data")),
                      qt.bits, qt.group_size, qt.shape),
        "t": (NamedSharding(load_mesh, P("data")), None),
    }
    restored, manifest = restore_sharded_checkpoint(ckpt_dir, 1, shardings)
    assert manifest["step"] == 1 and manifest["format"] == 2
    verify_shards(restored["w"], w_full)
    verify_shards(restored["qt"].packed, qt_full.packed)
    verify_shards(restored["qt"].scale, qt_full.scale)
    verify_shards(restored["t"][0], np.arange(n, dtype=np.float32))
    assert restored["t"][1] is None

    # degenerate re-mesh: plain host-local arrays
    local, _ = restore_sharded_checkpoint(ckpt_dir, 1, None)
    np.testing.assert_array_equal(np.asarray(local["w"]), w_full)
    np.testing.assert_array_equal(np.asarray(local["qt"].packed),
                                  qt_full.packed)
    runtime.barrier("ckpt-restored")
    print(f"[dist_smoke] sharded ckpt OK: saved on {save_mesh.shape}, "
          f"restored onto {load_mesh.shape} + host-local", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--coordinator", default=None,
                    help="host:port of process 0 (e.g. 127.0.0.1:12355)")
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--migrate-every", type=int, default=2)
    ap.add_argument("--population", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None,
                    help="SHARED directory for the sharded-checkpoint phase "
                         "(all processes must see the same files)")
    ap.add_argument("--metrics-out", default=None,
                    help="merged metrics snapshot path for the obs phase "
                         "(process 0 writes; default: no file)")
    args = ap.parse_args(argv)

    # must precede any jax computation (CPU collectives backend selection)
    from repro.dist import runtime
    runtime.initialize(args.coordinator, args.num_processes, args.process_id)

    import jax  # noqa: E402  (backend comes up here, after initialize)
    summary = runtime.device_summary()
    print(f"[dist_smoke] {summary}", flush=True)
    if args.num_processes > 1:
        assert jax.process_count() == args.num_processes, \
            f"expected {args.num_processes} processes, got {jax.process_count()}"
    runtime.barrier("bring-up")
    print(f"[dist_smoke] barrier OK across {jax.device_count()} devices",
          flush=True)

    _check_mapped_parity(args.steps, args.migrate_every, args.population)

    _check_obs_aggregation(args.metrics_out)

    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="dist_smoke_ckpt_")
    _check_sharded_ckpt(ckpt_dir)

    print(f"DIST_SMOKE_OK process={jax.process_index()}/"
          f"{jax.process_count()}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
