"""Multi-host aggregation: one merged metrics report for the whole fleet.

``dist_snapshot()`` turns N per-process registries into ONE snapshot,
identical on every host, using only the existing ``repro.dist`` machinery
(the same shard_map all-gather idiom as the mapped island search — no gRPC
side channel, no extra dependency):

  1. each process serializes its local ``Registry.snapshot()`` to JSON bytes;
  2. two all-gathers over a ("hosts",) mesh spanning every global device —
     first the payload lengths (so all processes agree on one padded width),
     then the padded payload rows themselves (as int32: exact for byte
     values, and the least exotic dtype for the CPU gloo backend);
  3. every host decodes all rows, dedupes by process index (a process with
     k local devices contributes k identical rows) and folds the per-process
     snapshots with ``merge_snapshots`` in process order.

Because the gathered bytes are identical everywhere and the merge is
deterministic, every host computes the SAME aggregate — the property the CI
2-process lane asserts. Counters sum, gauges keep (min, max, sum, n),
histograms add bucket-wise (exact: fixed edges).

``write_snapshot()`` is the process-0 commit: it writes (or name-merges
into) ``artifacts/obs/metrics.json`` so successive drivers in one CI lane —
the search bench, then the serving bench — accumulate into one report the
way ``BENCH_*.json`` rows do.
"""
from __future__ import annotations

import json
import pathlib
from typing import Optional

from repro.obs.registry import (Registry, get_registry, merge_snapshots)

__all__ = ["dist_snapshot", "write_snapshot", "DEFAULT_METRICS_PATH"]

DEFAULT_METRICS_PATH = "artifacts/obs/metrics.json"

_AXIS = "hosts"
_PAD = 4096          # payload rows padded to a multiple: bounds recompiles
_gather_fns: dict = {}


def _gather_rows(rows):
    """All-gather one (n_devices, L) int32 row per device; every process
    gets the full matrix. Compiled once per (topology, L)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from jax import shard_map

    devs = jax.devices()
    key = (tuple(d.id for d in devs), rows.shape[1])
    if key not in _gather_fns:
        mesh = Mesh(np.array(devs), (_AXIS,))
        shd = NamedSharding(mesh, P(_AXIS))
        fn = jax.jit(shard_map(
            lambda r: jax.lax.all_gather(r[0], _AXIS),
            mesh=mesh, in_specs=(P(_AXIS),), out_specs=P(),
            check_vma=False))

        def run(local_rows):
            # every process fills ALL of its addressable rows with its own
            # payload; make_array_from_callback touches only local shards
            arr = jax.make_array_from_callback(
                local_rows.shape, shd,
                lambda idx: np.ascontiguousarray(local_rows[idx]))
            return np.asarray(fn(arr))

        _gather_fns[key] = run
    return _gather_fns[key](rows)


def _exchange_payload(payload: bytes) -> list:
    """Returns every process's payload bytes, ordered by device id (rows of
    the same process repeat — callers dedupe by the embedded pid)."""
    import jax
    import numpy as np

    n = len(jax.devices())
    lens = np.full((n, 1), len(payload), np.int32)
    all_lens = _gather_rows(lens)[:, 0]
    width = -(-int(all_lens.max()) // _PAD) * _PAD
    rows = np.zeros((n, width), np.int32)
    rows[:, : len(payload)] = np.frombuffer(payload, np.uint8)
    gathered = _gather_rows(rows)
    return [gathered[i, : all_lens[i]].astype(np.uint8).tobytes()
            for i in range(n)]


def dist_snapshot(registry: Optional[Registry] = None, *,
                  force_gather: bool = False) -> dict:
    """Fleet-merged snapshot, identical on every process.

    Single-process runs skip the collectives and return the local snapshot
    (already in canonical mergeable form); ``force_gather=True`` exercises
    the gather path on a single-process multi-device topology (tests)."""
    reg = registry if registry is not None else get_registry()
    local = reg.snapshot()

    import jax
    if jax.process_count() == 1 and not force_gather:
        return merge_snapshots(local, {})   # normalize through the merge

    payload = json.dumps(
        {"pid": jax.process_index(), "snap": local}).encode()
    per_pid: dict = {}
    for raw in _exchange_payload(payload):
        msg = json.loads(raw.decode())
        per_pid.setdefault(int(msg["pid"]), msg["snap"])
    merged: dict = {}
    for pid in sorted(per_pid):
        merged = merge_snapshots(merged, per_pid[pid])
    return merged


def write_snapshot(snapshot: Optional[dict] = None,
                   path=DEFAULT_METRICS_PATH, *,
                   registry: Optional[Registry] = None,
                   merge: bool = True) -> Optional[pathlib.Path]:
    """Write a snapshot to ``path`` (process 0 only; other processes are a
    no-op and return None).

    ``snapshot=None`` takes ``dist_snapshot(registry)`` first — the one-call
    "fleet emits one report" path. With ``merge=True`` an existing file's
    metrics are kept unless this snapshot carries the same name (row-level
    replace, like the BENCH_*.json writers), so sequential drivers in one CI
    lane accumulate into a single report without double counting."""
    if snapshot is None:
        snapshot = dist_snapshot(registry)

    import jax
    if jax.process_count() > 1 and jax.process_index() != 0:
        return None
    p = pathlib.Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    out = snapshot
    if merge and p.exists():
        try:
            prev = json.loads(p.read_text())
        except ValueError:
            prev = {}
        out = {**prev, **snapshot}
        out = {k: out[k] for k in sorted(out)}
    p.write_text(json.dumps(out, indent=1, sort_keys=True))
    return p
