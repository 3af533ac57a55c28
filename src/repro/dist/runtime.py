"""Multi-host runtime: ``jax.distributed`` init, psum barrier, introspection.

The rest of ``repro.dist`` is written against an already-alive mesh; this
module is the piece that brings the mesh up. Three responsibilities:

  - ``initialize()``   — env/flag driven ``jax.distributed`` bring-up
                         (coordinator address, process id/count). On CPU the
                         gloo TCP collectives backend is selected first, since
                         the default CPU client cannot run cross-process
                         computations at all. A single-process call (no
                         coordinator configured anywhere) is a NO-OP, so every
                         existing single-host entry point keeps working
                         untouched.
  - ``barrier()``      — a real synchronization point built on a tiny psum
                         over a host axis: every device contributes 1, every
                         process checks the sum equals the global device
                         count. No gRPC side channel, no timeout knob — if a
                         host is gone the collective itself fails, which is
                         exactly the signal the fault layer wants.
  - introspection      — ``process_index`` / ``process_count`` /
                         ``device_summary()`` plus the ``global_put`` /
                         ``replicated`` helpers that place host-local numpy
                         values onto a (possibly multi-process) mesh without
                         ever touching non-addressable shards
                         (``jax.make_array_from_callback`` materializes only
                         the local ones).

Env vars (flags win over env, env wins over nothing):
    REPRO_COORDINATOR   — "host:port" of process 0 (also accepts
                          JAX_COORDINATOR_ADDRESS)
    REPRO_NUM_PROCESSES — world size
    REPRO_PROCESS_ID    — this process's rank
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import numpy as np

from jax import shard_map

__all__ = ["initialize", "is_distributed", "process_index", "process_count",
           "local_device_count", "global_device_count", "device_summary",
           "barrier", "global_put", "replicated"]

_AXIS = "hosts"
_initialized = False


def _env(name: str, alt: Optional[str] = None) -> Optional[str]:
    v = os.environ.get(name)
    if v is None and alt is not None:
        v = os.environ.get(alt)
    return v


def initialize(coordinator: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               local_device_ids=None) -> bool:
    """Bring up ``jax.distributed`` (idempotent). Returns True iff a
    multi-process runtime is (now) alive.

    Resolution order per field: explicit argument, then env var
    (``REPRO_COORDINATOR`` / ``JAX_COORDINATOR_ADDRESS``,
    ``REPRO_NUM_PROCESSES``, ``REPRO_PROCESS_ID``). When no coordinator is
    configured anywhere — the plain single-host invocation — this is a no-op
    and every query below answers from the local backend (process 0 of 1).

    MUST run before the first jax computation: on CPU the gloo collectives
    client has to be selected before the backend exists (the default CPU
    client refuses cross-process computations outright).
    """
    global _initialized
    coordinator = coordinator or _env("REPRO_COORDINATOR",
                                      "JAX_COORDINATOR_ADDRESS")
    if num_processes is None and _env("REPRO_NUM_PROCESSES"):
        num_processes = int(_env("REPRO_NUM_PROCESSES"))
    if process_id is None and _env("REPRO_PROCESS_ID"):
        process_id = int(_env("REPRO_PROCESS_ID"))
    if _initialized:
        return jax.process_count() > 1
    if coordinator is None:
        if process_id not in (None, 0):
            raise ValueError(
                f"process_id={process_id} configured but no coordinator "
                f"address — set REPRO_COORDINATOR (a silently single-process "
                f"rank would split-brain the fleet)")
        return False  # single-process fallback: nothing to bring up
    if num_processes is None or num_processes < 1:
        # a configured coordinator with no world size must NOT degrade to
        # single-process mode: every rank would believe it is 0-of-1 and
        # fight over the same checkpoint files
        raise ValueError(
            f"coordinator {coordinator!r} configured but num_processes is "
            f"{num_processes!r} — set REPRO_NUM_PROCESSES")
    if num_processes == 1:
        return False  # explicit world of one: valid single-process run

    # CPU backend: the default client cannot run multi-process computations;
    # gloo (TCP) can. Must be set before backend init; older jax spells the
    # knob differently or lacks it, in which case distributed CPU is simply
    # unavailable and initialize() below will surface the real error.
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except (AttributeError, ValueError):
        pass

    kwargs = {}
    if local_device_ids is not None:
        kwargs["local_device_ids"] = local_device_ids
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id, **kwargs)
    _initialized = True
    return True


def is_distributed() -> bool:
    return jax.process_count() > 1


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def local_device_count() -> int:
    return jax.local_device_count()


def global_device_count() -> int:
    return jax.device_count()


def device_summary() -> dict:
    """Process-local view of the global topology (one dict per host; the CI
    lane prints it from every process as the bring-up receipt)."""
    local = jax.local_devices()
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": [d.id for d in local],
        "local_device_count": len(local),
        "global_device_count": jax.device_count(),
        "platform": local[0].platform if local else "none",
    }


def global_put(x, sharding):
    """Place a host-local numpy/jax value onto ``sharding`` (which may span
    processes). Every process must pass the same logical value; only the
    locally-addressable shards are materialized."""
    x = np.asarray(x)
    return jax.make_array_from_callback(x.shape, sharding,
                                        lambda idx: np.ascontiguousarray(x[idx]))


def replicated(x, mesh):
    """``global_put`` with a fully-replicated spec on ``mesh``."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.tree.map(
        lambda v: global_put(v, NamedSharding(mesh, P())), x)


_barrier_fns: dict = {}


def _barrier_fn():
    """Compiled psum-of-ones over every global device (cached per topology)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    key = tuple(d.id for d in devs)
    if key not in _barrier_fns:
        mesh = Mesh(np.array(devs), (_AXIS,))
        sharding = NamedSharding(mesh, P(_AXIS))

        f = jax.jit(shard_map(
            lambda v: jax.lax.psum(v.sum(), _AXIS),
            mesh=mesh, in_specs=(jax.sharding.PartitionSpec(_AXIS),),
            out_specs=jax.sharding.PartitionSpec(), check_vma=False))

        def run():
            ones = jax.make_array_from_callback(
                (len(devs),), sharding, lambda idx: np.ones((1,), np.float32))
            return int(np.asarray(f(ones)))

        _barrier_fns[key] = run
    return _barrier_fns[key]


def barrier(tag: str = "") -> None:
    """Block until every process reaches this point.

    Implemented as a tiny psum over the host axis: each of the N global
    devices contributes 1 and every process verifies the all-reduced total is
    N — a wrong total means a peer ran a DIFFERENT collective (program
    divergence), which is worth failing loudly on rather than deadlocking
    later. Single-process runs execute the same psum on the local mesh (cheap,
    and it keeps the code path identical instead of special-cased).
    """
    from repro import obs
    with obs.trace_span("dist.barrier", tag=tag,
                        hist=obs.histogram("dist_barrier_seconds",
                                           "Barrier wait latency")):
        total = _barrier_fn()()
    n = jax.device_count()
    if total != n:
        raise RuntimeError(
            f"barrier({tag!r}) psum mismatch: got {total}, want {n} — "
            f"processes are running divergent programs")
