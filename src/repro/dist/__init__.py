"""Distributed substrate: sharding rules, fault tolerance, compressed
collectives, and sequence-sharded decode attention.

Layout:
    runtime.py     — jax.distributed bring-up (coordinator/process env vars),
                     psum barrier, device introspection, global placement
    sharding.py    — PartitionSpec trees over the ("data", "model") mesh
    fault.py       — straggler watchdog, checkpoint-restore resilient loop,
                     preemption-signal → checkpoint-and-barrier hook
    collectives.py — group-quantized (compressed) all-reduce + the island
                     search's elite exchange (argmin_allgather scalar race,
                     elite_broadcast state move)
    attention.py   — log-sum-exp partial-softmax merge for sharded KV decode

Everything here is mesh-shape driven and divisibility-aware: a dim that does
not divide its mesh axis falls back to replication instead of failing, so the
same rules serve every assigned architecture (14-head internvl2 included).
"""
from repro.dist.sharding import (ShardingRules, param_specs, opt_state_specs,
                                 cache_specs, data_spec, to_shardings)
from repro.dist.fault import (StepWatchdog, PreemptionGuard, run_resilient,
                              remesh_restore)
from repro.dist.collectives import (compressed_psum, argmin_allgather,
                                    elite_broadcast)
from repro.dist.attention import (partial_decode_attention, merge_partials,
                                  sharded_decode_attention,
                                  sharded_paged_decode_attention)
from repro.dist import runtime

__all__ = [
    "ShardingRules", "param_specs", "opt_state_specs", "cache_specs",
    "data_spec", "to_shardings",
    "StepWatchdog", "PreemptionGuard", "run_resilient", "remesh_restore",
    "compressed_psum", "argmin_allgather", "elite_broadcast",
    "partial_decode_attention", "merge_partials", "sharded_decode_attention",
    "sharded_paged_decode_attention",
    "runtime",
]
