"""Parallel discrete-search framework (population × islands × objectives).

The paper's Algorithm 1 evaluates ONE proposal per step on one chain; this
package scales it along orthogonal axes while keeping the single-chain
greedy hill-climb as an exact special case:

- ``api.py``        — ``repro.search.run``, the one front door (adapter
  dispatch, hybrid two-phase composites, objective resolution);
- ``population.py`` — K candidate transforms per step for the sampled unit;
- ``install.py``    — O(unit)-memory candidate install: ONE fake-quant
  stack + K per-unit buffers via ``dynamic_update_slice`` tree surgery;
- ``anneal.py``     — temperature schedules + the Metropolis acceptance rule
  (T=0 reduces bit-for-bit to the legacy accept-iff-better);
- ``islands.py``    — independent populations with counter-based per-island
  key streams and elite migration on a fixed cadence; with
  ``shard_calib=True`` each island climbs on its own calibration slice;
- ``tabu.py``       — tried-point dedup memory replaying cached scalars for
  proposals already evaluated at the current chain state;
- ``engine.py``     — the loop that composes all of the above, one staged
  propose/build/eval/pick step pipeline at every K.

Objectives are pluggable (``repro.core.objective``): ``"ce"`` (the paper's
Eqn. 23 default), ``"kl"``, ``"swd_actmatch"``, ``"saliency_ce"``, or any
registered/passed ``Objective`` instance.
"""
from repro.search.anneal import accept, temperature_schedule
from repro.search.api import run
from repro.search.install import tree_install_unit
from repro.search.islands import IslandState, migrate
from repro.search.population import candidate_keys
from repro.search.tabu import TabuMemory

__all__ = ["run", "temperature_schedule", "accept", "IslandState", "migrate",
           "candidate_keys", "tree_install_unit", "TabuMemory"]
