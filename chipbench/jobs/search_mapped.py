"""Search job over mapped islands: ``jobs/search.py`` with one island per
chip (``SearchConfig(mapped=True)``) and elite migration every
``migrate_every`` steps, as the traffic file sets them.

This job runs ``jobs/search.py``'s ``run`` on a private copy of that module
whose ``search_config`` adds the two settings; the weights, the window, the
reference and the checks are that job's, unchanged. A step makes
``population`` proposals on each of ``islands`` chips, and the search
returns the elite island's best state, which the reference checks.
"""
from __future__ import annotations

import dataclasses

from chipbench import harness

_BASE = harness.load_module(harness.BENCH_DIR / "jobs" / "search.py",
                            "chipbench_job_search_base_mapped")
_search_config = _BASE.search_config


def search_config(traffic: dict, seed: int, steps: int):
    return dataclasses.replace(_search_config(traffic, seed, steps),
                               mapped=True,
                               migrate_every=traffic["migrate_every"])


def run(ctx) -> harness.JobResult:
    _BASE.search_config = search_config
    return _BASE.run(ctx)
