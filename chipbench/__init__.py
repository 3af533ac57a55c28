"""On-chip benchmark: data-driven cells for the search and serving jobs.

``python chipbench/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it is started
on and prints one JSON result line. See ``chipbench/harness.py``.
"""
