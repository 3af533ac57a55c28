"""Run one benchmark cell on the chip this process is started on.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The last stdout line is the JSON result; the compared numbers and their
limits are the last stderr lines. Without a TPU it exits 3 and prints no
result.
"""
import time

T0 = time.monotonic()   # set-up is timed from here

import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

_ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]
os.environ.setdefault("TPU_STDERR_LOG_LEVEL", "2")

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t0=T0))
