"""Readings that set the limits of ``correct`` for the DeepSeek-V3 search job
(``jobs/search_mla_moe.py``): the program's, and the float8 control's on the
same states, as ``control.py`` takes them for OPT.

    python3 chipbench/control_mla_moe.py --workload <name> --seeds 1,2 \
        --seconds 5

In one process, for each seed: one run of the cell's job (its timed path at
the cell's size, a short window), its compared numbers and routing flip
share, and the control's cross-entropy and activation MSE of the starting
and the returned state as relative gaps to the float32 reference: the plain
reference with every matmul's inputs rounded to float8 e4m3, the precision
below the bfloat16 inputs the configuration's matmuls take at JAX's default
precision on the TPU. One JSON line per seed on stdout.
"""
import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

_ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from chipbench import harness  # noqa: E402

CONTROL = "float8_e4m3fn"   # the precision below the matmuls' bfloat16


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    for seed in [int(s) for s in args.seeds.split(",")]:
        run = argparse.Namespace(workload=args.workload, seed=seed,
                                 seconds=args.seconds, trace=0,
                                 t0=time.monotonic())
        try:
            _, _, _, ctx, res = harness.run_job(run)
        except harness.NoChip as e:
            harness.log(f"control: {e}")
            return harness.EXIT_NO_CHIP
        import jax.numpy as jnp

        from chipbench import weights_mla_moe
        job = harness.job_module(ctx.traffic["job"])
        art = res.artifacts
        calib = jnp.asarray(ctx.generator.make(ctx.traffic, ctx.config,
                                               seed))
        params = weights_mla_moe.make_weights(ctx.config,
                                              harness.seed_key(seed), calib)
        low = job.reference_terms(ctx.config, params, calib,
                                  art["transforms"], art["n_match"],
                                  matmul_dtype=CONTROL)
        ce, mse = job._BASE.rel_gaps(low, art["reference"])
        row = {"seed": seed,
               "program": {c.name: c.value for c in res.checks},
               "routing_flip_share": art["routing_flip_share"],
               "memory_peak_bytes": res.memory_peak_bytes,
               "proposals_per_s": res.end_to_end["search_proposals_per_s"],
               "control": {"ce_gap": ce, "mse_gap": mse}}
        print(json.dumps(row), flush=True)
        del res, params
    return 0


if __name__ == "__main__":
    sys.exit(main())
