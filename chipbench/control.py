"""Readings that set the limits of ``correct``: the program's, and the
control's on the same states or tokens.

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 --seconds 5

In one process, for each seed: one run of the cell's job (its timed path at
the cell's size, a short window), its compared numbers, and the control's:
the plain reference with every matmul's inputs rounded to float8 e4m3 (the
precision below the bfloat16 inputs the configuration's matmuls take at
JAX's default precision on the TPU) in the program's place.

- search: the control's cross-entropy and activation MSE of the starting and
  the returned state, as relative gaps to the float32 reference.
- serve: at each position that produced a served token, the token the
  control puts first, read as its gap below the float32 reference's best.

One JSON line per seed on stdout. Not run by the benchmark's own runs.
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


def search_control(ctx, res) -> dict:
    import jax.numpy as jnp

    from chipbench import weights
    search = harness.job_module("search")
    art = res.artifacts
    params = weights.make_weights(ctx.config, harness.seed_key(ctx.seed))
    calib = jnp.asarray(ctx.generator.make(ctx.traffic, ctx.config,
                                           ctx.seed))
    low = search.reference_terms(ctx.config, params, calib,
                                 art["transforms"], art["n_match"],
                                 matmul_dtype=CONTROL)
    ce, mse = search.rel_gaps(low, art["reference"])
    return {"ce_gap": ce, "mse_gap": mse}


def serve_control(ctx, res) -> dict:
    import numpy as np

    from chipbench import weights
    serve = harness.job_module("serve")
    art = res.artifacts
    params = weights.make_weights(ctx.config, harness.seed_key(ctx.seed))
    low = serve.reference_logits(ctx.config, params, art["sample"],
                                 ctx.traffic["server"]["context"],
                                 matmul_dtype=CONTROL)
    tokens = serve.control_sample(art["sample"], low)
    gaps = serve.served_gaps(art["reference_logits"], tokens)
    flips = sum(int(np.sum(lg.argmax(axis=-1) != np.asarray(s)))
                for lg, (_, s) in zip(art["reference_logits"], tokens))
    return {"served_logit_gap": max(gaps), "flipped_tokens": flips,
            "served_tokens": len(gaps)}


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
        control = {"search": search_control, "serve": serve_control}
        row = {"seed": seed,
               "program": {c.name: c.value for c in res.checks},
               "control": control[ctx.traffic["job"]](ctx, res)}
        print(json.dumps(row), flush=True)
        del res
    return 0


if __name__ == "__main__":
    sys.exit(main())
