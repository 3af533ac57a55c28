"""Sweep of offered load for a serving cell: find the knee once, on the chip.

    python3 chipbench/knee.py --config opt-1.3b --traffic sessions \
        --seed 7 --seconds 50 --rates 0.05,0.1,0.15,0.2

One process builds the server of the configuration
``chipbench/configs/<config>.json`` once and replays the traffic mix
``chipbench/traffic/<traffic>.json`` at each session rate in turn (prefix
cache emptied between rates), on one chip. A rate is
sustained when the queue at the end of the window is no longer than at its
middle; the knee is the highest sustained rate, and the cell's traffic file
takes about four fifths of it. One JSON line per rate on stdout. Not run by
the benchmark's own runs.
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


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    config = harness.load_json(harness.BENCH_DIR / "configs"
                               / f"{args.config}.json")
    traffic = harness.load_traffic(args.traffic)
    workload = {"name": f"knee.{args.config}.{args.traffic}", "chips": 1}
    harness.setup_compile_cache()
    try:
        harness.device_info(1)
    except harness.NoChip as e:
        harness.log(f"knee: {e}")
        return harness.EXIT_NO_CHIP
    serve = harness.job_module("serve")
    gen = harness.traffic_generator(traffic)
    ctx = harness.RunContext(workload=workload, config=config,
                             traffic=traffic, generator=gen, seed=args.seed,
                             seconds=args.seconds, trace=False, t_process=T0)
    srv = traffic["server"]
    cfg, params_q = serve.build(ctx)
    server = serve.make_server(params_q, cfg, srv)
    serve.warm(server, config["vocab_size"], srv["page_size"],
               srv["prefill_chunk_pages"])
    for rate in [float(r) for r in args.rates.split(",")]:
        turns = gen.make(traffic, config, args.seed, args.seconds, rate=rate)
        s0 = dict(server.batcher.stats)
        recs, info = serve.replay(server, turns, args.seconds,
                                  traffic["drain_s"])
        lat = serve.latency(recs, info["t0"], args.seconds)
        s1 = server.batcher.stats
        if server.batcher.prefix is not None:
            server.batcher.prefix.clear()
        saved = s1["prefill_tokens_saved"] - s0["prefill_tokens_saved"]
        done = s1["prefill_tokens"] - s0["prefill_tokens"]
        print(json.dumps({
            "session_rate_per_s": rate, "requests": len(turns),
            "request_rate_per_s": len(turns) / args.seconds,
            "sustained": info["queue_end"] <= info["queue_mid"],
            **{k: info[k] for k in ("queue_mid", "queue_end", "late_max_s")},
            **lat, "evictions": s1["evictions"] - s0["evictions"],
            "prefix_hit_share": saved / max(saved + done, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
