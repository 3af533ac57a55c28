"""Benchmark harness entry point — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast]

Prints ``name,us_per_call,derived`` CSV rows (assignment format) and writes
each table's JSON to artifacts/benchmarks/. See DESIGN.md §7 for the
paper-table ↔ benchmark mapping.
"""
import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="fewer search steps (CI-speed run)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: table1,table2,table3,table4,"
                         "fig1,kernels,serving,search")
    args = ap.parse_args()
    steps = 120 if args.fast else 400

    from benchmarks import (table1_main, table2_ablation, table3_bits,
                            table4_actmatch, fig1_curves, kernel_bench,
                            serving_bench)

    def search_mem_bench():
        # K=8 candidate eval through the O(unit) dynamic-slice install: a
        # search_unit_install/ row with peak_live_bytes (jax.live_arrays()
        # delta) in BENCH_search.json
        from repro.launch.search import run_search_bench
        run_search_bench(steps=4 if args.fast else 16, population=8,
                         n_seqs=2, seq_len=64, measure_mem=True)

    jobs = {
        "table1": lambda: table1_main.run(search_steps=steps),
        "table2": lambda: table2_ablation.run(search_steps=max(steps * 3 // 4, 80)),
        "table3": lambda: table3_bits.run(search_steps=max(steps * 5 // 8, 80)),
        "table4": lambda: table4_actmatch.run(search_steps=max(steps * 3 // 4, 80)),
        "fig1": lambda: fig1_curves.run(search_steps=steps),
        "kernels": kernel_bench.run,
        "serving": serving_bench.run,
        "search": search_mem_bench,
    }
    only = set(args.only.split(",")) if args.only else set(jobs)
    t0 = time.time()
    print("name,us_per_call,derived")
    for name, fn in jobs.items():
        if name not in only:
            continue
        t1 = time.time()
        fn()
        print(f"# {name} done in {time.time()-t1:.1f}s", file=sys.stderr)
    print(f"# all benchmarks in {time.time()-t0:.1f}s", file=sys.stderr)
    # attach the final merged metrics snapshot next to the bench tables (the
    # serving bench resets the registry mid-run; this captures what remains
    # after the last job plus whatever earlier jobs already merged into it)
    from repro import obs
    p = obs.write_snapshot()
    print(f"# metrics snapshot -> {p}", file=sys.stderr)


if __name__ == "__main__":
    main()
