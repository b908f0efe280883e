"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Modules:
  alexnet_table1     — paper Table 1 (per-layer ops & storage)
  decomposition_fig6 — paper Fig. 6 (conv1 decomposition under 128 KB)
  throughput_table2  — paper Table 2 (GOPS / TOPS/W, both voltage points)
  kernel_bench       — Pallas kernels vs XLA references
  streaming_bench    — tiled streaming executor end-to-end

``--json-out BENCH_streaming.json`` additionally persists the streaming
records machine-readably (the perf trajectory future PRs diff against —
``benchmarks/regression_gate.py`` fails CI when any of its ratchets
regress: grouped/int8/batched speedups, launch counts, DRAM traffic);
``--smoke`` is the reduced-reps CI configuration and
``--only`` restricts which modules run, e.g.::

    python -m benchmarks.run --only streaming_bench --smoke \
        --json-out BENCH_streaming.json
"""
import argparse
import json
import platform
import sys
import traceback


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced repeats per timing (CI smoke mode)")
    ap.add_argument("--json-out", default=None, metavar="PATH",
                    help="write streaming records as JSON (runs "
                         "streaming_bench even if --only excludes it)")
    ap.add_argument("--only", default=None, metavar="MOD[,MOD]",
                    help="run only these benchmark modules")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (alexnet_table1, decomposition_fig6,
                            kernel_bench, network_sweep,
                            streaming_bench, throughput_table2)
    modules = [alexnet_table1, decomposition_fig6, throughput_table2,
               network_sweep, kernel_bench, streaming_bench]
    if args.only:
        wanted = {m.strip() for m in args.only.split(",")}
        known = {m.__name__.rsplit(".", 1)[-1] for m in modules}
        unknown = wanted - known
        if unknown:
            raise SystemExit(f"unknown benchmark module(s): "
                             f"{sorted(unknown)} (have {sorted(known)})")
        modules = [m for m in modules
                   if m.__name__.rsplit(".", 1)[-1] in wanted]

    print("name,us_per_call,derived")
    failed = 0
    streaming_records = None
    for mod in modules:
        try:
            if mod is streaming_bench:
                streaming_records = mod.run_structured(smoke=args.smoke)
                rows = mod.format_rows(streaming_records)
            else:
                rows = mod.run()
            for row in rows:
                print(row)
        except Exception:
            failed += 1
            print(f"{mod.__name__},0,FAILED", file=sys.stderr)
            traceback.print_exc()

    if args.json_out and not failed:
        if streaming_records is None:
            streaming_records = streaming_bench.run_structured(
                smoke=args.smoke)
        import jax
        payload = {
            "benchmark": "streaming",
            "smoke": args.smoke,
            "jax_backend": jax.default_backend(),
            "platform": platform.platform(),
            "records": streaming_records,
        }
        with open(args.json_out, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"wrote {args.json_out} "
              f"({len(streaming_records)} records)", file=sys.stderr)

    if failed:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
