# One function per paper table. Print ``name,us_per_call,derived`` CSV.
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: fig1,fig6,fig7,fig9,table1,"
                         "fig11,kernels,roofline,cache,fusion,rewrite,tiling,"
                         "transfer,shard,serve,resilience,online")
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args()

    from repro.device import use_compile_cache

    use_compile_cache()
    from . import (bench_cache, bench_fusion, bench_online, bench_resilience,
                   bench_rewrite, bench_serve, bench_shard, bench_tiling,
                   bench_transfer,
                   fig1_gemm,
                   fig6_robustness, fig7_ablation, fig9_python,
                   fig11_cloudsc_full, kernels_micro, roofline_report,
                   table1_cloudsc)

    suites = {
        "cache": lambda: bench_cache.run(repeats=args.repeats),
        "fusion": lambda: bench_fusion.run(repeats=args.repeats),
        "rewrite": lambda: bench_rewrite.run(repeats=args.repeats),
        "tiling": lambda: bench_tiling.run(repeats=args.repeats),
        "transfer": lambda: bench_transfer.run(repeats=args.repeats),
        "shard": lambda: bench_shard.run(repeats=args.repeats),
        "serve": lambda: bench_serve.run(repeats=args.repeats),
        "resilience": lambda: bench_resilience.run(repeats=args.repeats),
        "online": lambda: bench_online.run(repeats=args.repeats),
        "fig1": lambda: fig1_gemm.run(repeats=args.repeats),
        "fig6": lambda: fig6_robustness.run(repeats=args.repeats),
        "fig7": lambda: fig7_ablation.run(repeats=args.repeats),
        "fig9": lambda: fig9_python.run(repeats=args.repeats),
        "table1": lambda: table1_cloudsc.run(repeats=args.repeats),
        "fig11": lambda: fig11_cloudsc_full.run(repeats=args.repeats),
        "kernels": lambda: kernels_micro.run(repeats=args.repeats),
        "roofline": lambda: roofline_report.run(),
    }
    only = args.only.split(",") if args.only else list(suites)
    unknown = sorted(set(only) - set(suites))
    if unknown:
        ap.error(f"unknown suite(s): {', '.join(unknown)} "
                 f"(valid: {', '.join(suites)})")
    print("name,us_per_call,derived")
    failed = []
    for name in only:
        try:
            suites[name]()
        except Exception:
            traceback.print_exc()
            failed.append(name)
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
