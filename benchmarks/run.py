"""Benchmark harness — one function per paper table.

Prints ``name,value,derived`` CSV rows:
  quality.*      Table 1 (perplexity: fp32 vs Q8_0 vs Q4_0 vs half-size)
  throughput.*   Tables 2-3 (tok/s + ms/token per weight format)
  energy.*       Tables 4-6 (modeled mWh/token on TPU v5e)
  kernelbench.*  Appendix A.2 (per-stage timings at the paper's shapes)
  roofline.*     §Roofline terms per (arch x shape) from the dry-run
"""

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: quality,throughput,energy,kernels,"
                         "decode,engine,roofline")
    ap.add_argument("--quick", action="store_true",
                    help="smaller step/token budgets")
    args = ap.parse_args()
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    which = set(args.only.split(",")) if args.only else {
        "kernels", "energy", "roofline", "throughput", "quality"}

    print("name,value,derived")
    if "kernels" in which:
        from benchmarks import kernels_bench
        kernels_bench.run()
    if "decode" in which or "kernels" in which:
        from benchmarks import kernels_bench
        kernels_bench.run_decode()
    if "engine" in which:
        from benchmarks import engine_bench
        engine_bench.run()
    if "energy" in which:
        from benchmarks import energy
        energy.run()
    if "roofline" in which:
        from benchmarks import roofline_table
        roofline_table.run()
    if "throughput" in which:
        from benchmarks import throughput
        throughput.run(tokens=8 if args.quick else 32)
    if "quality" in which:
        from benchmarks import quality
        quality.run(steps=60 if args.quick else 250)


if __name__ == "__main__":
    main()
