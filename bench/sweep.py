#!/usr/bin/env python3
"""Sweep an open-loop cell's arrival rate on the chip, to find its knee.

    python3 bench/sweep.py --workload <cell> --seconds <s> --rates 0.5,1,2

Each rate is one run of the cell with its mix's ``rate_per_s`` replaced,
in one process.  For each it prints the requests due and served, the
first-token latencies of the window's first and second halves, and how
many requests still waited for a first token when the window closed: a
rate the system sustains keeps the second half's latency near the
first's and leaves no backlog growing.  Run by hand, once, when a cell is
defined; the cell then fixes its rate.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=20240506)
    args = ap.parse_args()
    import jax
    import numpy as np

    from bench import harness, spec
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for rate in [float(r) for r in args.rates.split(",")]:
        cell = spec.resolve(args.workload)
        cell.traffic["rate_per_s"] = rate
        harness.check = lambda *a: {"logit_gap": None, "limit": None}
        rec = harness.run_cell(cell, args.seed, args.seconds, False,
                               time.time())
        ttft = np.asarray(rec["ttft_s"]) * 1e3
        half = len(ttft) // 2
        print(json.dumps({
            "rate_per_s": rate, "due": rec["attempted"],
            "tokens_per_s": rec["tokens"] / rec["window_s"],
            "ttft_ms_p50": float(np.percentile(ttft, 50)) if half else None,
            "ttft_ms_p90": float(np.percentile(ttft, 90)) if half else None,
            "ttft_ms_first_half_p50":
                float(np.median(ttft[:half])) if half else None,
            "ttft_ms_second_half_p50":
                float(np.median(ttft[half:])) if half else None,
            "waiting_at_close": rec["waiting_at_close"],
            "preemptions": rec["counters"]["preemptions"],
            "prefix_hits": rec["counters"]["prefix_hits"]}), flush=True)


if __name__ == "__main__":
    main()
