#!/usr/bin/env python3
"""Readings of the check and of its control, on the chip, for a cell.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 11,12,13

For each seed, in one process: one run of the cell as ``bench/run.py``
makes it (set-up, the window at the cell's own load), then, on the
requests that run's check compares, the program's reading (the widest
gap by which a served token's reference logit lies below the reference's
best) and the control's (the same gap for the token that the reference
computed in float8 puts first).  The limit of ``max_logit_gap`` in the
configuration file is set from these readings (see PERF.md).  Not run by
the benchmark's own runs.
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
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    import jax

    from bench import harness, reference, spec
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.resolve(args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        seen = {}

        def both(conf, seed_, seqs):
            """The run's check, with the control read on the same rows."""
            ref = reference.logits_before(conf, seed_, seqs)
            ctl = reference.logits_before(conf, seed_, seqs, control=True)
            seen.update(seqs=seqs, control=reference.control_gap(ref, ctl))
            return {"logit_gap": reference.served_gap(
                ref, [s for _, s in seqs]), "limit": None}

        harness.check = both
        t = time.time()
        rec = harness.run_cell(cell, seed, args.seconds, False, t)
        seqs = seen["seqs"]
        print(json.dumps({
            "cell": cell.name, "seed": seed,
            "program_gap": rec["check"]["logit_gap"],
            "control_gap": seen["control"],
            "requests": len(seqs),
            "served_tokens": sum(len(s) for _, s in seqs),
            "longest": max(len(p) + len(s) for p, s in seqs),
            "failed": rec["failed"], "setup_s": rec["setup_s"],
            "wall_s": time.time() - t}), flush=True)


if __name__ == "__main__":
    main()
