#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up, a measured window of ``--seconds``, then the check against the
plain reference.  With ``--trace 0`` the result carries the cell's
end-to-end metrics (host clock, profiler off); with ``--trace 1`` the
window is traced and the result carries its per-layer metrics, the
device's busy and traced seconds and a breakdown.  Progress and counts
go to earlier lines; the last line of standard output is the result as
one JSON object, and the last lines of standard error give each number
the check compared beside its limit.  Exits 3, with no result, when JAX
finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse      # noqa: E402
import json          # noqa: E402
import sys           # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TRACE_DIR = ROOT / ".bench_trace"


def checks(rec):
    chk = rec["check"]
    return {"logit_gap": {"value": chk["logit_gap"], "limit": chk["limit"]},
            "failed_requests": {"value": rec["failed"], "limit": 0}}


def is_correct(rec) -> bool:
    return all(c["limit"] is not None and c["value"] is not None
               and c["value"] <= c["limit"] for c in checks(rec).values())


def info(rec) -> None:
    c = rec["counters"]
    print(f"[bench] {rec['cell']} seed {rec['seed']}: window "
          f"{rec['window_s']:.3f} s, {rec['attempted']} requests due in it, "
          f"{rec['failed']} failed, {rec['tokens']} tokens streamed; "
          f"engine: {c['tokens_out']} tokens, {c['decode_steps']} decode "
          f"steps, {c['chunk_batch_calls']} chunk steps, "
          f"{c['prefix_hits']} prefix hits ({c['prefix_cached_tokens']} "
          f"tokens), {c['preemptions']} preemptions")
    print(f"[bench] set-up {rec['setup_s']:.3f} s: "
          f"{rec['compiles_setup']} compiles ({rec['compile_secs']:.2f} s, "
          f"{rec['cache_hits']} from the persistent cache at "
          f"{rec.get('cache_dir', 'none')}); compiles inside the window: "
          f"{rec['compiles_in_window']} (must be 0)")
    ph = rec["setup_phases"]
    print("[bench] set-up phases (s): " + ", ".join(
        f"{name} {t - prev:.3f}" for (_, prev), (name, t) in zip(ph, ph[1:]))
        + f"; process start to {ph[0][0]} {ph[0][1]:.3f}; set-up served "
        f"{rec['setup_counters']['chunk_batch_calls']} chunk steps and "
        f"{rec['setup_counters']['decode_steps']} decode steps")
    print(f"[bench] bytes_in_use with weights and pool "
          f"{rec['memory_after_engine']}; peak_bytes_in_use after set-up "
          f"{rec['memory_peak_setup']}, after the window "
          f"{rec['memory_peak_bytes']}; KV pool {rec['pool_pages']} pages, "
          f"{rec['pool_bytes']} B")
    chk = rec["check"]
    print(f"[bench] check: {chk['requests']} requests the window "
          f"finished, {chk['served_tokens']} served tokens, longest "
          f"sequence {chk.get('longest', 0)}, reference {rec['check_s']:.3f} s")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench import spec
    from bench.harness import NoChip, run_cell
    cell = spec.resolve(args.workload)
    import jax
    from repro.launch.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    # every program, however quick to compile, is kept, so that a run
    # after the first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        rec = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       T_START, trace_dir=str(TRACE_DIR))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    rec["cache_dir"] = cache_dir
    info(rec)
    device = dict(rec["device"])
    out = {"correct": is_correct(rec), "attempted": rec["attempted"],
           "failed": rec["failed"]}
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = spec.metric_reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        elif not args.trace:
            raise RuntimeError(f"end-to-end metric {m['name']} has no "
                               f"reading in this run")
    out.update(metrics=metrics, device=device)
    if args.trace:
        tr = rec["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        print(f"[bench] trace: programs {json.dumps(tr['programs'])}; "
              f"prefix-kernel device time {tr['kernel_s']:.6f} s")
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    chk = checks(rec)
    out["checks"] = chk
    print(json.dumps(out, allow_nan=True))
    for name, c in chk.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
