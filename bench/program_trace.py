#!/usr/bin/env python3
"""The serving engine's own host spans and the step programs' named
scopes, read from a profiler trace of a cell's window.

Run by hand, on the chip:

    python3 bench/program_trace.py --workload <cell> --seed <n> --seconds <s>

It makes one traced run of the cell, as ``bench/run.py --trace 1`` does,
prints the same progress lines, and then one JSON line: the cell's
per-layer metrics, the reduction below and the four readings of
:func:`readings`.

The program puts each phase of an engine step in a
``jax.profiler.TraceAnnotation`` named ``engine.*`` (``serving/engine.py``;
``engine.step`` carries the step index that ``plan_log`` entries record)
and each part of its decode and chunk steps in a ``jax.named_scope``
(:data:`SCOPES`, ``models/``).  From the window's trace,
:func:`reduce_program` gives

* ``engine_steps``: the ``engine.step`` spans that start in the window;
* ``host_spans``: calls and seconds of each ``engine.*`` name, for spans
  that start in the window (clipped at its end);
* ``host_idle``: the window's device-idle seconds, by the innermost
  ``engine.*`` span over them, ``outside_engine`` where there is none;
* ``scopes``: the device seconds of each step program's executions that
  start in the window, by scope, ``unscoped`` for the rest.

The trace names an operation by its HLO instruction only and carries no
HLO (on the CPU, and on a TPU v5e), so :func:`step_hlo` compiles both
steps again and :func:`scope_map` reads the ``op_name`` metadata of their
optimized HLO.  ``bench/trace.py`` does not read any of this yet: a run of
``bench/run.py`` reports none of it (PERF.md, section 7).
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse      # noqa: E402
import bisect        # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import sys           # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace as btr  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"

# named scopes of the step programs, from the embedding to the head
SCOPES = ("embed", "qkv", "kv_write", "kv_gather", "attend", "out_proj",
          "mlp", "lm_head")
STEP_ROLES = ("decode_step", "chunk_step")
ATTN_SCOPES = ("kv_gather", "attend")
WEIGHT_SCOPES = ("qkv", "out_proj", "mlp", "lm_head")

Span = Tuple[float, float, str, Optional[int]]


def step_hlo(engine) -> Dict[str, str]:
    """Optimized HLO text of the engine's decode and chunk steps, keyed by
    module name: each step lowered with arguments of the shapes and kinds
    the engine passes, and compiled (a cache read, for a step the engine
    has run).  Call it after the engine's first step and outside a
    measured window."""
    import numpy as np

    from repro.models import transformer
    slots = engine.max_slots
    decode = engine._decode.lower(
        engine.params, engine.cache,
        engine._put(np.zeros((slots,), np.int32))).compile()
    chunk_fn = transformer._prefill_chunk_fn(
        engine.model.cfg, transformer.prefill_fused_mode(), mesh=engine.mesh)
    args = transformer._chunk_call_args(
        np.zeros((slots, engine.prefill_chunk_tokens), np.int32),
        engine.cache, np.full((slots,), -1, np.int32), 0,
        engine._host_pt, None)
    chunk = chunk_fn.lower(engine.params, engine.cache, *args).compile()
    return {btr.module_name(engine._decode): decode.as_text(),
            btr.module_name(chunk_fn): chunk.as_text()}


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")


def scope_map(hlo_text: str) -> Dict[str, str]:
    """Each instruction of an optimized HLO module, by name, to the first
    of :data:`SCOPES` on the path of its ``op_name`` metadata, else
    ``unscoped``.  A fusion takes the scope of its root: XLA gives a
    fusion instruction the metadata of the fused computation's root, so
    a fusion that also holds operations of another scope counts whole
    under its root's.  An instruction that XLA left with no ``op_name``
    at all (on a TPU v5e, the f32 converts it splits off the decode
    step's gathered KV) takes the scope of its first operand that has
    one; the text lists a computation's operands before their users."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        if op:
            out[m.group(1)] = next(
                (p for p in op.group(1).split("/") if p in SCOPES),
                "unscoped")
        else:
            out[m.group(1)] = next(
                (out[o] for o in _OPERAND.findall(m.group(2))
                 if out.get(o, "unscoped") != "unscoped"), "unscoped")
    return out


def read_spans(path: str) -> List[Span]:
    """The ``engine.*`` host spans of a trace file: ``(start, end, name,
    step)`` in seconds.  The profiler keeps a span's metadata as stats, or
    in its name (``engine.step#step=12#``)."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out: List[Span] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith("engine."):
                    continue
                name, _, meta = ev.name.partition("#")
                fields = dict(kv.split("=", 1)
                              for kv in meta.strip("#").split(",")
                              if "=" in kv)
                step = btr._stats(ev).get("step", fields.get("step"))
                s = ev.start_ns * 1e-9
                out.append((s, s + ev.duration_ns * 1e-9, name,
                            None if step is None else int(step)))
    return out


def reduce_program(ev: Dict[str, Any], spans: List[Span],
                   roles: Dict[str, str],
                   scope_maps: Dict[str, Dict[str, str]]) -> Dict[str, Any]:
    """The reduction of the module docstring, from ``bench.trace.read``'s
    events of a trace, its :func:`read_spans` and each step module's
    :func:`scope_map`; device seconds are averaged over device planes as
    ``bench.trace.reduce_events`` averages them."""
    win = [(s, e) for s, e, n in ev["spans"] if n == "bench.window"]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = win[0]
    segments = [(a, b, "outside_engine" if n == "host:other" else n)
                for a, b, n in btr._segments([sp[:3] for sp in spans],
                                             lo, hi)]
    role_of = {roles[r]: r for r in STEP_ROLES if r in roles}
    scopes: Dict[str, Dict[str, float]] = {r: defaultdict(float)
                                           for r in role_of.values()}
    idle: Dict[str, float] = defaultdict(float)
    planes = sorted(ev["ops"])
    for p in planes:
        busy = btr._merge(btr._clip([(s, e) for s, e, _, _ in ev["ops"][p]],
                                    lo, hi))
        for k, v in btr._idle_by_label(busy, segments, lo, hi).items():
            idle[k] += v
        execs = sorted((s, e, m) for s, e, m in ev["modules"].get(p, [])
                       if m in role_of and lo <= s < hi)
        starts = [x[0] for x in execs]
        for s, e, name, mod in btr._attribute(btr._leaves(ev["ops"][p]),
                                              ev["modules"].get(p, [])):
            i = bisect.bisect_right(starts, s) - 1
            if mod in role_of and i >= 0 and execs[i][1] >= s \
                    and execs[i][2] == mod:
                scope = scope_maps.get(mod, {}).get(name, "unscoped")
                scopes[role_of[mod]][scope] += min(e, hi) - s
    host_spans: Dict[str, Dict[str, float]] = {}
    for s, e, name, _ in spans:
        if lo <= s < hi:
            h = host_spans.setdefault(name, {"calls": 0, "seconds": 0.0})
            h["calls"] += 1
            h["seconds"] += min(e, hi) - s
    n = max(len(planes), 1)
    return {"engine_steps": host_spans.get("engine.step", {}).get("calls", 0),
            "host_spans": host_spans,
            "host_idle": {k: v / n for k, v in idle.items()},
            "scopes": {r: {k: v / n for k, v in sc.items()}
                       for r, sc in scopes.items()}}


def readings(program: Dict[str, Any], programs: Dict[str, Any]
             ) -> Dict[str, Optional[float]]:
    """Four per-layer numbers, in ms, from :func:`reduce_program` and the
    step programs' calls (``bench.trace``'s ``programs``); ``None`` where
    the trace holds no span or scope to read.

    * ``host_idle_ms``: device-idle seconds under any ``engine.*`` span
      per engine step (the engine's host work, ``serving/engine.py``);
    * ``schedule_ms``: seconds per ``engine.schedule`` call
      (``serving/scheduler.py``);
    * ``decode_attn_ms``: decode-step device seconds in ``kv_gather`` and
      ``attend`` per call (``models/layers.py``);
    * ``decode_matmul_ms``: the same in ``qkv``, ``out_proj``, ``mlp`` and
      ``lm_head``, the scopes that read the weights (``core/qlinear.py``).
    """
    out: Dict[str, Optional[float]] = dict.fromkeys(
        ("host_idle_ms", "schedule_ms", "decode_attn_ms",
         "decode_matmul_ms"))
    steps = program["engine_steps"]
    if steps:
        idle = sum(v for k, v in program["host_idle"].items()
                   if k != "outside_engine")
        out["host_idle_ms"] = 1e3 * idle / steps
    sched = program["host_spans"].get("engine.schedule")
    if sched:
        out["schedule_ms"] = 1e3 * sched["seconds"] / sched["calls"]
    dec = program["scopes"].get("decode_step", {})
    calls = programs.get("decode_step", {}).get("calls")
    if calls and any(k != "unscoped" for k in dec):
        for key, names in (("decode_attn_ms", ATTN_SCOPES),
                           ("decode_matmul_ms", WEIGHT_SCOPES)):
            out[key] = 1e3 * sum(dec.get(k, 0.0) for k in names) / calls
    return out


def run_traced(cell, seed: int, seconds: float, t_start: float,
               **kw) -> Dict[str, Any]:
    """One traced run of ``cell`` (``bench.harness.run_cell``) whose record
    also holds ``program`` (:func:`reduce_program`) and ``hlo``.  For the
    run, ``bench.trace.program_roles`` is wrapped to compile the steps
    while the engine lives, before the window, and ``bench.trace.reduce``
    to read the trace before it is deleted."""
    from bench import harness
    seen: Dict[str, Any] = {}
    roles0, reduce0 = btr.program_roles, btr.reduce

    def program_roles(engine):
        seen["hlo"] = step_hlo(engine)
        return roles0(engine)

    def reduce(path, t0, t_end, inflight, roles):
        maps = {m: scope_map(t) for m, t in seen["hlo"].items()}
        seen["program"] = reduce_program(btr.read(path), read_spans(path),
                                         roles, maps)
        return reduce0(path, t0, t_end, inflight, roles)

    btr.program_roles, btr.reduce = program_roles, reduce
    try:
        rec = harness.run_cell(cell, seed, seconds, True, t_start, **kw)
    finally:
        btr.program_roles, btr.reduce = roles0, reduce0
    rec.update(program=seen["program"], hlo=seen["hlo"])
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()

    from bench import run as brun
    from bench import spec
    from bench.harness import NoChip
    cell = spec.resolve(args.workload)
    import jax
    from repro.launch.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        rec = run_traced(cell, args.seed, args.seconds, T_START,
                         trace_dir=str(TRACE_DIR))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    rec["cache_dir"] = cache_dir
    brun.info(rec)
    tr, prog = rec["trace"], rec["program"]
    by_time = lambda d: sorted(([k, v] for k, v in d.items()),
                               key=lambda kv: -kv[1])
    print(json.dumps({
        "correct": brun.is_correct(rec), "device": rec["device"],
        "window_s": rec["window_s"], "tokens": rec["tokens"],
        "busy_s": tr["busy_s"], "programs": tr["programs"],
        "per_layer": {m["name"]: spec.metric_reader(m["name"])(rec)
                      for m in cell.per_layer},
        "readings": readings(prog, tr["programs"]),
        "engine_steps": prog["engine_steps"],
        "host_spans": prog["host_spans"],
        "host_idle": by_time(prog["host_idle"]),
        "scopes": {r: by_time(sc) for r, sc in prog["scopes"].items()}},
        allow_nan=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
