"""From a profiler trace of the window to per-layer numbers.

The window of a ``--trace 1`` run is recorded with ``jax.profiler``.  The
reduction reads the ``.xplane.pb`` with ``jax.profiler.ProfileData``:

* device operations: events of a device plane's ``XLA Ops`` line (on a
  backend with no device plane, such as the CPU, events that carry an
  ``hlo_module`` stat), named by the HLO instruction (``fusion.171``);
* program executions: events of a device plane's ``XLA Modules`` line
  (elsewhere: one per ``(hlo_module, run_id)`` of the operations);
* host spans: the benchmark's own ``bench.*`` annotations.

The ``bench.window`` span puts the host clock and the trace on one time
line.  Device busy time is the union of operation intervals inside the
window, averaged over the device planes; an idle gap is a stretch of the
window with no operation on the device, labelled with the innermost
``bench.*`` span that covers it.  A control-flow operation (a ``while``
over the layers) spans the operations of its body on the same line; the
time per operation counts only operations that hold no other, so that
no second is counted twice.  An operation is charged to the program
execution that covers its start.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

_DIR: List[str] = []


def module_name(jitted) -> str:
    """The HLO module name XLA gives a jitted function, in the form
    :func:`_norm` compares."""
    return _norm("jit_" + jitted.__name__)


def _norm(name: str) -> str:
    return re.sub(r"\W", "_", name).strip("_")


def wrap_spans(server) -> None:
    """Put ``bench.*`` host spans around the server's and the engine's
    entry points (instance attributes: the program is not changed)."""
    import functools

    import jax

    def traced(obj, attr, name):
        fn = getattr(obj, attr)

        @functools.wraps(fn)
        def call(*a, **kw):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **kw)
        setattr(obj, attr, call)

    traced(server, "step", "bench.server_step")
    traced(server, "submit", "bench.submit")
    traced(server.engine, "step_async", "bench.step_async")
    traced(server.engine, "finish_step", "bench.finish_step")


def program_roles(engine) -> Dict[str, str]:
    """Module names of the engine's chunk and decode steps, and the
    name prefix of the Pallas prefix-read kernel's operations."""
    from repro.models import transformer
    chunk = transformer._prefill_chunk_fn(
        engine.model.cfg, transformer.prefill_fused_mode(), mesh=engine.mesh)
    return {"chunk_step": module_name(chunk),
            "decode_step": module_name(engine._decode),
            "prefill_attn_kernel": "paged_prefill_attention"}


def start(trace_dir: Optional[str]) -> None:
    import jax
    if os.path.exists(trace_dir):
        shutil.rmtree(trace_dir)
    os.makedirs(trace_dir)
    _DIR[:] = [trace_dir]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def stop() -> str:
    """Stop tracing; returns the ``.xplane.pb`` written."""
    import jax
    jax.profiler.stop_trace()
    files = glob.glob(os.path.join(_DIR[0], "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file, found {files}")
    return files[0]


def op_name(name: str) -> str:
    """The HLO instruction's name from a trace event's name, which on a
    TPU is the instruction's text (``%fusion.171 = f32[...] fusion(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def _stats(ev) -> Dict[str, Any]:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def read(path: str) -> Dict[str, Any]:
    """Normalised events of a trace file (times in seconds)."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    ops: Dict[str, List[Tuple[float, float, str, str]]] = defaultdict(list)
    modules: Dict[str, List[Tuple[float, float, str]]] = defaultdict(list)
    spans: List[Tuple[float, float, str]] = []
    cpu_runs: Dict[Tuple[str, Any], List[float]] = {}
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                if device and line.name == "XLA Ops":
                    mod = _stats(ev).get("hlo_module", "")
                    ops[plane.name].append((s, e, op_name(ev.name),
                                            str(mod)))
                elif device and line.name == "XLA Modules":
                    modules[plane.name].append(
                        (s, e, _norm(ev.name.split("(")[0])))
                elif not device and ev.name.startswith("bench."):
                    spans.append((s, e, ev.name))
                elif not device:
                    st = _stats(ev)
                    if "hlo_module" in st and ev.duration_ns > 0:
                        key = (str(st["hlo_module"]), st.get("run_id"))
                        ops["/host:CPU"].append((s, e, op_name(ev.name),
                                                 key[0]))
                        r = cpu_runs.setdefault(key, [s, e])
                        r[0], r[1] = min(r[0], s), max(r[1], e)
    if not modules and cpu_runs:
        modules["/host:CPU"] = [(s, e, _norm(k[0])) for k, (s, e) in
                                cpu_runs.items()]
    return {"ops": dict(ops), "modules": dict(modules), "spans": spans}


def _merge(intervals) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _leaves(ops) -> List[Tuple[float, float, str, str]]:
    """The operations of one plane that hold no other operation."""
    order = sorted(ops, key=lambda o: (o[0], -o[1]))
    parent = [False] * len(order)
    stack: List[int] = []
    for i, (s, e, _, _) in enumerate(order):
        while stack and order[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= order[stack[-1]][1]:
            parent[stack[-1]] = True
        stack.append(i)
    return [o for o, p in zip(order, parent) if not p]


def _attribute(ops, modules) -> List[Tuple[float, float, str, str]]:
    """Give each operation without a module the program execution that
    covers its start."""
    mods = sorted(modules)
    starts = [m[0] for m in mods]
    out = []
    for s, e, name, mod in ops:
        if not mod:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and mods[i][1] >= s:
                mod = mods[i][2]
        out.append((s, e, name, mod))
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _segments(spans, lo, hi) -> List[Tuple[float, float, str]]:
    """The window cut where the innermost ``bench.*`` span changes:
    ``(start, end, label)`` in order, labelled ``host:other`` where no
    span but the window itself is open."""
    spans = [(max(s, lo), min(e, hi), n) for s, e, n in spans
             if n != "bench.window" and e > lo and s < hi]
    cuts = sorted({lo, hi, *(s for s, _, _ in spans),
                   *(e for _, e, _ in spans)})
    starts = sorted(spans)
    out, active, k = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(starts) and starts[k][0] <= a:
            active.append(starts[k])
            k += 1
        active = [sp for sp in active if sp[1] > a]
        inner = min(active, key=lambda sp: sp[1] - sp[0], default=None)
        out.append((a, b, inner[2] if inner else "host:other"))
    return out


def _idle_by_label(busy, segments, lo, hi) -> Dict[str, float]:
    """Idle time (the window less ``busy``) split by segment label."""
    idle, prev = [], lo
    for s, e in busy:
        if s > prev:
            idle.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        idle.append((prev, hi))
    out: Dict[str, float] = defaultdict(float)
    i = j = 0
    while i < len(idle) and j < len(segments):
        a, b = max(idle[i][0], segments[j][0]), min(idle[i][1],
                                                   segments[j][1])
        if b > a:
            out[segments[j][2]] += b - a
        if idle[i][1] < segments[j][1]:
            i += 1
        else:
            j += 1
    return out


def reduce_events(ev: Dict[str, Any], roles: Dict[str, str],
                  inflight_s: float) -> Dict[str, Any]:
    """Per-layer numbers from normalised events (see :func:`read`)."""
    win = [(s, e) for s, e, n in ev["spans"] if n == "bench.window"]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = win[0]
    window_s = hi - lo
    planes = sorted(ev["ops"])
    segments = _segments(ev["spans"], lo, hi)
    busy, gaps = [], defaultdict(float)
    op_time: Dict[str, float] = defaultdict(float)
    kernel_s = 0.0
    kprefix = roles.get("prefill_attn_kernel", "\0")
    for p in planes:
        ops = _clip([(s, e) for s, e, _, _ in ev["ops"][p]], lo, hi)
        merged = _merge(ops)
        busy.append(sum(e - s for s, e in merged))
        for k, v in _idle_by_label(merged, segments, lo, hi).items():
            gaps[k] += v
        leaves = _attribute(_leaves(ev["ops"][p]),
                            ev["modules"].get(p, []))
        for s, e, name, mod in leaves:
            d = min(e, hi) - max(s, lo)
            if d <= 0:
                continue
            op_time[f"{mod}/{name}" if mod else name] += d
            if name.startswith(kprefix):
                kernel_s += d
    n = max(len(planes), 1)
    mods: Dict[str, Dict[str, float]] = {}
    for role, mname in roles.items():
        if role == "prefill_attn_kernel":
            continue
        calls, secs = 0, 0.0
        for p, evs in ev["modules"].items():
            for s, e, name in evs:
                if name == mname and s >= lo and s < hi:
                    calls += 1
                    secs += min(e, hi) - s
        mods[role] = {"calls": calls / n, "seconds": secs / n}
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": window_s, "busy_s": sum(busy) / n,
            "inflight_s": inflight_s, "programs": mods,
            "kernel_s": kernel_s / n,
            "device_ops": [[k, v / n] for k, v in top_ops],
            "idle_gaps": [[k, v / n] for k, v in top_gaps]}


def reduce(path: str, t0: float, t_end: float, inflight, roles
           ) -> Dict[str, Any]:
    """Read and reduce the window's trace, then delete the trace files.
    ``inflight`` holds the host-clock intervals with a request in flight."""
    ev = read(path)
    inflight_s = sum(min(e, t_end) - max(s, t0) for s, e in inflight
                     if e > t0 and s < t_end)
    out = reduce_events(ev, roles, inflight_s)
    shutil.rmtree(_DIR[0], ignore_errors=True)
    return out
