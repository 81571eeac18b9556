"""One run of one cell: set-up, the measured window, the check.

Set-up builds the model from the configuration file, makes its Q8_0
weights on the device from the seed, starts an ``Engine`` with the
configuration's geometry under an ``AsyncServer``, and serves the mix's
set-up jobs; that compiles (or loads from the persistent cache) every
program the window will run.  The window then drives the server for
``seconds``: it releases the mix's jobs as they fall due, stamps each
streamed token with the host clock when the step that made it returns,
and hands completions back to the mix (a session's next turn).

Then the program's state is freed and the plain reference
(``bench/reference.py``) checks a sample of the requests the window
finished.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List, Optional

import numpy as np

from bench import model as bm
from bench import spec
from bench import traffic as bt
from bench.spec import Cell

SAMPLE_MIN_REQUESTS = 3


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def _listen_compiles() -> Dict[str, Any]:
    """Counts backend compiles (a persistent-cache read shows as a short
    one) and their seconds, with the time of each."""
    import jax.monitoring
    seen: Dict[str, Any] = {"times": [], "secs": 0.0, "cache_hits": 0}

    def on_duration(name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            seen["times"].append(time.perf_counter())
            seen["secs"] += secs

    def on_event(name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            seen["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return seen


def _span(name: str):
    """A ``bench.*`` host span for the profiler (free when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class _Run:
    """Book-keeping of one window: jobs, token stamps, arrivals."""

    def __init__(self, server, mix, clock):
        self.server, self.mix, self.clock = server, mix, clock
        self.t0 = 0.0
        self.live: Dict[int, Dict[str, Any]] = {}     # uid -> entry
        self.entries: List[Dict[str, Any]] = []

    def submit(self, job: bt.Job, now: float, in_window: bool) -> None:
        due = now if job.due is None else self.t0 + job.due
        h = self.server.submit(job.prompt, t_arrival=due,
                               max_new_tokens=job.max_new, temperature=0.0)
        e = {"job": job, "handle": h, "due": due, "stamps": [],
             "in_window": in_window}
        self.entries.append(e)
        if h.req.error is not None:
            e["done"] = True
        else:
            self.live[h.uid] = e

    def step(self) -> List[Dict[str, Any]]:
        """One server step; stamps new tokens, returns finished entries."""
        done = self.server.step()
        now = self.clock()
        with _span("bench.stamp"):
            return self._stamp(done, now)

    def _stamp(self, done, now):
        for e in self.live.values():
            out = e["handle"].req.output or []
            new = len(out) - len(e["stamps"])
            if new > 0:
                e["stamps"].extend([now] * new)
        finished = []
        for req in done:
            e = self.live.pop(req.uid, None)
            if e is not None:
                e["done"] = True
                finished.append(e)
        return finished


def _serve_setup(run: _Run, jobs: List[bt.Job], until_first: bool,
                 slots: int) -> None:
    """Serve ``jobs`` in set-up: to completion, or (``until_first``: they
    run on into the window) until every slot they can fill holds one
    that has its first token.  Jobs that run on hand their completions
    back to the mix."""
    for job in jobs:
        run.submit(job, run.clock(), in_window=False)
    mine = list(run.entries[-len(jobs):]) if jobs else []
    if until_first:
        want = min(len(mine), slots)
        waiting = lambda: sum(1 for e in mine
                              if e.get("done") or e["stamps"]) < want
    else:
        waiting = lambda: any(not e.get("done") for e in mine)
    while waiting():
        for e in run.step():
            if until_first:
                for job in run.mix.on_done(e["job"], e["handle"].req.prompt,
                                           e["handle"].req.output, 0.0):
                    run.submit(job, run.clock(), in_window=False)


def _warm_row_slices(vocab: int, slots: int) -> None:
    """The engine slices each chunk row's logits ``logits[i:i+1]``
    eagerly, one program per row index: warm all of them."""
    import jax.numpy as jnp
    x = jnp.zeros((slots, vocab), jnp.float32)
    for i in range(slots):
        x[i:i + 1].block_until_ready()


def sample_requests(entries, seed: int, min_tokens: int, t0: float,
                    t_end: float):
    """Requests the window finished, for the check, drawn from the seed:
    the longest (prompt plus served tokens) first, then others until at
    least ``min_tokens`` served tokens and ``SAMPLE_MIN_REQUESTS``
    requests."""
    done = [e for e in entries if e.get("done")
            and e["handle"].req.error is None
            and len(e["handle"].req.output or []) > 0
            and t0 <= e["stamps"][-1] <= t_end]
    if not done:
        return []
    size = lambda e: len(e["handle"].req.prompt) + len(e["handle"].req.output)
    longest = max(range(len(done)), key=lambda i: (size(done[i]), -i))
    order = [longest] + [int(i) for i in np.random.default_rng(
        [int(seed) % (1 << 64), 9]).permutation(len(done)) if i != longest]
    picked, tokens = [], 0
    for i in order:
        if tokens >= min_tokens and len(picked) >= SAMPLE_MIN_REQUESTS:
            break
        picked.append(done[i])
        tokens += len(done[i]["handle"].req.output)
    return picked


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, require_tpu: bool = True,
             trace_dir: Optional[str] = None) -> Dict[str, Any]:
    """One run.  Returns the run record: metrics' inputs, counts, the
    check's numbers and the device."""
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise NoChip(f"the cell needs {cell.chips} TPU chip(s); JAX has "
                     f"{len(devs)} {devs[0].platform} device(s)")
    compiles = _listen_compiles()
    # set-up's phases: (name, seconds from process start at its end)
    phases = [("start", time.time() - t_start)]
    mark = lambda name: phases.append((name, time.time() - t_start))

    from repro.models import build_model
    from repro.serving.async_serving import AsyncServer
    from repro.serving.engine import Engine

    conf, srv = cell.config, cell.config["serving"]
    model = build_model(bm.model_config(conf))
    cfg = model.cfg
    mark("model")
    params = bm.build_weights(model, conf, seed)
    jax.block_until_ready(params)
    mark("weights")
    engine = Engine(model, params, max_slots=srv["slots"],
                    max_seq=srv["max_seq"], eos_id=-1,
                    seed=int(seed) % (1 << 31), page_size=srv["page_size"],
                    n_pages=srv["n_pages"],
                    prefill_chunk_tokens=srv["prefill_chunk_tokens"])
    del params
    server = AsyncServer(engine)
    mix = bt.make_mix(cell.traffic, seed, cfg.vocab_size)
    clock = time.perf_counter
    run = _Run(server, mix, clock)
    jax.block_until_ready(engine.cache)
    mem_weights = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use", 0)
    mark("engine")
    _warm_row_slices(cfg.vocab_size, srv["slots"])
    mark("row_slices")
    _serve_setup(run, mix.setup_jobs(), False, srv["slots"])
    mark("setup_turns")
    _serve_setup(run, mix.first_jobs(), True, srv["slots"])
    jax.block_until_ready(engine.cache)
    mark("first_turns")

    mem_setup = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0)
    roles = {}
    if trace:
        from bench import trace as btr
        roles = btr.program_roles(engine)
        btr.wrap_spans(server)
        btr.start(trace_dir)
    n_compiles_setup = len(compiles["times"])
    m0 = dict(engine.metrics)
    plan0 = len(engine.plan_log)
    t0 = clock()
    setup_s = time.time() - t_start
    run.t0 = t0
    window = _window(run, seconds, trace)
    t_end = window["t_end"]
    if trace:
        trace_file = btr.stop()
    compiles_in_window = sum(1 for t in compiles["times"] if t0 <= t <= t_end)
    plans = engine.plan_log[plan0:window["plan_end"]]
    counters = {k: engine.metrics[k] - m0[k] for k in
                ("tokens_out", "decode_steps", "chunk_batch_calls",
                 "prefill_chunks", "prefix_cached_tokens", "prefix_hits",
                 "preemptions", "requests_failed", "requests_rejected")}
    waiting = sum(1 for e in run.entries if e["in_window"]
                  and not e["stamps"] and not e.get("done"))

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    pool_bytes = sum(int(a.nbytes) for a in
                     jax.tree_util.tree_leaves(engine.cache["attn"]))
    rec = _window_record(run, t0, t_end, plans, counters, srv)
    rec["steps"] = window_steps(engine.plan_log, plan0, window["plan_end"])
    rec["kv_bytes"] = engine.cache["attn"]["k"].dtype.itemsize
    rec.update(cell=cell.name, seed=seed, seconds=seconds,
               work=spec.arch(conf).token_work(conf), conf=conf,
               setup_s=setup_s, setup_phases=phases,
               setup_counters={k: m0[k] for k in ("chunk_batch_calls",
                                                  "decode_steps")},
               roles=roles,
               compiles_setup=n_compiles_setup,
               compile_secs=compiles["secs"],
               cache_hits=compiles["cache_hits"],
               compiles_in_window=compiles_in_window,
               waiting_at_close=waiting,
               memory_peak_bytes=peak, pool_pages=srv["n_pages"],
               memory_after_engine=mem_weights, memory_peak_setup=mem_setup,
               pool_bytes=pool_bytes,
               device={"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices()),
                       "memory_peak_bytes": peak})
    picked = sample_requests(run.entries, seed,
                             conf["check"]["sample_tokens"], t0, t_end)
    seqs = [(np.asarray(e["handle"].req.prompt),
             list(e["handle"].req.output)) for e in picked]
    # free the program's state before the reference runs
    del engine, server, run, mix
    gc.collect()
    jax.clear_caches()
    if trace:
        rec["trace"] = btr.reduce(trace_file, t0, t_end, rec["inflight"],
                                  roles)
    t_check = time.perf_counter()
    rec["check"] = check(conf, seed, seqs)
    rec["check_s"] = time.perf_counter() - t_check
    return rec


def _window(run: _Run, seconds: float, trace: bool) -> Dict[str, Any]:
    mix, clock = run.mix, run.clock
    annotation = None
    if trace:
        import jax
        annotation = jax.profiler.TraceAnnotation("bench.window")
        annotation.__enter__()
    try:
        while True:
            now = clock()
            t = now - run.t0
            if t >= seconds:
                break
            for job in mix.due_jobs(t):
                run.submit(job, now, in_window=True)
            if not run.server.has_work():
                # open loop and nothing in flight: wait for the next due
                nxt = getattr(mix, "t_next", None)
                wait = 0.001 if nxt is None else max(0.0, nxt - t)
                wait = min(wait, seconds - t)
                time.sleep(wait)
                continue
            for e in run.step():
                now = clock()
                for job in mix.on_done(e["job"], e["handle"].req.prompt,
                                       e["handle"].req.output, now - run.t0):
                    run.submit(job, now, in_window=True)
    finally:
        if annotation is not None:
            annotation.__exit__(None, None, None)
    engine = run.server.engine
    return {"t_end": clock(), "plan_end": len(engine.plan_log)}


def _window_record(run: _Run, t0: float, t_end: float, plans, counters,
                   srv) -> Dict[str, Any]:
    """Host-clock readings of the window."""
    tokens, gaps, ttft, inflight = 0, [], [], []
    attempted = failed = 0
    for e in run.entries:
        st = [s for s in e["stamps"] if t0 <= s <= t_end]
        tokens += len(st)
        gaps.extend(np.diff(st).tolist())
        req = e["handle"].req
        start = max(e["due"], t0)
        end = (e["stamps"][-1] if e.get("done") and e["stamps"] else t_end)
        if end > start:
            inflight.append((start, min(end, t_end)))
        if not e["in_window"]:
            continue
        attempted += 1
        if req.error is not None:
            failed += 1
        if e["stamps"] and e["stamps"][0] <= t_end and req.error is None:
            ttft.append(e["stamps"][0] - e["due"])
        else:
            ttft.append(t_end - e["due"])      # missing: a lower bound
    prompt_len = {e["handle"].uid: len(e["handle"].req.prompt)
                  for e in run.entries}
    admitted = [(uid, c) for p in plans for (uid, c) in p["admitted"]]
    return {"t0": t0, "t_end": t_end, "window_s": t_end - t0,
            "tokens": tokens, "gaps_s": gaps, "ttft_s": ttft,
            "attempted": attempted, "failed": failed,
            "inflight": _union(inflight),
            "counters": counters,
            "admitted_prompt_tokens": sum(prompt_len.get(u, 0)
                                          for u, _ in admitted),
            "admitted_cached_tokens": sum(c for _, c in admitted),
            "slots": srv["slots"], "chunk": srv["prefill_chunk_tokens"],
            "page_size": srv["page_size"]}


def window_steps(plan_log, first: int, end: int) -> List[Dict[str, Any]]:
    """Each window step's work from the engine's step plans: chunk rows
    as ``(start, tokens)`` and, for each decoded row, the keys it attends.
    Lengths are followed from the engine's first plan on, so that rows
    already running when the window opens have their context."""
    kv: Dict[int, int] = {}
    steps = []
    for i, plan in enumerate(plan_log[:end]):
        rows = []
        for uid, s, e in plan["prefills"]:
            kv[uid] = e
            rows.append((s, e - s))
        ctx = []
        for uid in plan["decodes"]:
            kv[uid] = kv.get(uid, 0) + 1
            ctx.append(kv[uid])
        if i >= first:
            steps.append({"chunk": rows, "decode": ctx})
    return steps


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def check(conf, seed: int, seqs) -> Dict[str, Any]:
    """The comparison that decides ``correct``: the widest gap by which a
    served token's reference logit lies below the reference's best."""
    from bench import reference
    check_conf = conf["check"]
    served = sum(len(s) for _, s in seqs)
    if not seqs:
        return {"logit_gap": None, "served_tokens": 0, "requests": 0,
                "limit": check_conf["max_logit_gap"]}
    rows = reference.logits_before(conf, seed, seqs)
    return {"logit_gap": reference.served_gap(rows, [s for _, s in seqs]),
            "served_tokens": served, "requests": len(seqs),
            "longest": max(len(p) + len(s) for p, s in seqs),
            "limit": check_conf["max_logit_gap"]}
