"""Serving entrypoint: quantize a (trained or fresh) model per the
paper's PTQ flow and serve it with the continuous-batching engine —
closed batch by default, or an open-loop continuous-arrival stream with
per-step token streaming (`--open-loop`).

  # closed batch (drain-style, the original mode)
  PYTHONPATH=src python -m repro.launch.serve --arch llama2-110m \
      --reduced --requests 16 --bits 8

  # open loop: seeded Poisson arrivals at 0.85x measured capacity,
  # goodput + TTFT/TPOT percentiles from true arrival time
  PYTHONPATH=src python -m repro.launch.serve --reduced --requests 16 \
      --open-loop

  # same, streaming each token to stdout as it is produced
  PYTHONPATH=src python -m repro.launch.serve --reduced --requests 4 \
      --open-loop --stream
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np

from repro.checkpoint import store
from repro.configs.base import get_config, reduced
from repro.core.policy import QuantPolicy
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.roofline import PEAKS_BY_DEVICE_KIND, V5E_POWER_W
from repro.models.model import build_model, count_params
from repro.serving.async_serving import (first_token_latencies,
                                         latency_summary_ms,
                                         poisson_arrivals, run_open_loop)
from repro.serving.engine import Engine


def _load_params(model, cfg, ckpt_dir: str, seed: int):
    """Init params, restoring from ``ckpt_dir`` when given.  The restore
    passes the template state (so quantized leaves round-trip through
    their own container type) and verifies the step it loaded is the
    latest one on disk — a stale or missing step directory should fail
    loudly here, not serve silently-old weights."""
    params = model.init(jax.random.PRNGKey(seed))
    if ckpt_dir:
        state_like = {"params": params}
        restored, step, _ = store.restore(ckpt_dir, state_like)
        latest = store.latest_step(ckpt_dir)
        if step != latest:
            raise RuntimeError(
                f"restored step {step} from {ckpt_dir} but latest on "
                f"disk is {latest}")
        params = restored["params"]
        print(f"[serve] restored checkpoint step {step} from {ckpt_dir} "
              f"(latest on disk)")
    return params


def _make_prompts(rng, cfg, n: int):
    return [rng.integers(4, cfg.vocab_size,
                         size=int(rng.integers(4, 32))).astype(np.int32)
            for _ in range(n)]


def _print_throughput(eng, toks: int, wall: float) -> None:
    # two figures, each saying what it measures: the wall-clock number
    # spans prefill + decode + host work end to end; the engine's
    # throughput_tok_s() is decode-only (tokens_out / t_decode) and is
    # what BENCH_engine.json gates as decode_tok_s.
    print(f"[serve] throughput: {toks/wall:,.1f} tok/s end-to-end "
          f"wall-clock | {eng.throughput_tok_s():,.1f} tok/s decode-only "
          f"(tokens_out/t_decode; the bench-gated figure)")


def _print_energy_model(eng, toks: int) -> None:
    # the engine's joules come from v5e roofline peaks and an assumed
    # board power: a model, and meaningless on any other device
    kind = jax.devices()[0].device_kind
    joules = eng.metrics["energy_joules"]
    if kind in PEAKS_BY_DEVICE_KIND and joules > 0:
        print(f"[serve] modelled energy (roofline at {kind} published "
              f"peaks, {V5E_POWER_W:.0f} W assumed; not measured): "
              f"{joules:.3g} J -> {toks/joules:,.0f} tok/J")


def run(arch: str = "llama2-110m", use_reduced: bool = True,
        requests: int = 16, bits: int = 8, kv_int8: bool = False,
        max_seq: int = 512, max_new: int = 48, slots: int = 4,
        ckpt_dir: str = "", seed: int = 0, no_quant: bool = False,
        spec_tokens: int = 0, draft: str = "ngram",
        open_loop: bool = False, rate: float = 0.0,
        load_factor: float = 0.85, stream: bool = False,
        stream_interval: int = 1, mesh_size: int = 0):
    cfg = get_config(arch)
    if use_reduced:
        cfg = reduced(cfg)
    if kv_int8:
        cfg = cfg.with_(kv_cache_dtype="int8")
    model = build_model(cfg)
    params = _load_params(model, cfg, ckpt_dir, seed)

    mesh = None
    if mesh_size > 0:
        from repro.launch.mesh import make_serve_mesh
        mesh = make_serve_mesh(mesh_size)
        print(f"[serve] tensor-parallel mesh: model={mesh_size} "
              f"({len(mesh.devices.flat)} devices; KV pool sharded on "
              f"KV heads, streams bit-identical to unsharded)")

    if not no_quant:
        t0 = time.perf_counter()
        params = model.quantize(params, QuantPolicy(bits=bits, min_size=512))
        print(f"[serve] Q{bits}_0 post-training quantization "
              f"in {time.perf_counter()-t0:.2f}s")

    def make_engine():
        return Engine(model, params, max_slots=slots, max_seq=max_seq,
                      seed=seed, spec_tokens=spec_tokens,
                      draft_proposer=draft, mesh=mesh)

    rng = np.random.default_rng(seed)
    prompts = _make_prompts(rng, cfg, requests)
    if open_loop:
        return _run_open_loop(make_engine, prompts, max_new, seed, rate,
                              load_factor, stream, stream_interval)

    eng = make_engine()
    for prompt in prompts:
        eng.submit(prompt, max_new_tokens=max_new)
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    toks = eng.metrics["tokens_out"]
    print(f"[serve] {len(done)}/{requests} requests, {toks} tokens "
          f"in {wall:.2f}s")
    _print_throughput(eng, toks, wall)
    # exclude requests that never produced a first token (errored or
    # rejected keep t_first_token == 0.0; their "latency" would be a
    # huge negative sample that corrupts the percentiles)
    lat = first_token_latencies(done)
    if len(lat):
        print(f"[serve] TTFT p50 {np.median(lat)*1e3:.0f}ms  "
              f"p95 {np.percentile(lat, 95)*1e3:.0f}ms "
              f"(from arrival, {len(lat)}/{len(done)} with first token)")
    _print_energy_model(eng, toks)
    if spec_tokens > 0:
        print(f"[serve] speculation ({draft}, k={spec_tokens}): "
              f"accept_ratio {eng.metrics['accept_ratio']:.2f} "
              f"({eng.metrics['accepted_tokens']}"
              f"/{eng.metrics['draft_tokens']} drafts), "
              f"steps/token {eng.metrics['steps_per_token']:.3f}, "
              f"{eng.metrics['spec_rollbacks']} rollbacks")
    return eng, done


def _run_open_loop(make_engine, prompts, max_new: int, seed: int,
                   rate: float, load_factor: float, stream: bool,
                   stream_interval: int):
    """Continuous-arrival serving: requests arrive mid-flight on a
    seeded Poisson process and tokens stream back per step.  When no
    ``--rate`` is given, a short closed-loop calibration pass measures
    service capacity and the arrival rate is set to ``load_factor`` of
    it — loaded enough that queueing delay is visible, stable enough
    that the queue drains."""
    if rate <= 0:
        n_cal = min(4, len(prompts))
        cal = make_engine()
        for p in prompts[:n_cal]:
            cal.submit(p, max_new_tokens=max_new)
        t0 = time.perf_counter()
        cal.run()
        cal_wall = max(time.perf_counter() - t0, 1e-6)
        rate = load_factor * n_cal / cal_wall
        print(f"[serve] calibrated: {n_cal} requests in {cal_wall:.2f}s "
              f"-> open-loop arrival rate {rate:.2f} req/s "
              f"({load_factor:.0%} of measured capacity)")

    on_token = None
    if stream:
        def on_token(handle, sibling, tokens, done):
            for t in tokens:
                print(f"[stream] uid={handle.uid} sib={sibling} tok={t}")
            if done:
                tag = "ok" if handle.error is None else handle.error_kind
                print(f"[stream] uid={handle.uid} done ({tag})")

    arrivals = poisson_arrivals(seed, len(prompts), rate)
    workload = [(float(t), p, {"max_new_tokens": max_new, "seed": seed + i})
                for i, (t, p) in enumerate(zip(arrivals, prompts))]
    eng = make_engine()
    t0 = time.perf_counter()
    handles, report = run_open_loop(
        eng, workload, stream_interval_steps=stream_interval,
        on_token=on_token)
    wall = time.perf_counter() - t0
    toks = eng.metrics["tokens_out"]
    print(f"[serve] open loop: {report.completed_ok}/{report.n_requests} "
          f"ok ({report.failed} failed), {report.midflight_submits} "
          f"arrivals landed mid-flight, peak queue depth "
          f"{report.peak_queue_depth}")
    print(f"[serve] goodput {report.goodput_tok_s:,.1f} tok/s "
          f"({report.goodput_req_s:.2f} req/s) at offered "
          f"{report.arrival_rate_req_s:.2f} req/s over {report.wall_s:.2f}s")
    print(f"[serve] TTFT p50 {report.ttft_ms['p50']:.0f}ms "
          f"p99 {report.ttft_ms['p99']:.0f}ms | TPOT p50 "
          f"{report.tpot_ms['p50']:.1f}ms p99 {report.tpot_ms['p99']:.1f}ms "
          f"(from true arrival time)")
    _print_throughput(eng, toks, wall)
    return eng, [h.req for h in handles if h.req is not None]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2-110m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--bits", type=int, default=8, choices=(4, 8))
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--no-quant", action="store_true")
    ap.add_argument("--max-seq", type=int, default=512)
    ap.add_argument("--max-new", type=int, default=48)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--spec-tokens", type=int, default=0,
                    help="draft-then-verify speculation depth (0 = off)")
    ap.add_argument("--draft", default="ngram",
                    help="draft proposer kind (see serving/spec_decode.py)")
    ap.add_argument("--open-loop", action="store_true",
                    help="continuous Poisson arrivals instead of a "
                         "closed batch; reports goodput and TTFT/TPOT "
                         "percentiles from true arrival time")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="open-loop arrival rate in req/s "
                         "(0 = calibrate to --load-factor of capacity)")
    ap.add_argument("--load-factor", type=float, default=0.85,
                    help="target utilization for rate calibration")
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as they stream back per step")
    ap.add_argument("--stream-interval", type=int, default=1,
                    help="flush streamed tokens every N engine steps")
    ap.add_argument("--mesh", type=int, default=0,
                    help="tensor-parallel mesh size over the model axis "
                         "(0 = single-device serving; needs that many "
                         "devices, e.g. XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.set_defaults(reduced=True)
    args = ap.parse_args()
    configure_compile_cache()
    _, done = run(
        args.arch, args.reduced, args.requests, args.bits, args.kv_int8,
        args.max_seq, args.max_new, args.slots, args.ckpt_dir,
        no_quant=args.no_quant, spec_tokens=args.spec_tokens,
        draft=args.draft, open_loop=args.open_loop, rate=args.rate,
        load_factor=args.load_factor, stream=args.stream,
        stream_interval=args.stream_interval, mesh_size=args.mesh)
    failed = [r for r in done if r.error is not None]
    if failed or len(done) != args.requests:
        kinds = sorted({str(r.error_kind) for r in failed})
        sys.exit(f"[serve] FAILED: {len(failed)} of {args.requests} "
                 f"requests errored or were rejected ({', '.join(kinds)}); "
                 f"{len(done)} came back")


if __name__ == "__main__":
    main()
