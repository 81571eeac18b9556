#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU.

Serves llama2-110m at its published widths (12 layers, d_model 768, 12
heads of 64, vocabulary 32000, context 1024) through ``Engine`` with its
default geometry: 8 slots, pages of 64, prefill chunks of 512.  Weights
are random from ``--seed`` and quantized to Q8_0 exactly as
``launch/serve.py`` does; the published checkpoint is not in the repo.

    python chip_smoke.py            # one chip
    python chip_smoke.py --mesh 4   # 4-chip serving mesh vs one chip

One chip: a prompt longer than one chunk (its second chunk reads a
512-token prefix through the Pallas kernel), two prompts that share a
192-token prefix (the second is submitted after the first has finished,
so it is admitted from the prefix cache) and three short prompts, all
greedy.  It checks that

  * every request ends without error and produces ``max_new_tokens``
    (random weights give the end-of-sequence id no meaning, so it is
    switched off);
  * the chunk step takes the prefix read through the compiled Pallas
    kernel (``tpu_custom_call``), not the jnp gather, and the decode
    step its attention through the Pallas paged decode kernel;
  * the served chunk step's last-position logits for the long prompt
    match one-shot ``prefill``, which uses no Pallas, on the same params;
  * each served greedy token is the reference's choice up to that
    tolerance, with the reference teacher-forced on the served stream.

``--mesh N`` runs only the N-chip tensor-parallel engine and its 1-chip
twin on the same requests, checks that both compiled steps carry their
kernels, and that their greedy streams are identical.

The figures it prints come from a smoke run, not a benchmark.  The last
line of stdout is ``{"ok": true, "device": {...}}``; any failure exits
non-zero before it is printed.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "llama2-110m"
MAX_NEW = 32
LONG_LEN = 700                 # > one 512-token chunk
SHARED_LEN = 192               # three full 64-token pages
SHORT_LENS = (9, 17, 33)
# XLA's default matmul precision on a TPU rounds f32 operands to
# bfloat16 (8-bit mantissa, a relative step of 2**-8 = 3.9e-3).  The
# chunk step and one-shot prefill round at different points (other
# fusions, the kernel's own dots), so their logits may differ by a few
# such steps of the largest logit; 2e-2 is about five.
LOGIT_TOL = 2e-2


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def build(seed: int):
    """(model, Q8_0 params): the published llama2-110m config, weights
    random from ``seed``."""
    import jax

    from repro.configs.base import get_config
    from repro.core.policy import QuantPolicy
    from repro.models.model import build_model
    model = build_model(get_config(ARCH))
    params = model.init(jax.random.PRNGKey(seed))
    return model, model.quantize(params, QuantPolicy(bits=8, min_size=512))


def workload(seed: int, vocab: int):
    """Two phases of named prompts; the second is submitted after the
    first drains, so ``shared_b`` finds ``shared_a``'s pages cached."""
    rng = np.random.default_rng(seed)
    draw = lambda n: rng.integers(4, vocab, size=n).astype(np.int32)
    shared = draw(SHARED_LEN)
    first = {"long": draw(LONG_LEN),
             "shared_a": np.concatenate([shared, draw(40)])}
    first.update({f"short_{n}": draw(n) for n in SHORT_LENS})
    return [first, {"shared_b": np.concatenate([shared, draw(24)])}]


def serve(eng, phases):
    """Serve the phases greedily, draining between them.  Returns
    ({name: Request}, [(tokens, wall seconds)] per phase)."""
    done, stats = {}, []
    for phase in phases:
        tok0 = eng.metrics["tokens_out"]
        uids = {eng.submit(p, max_new_tokens=MAX_NEW, temperature=0.0): name
                for name, p in phase.items()}
        t0 = time.perf_counter()
        finished = eng.run()
        stats.append((eng.metrics["tokens_out"] - tok0,
                      time.perf_counter() - t0))
        done.update({uids[r.uid]: r for r in finished})
    return done, stats


def check_requests(done, phases) -> None:
    names = [n for phase in phases for n in phase]
    if sorted(done) != sorted(names):
        fail(f"requests {sorted(set(names) - set(done))} never came back")
    for name in names:
        r = done[name]
        if r.error is not None:
            fail(f"request {name}: {r.error_kind}: {r.error}")
        if len(r.output) != MAX_NEW:
            fail(f"request {name} produced {len(r.output)} tokens, "
                 f"not {MAX_NEW}")


def chunk_step_text(eng) -> str:
    """Compiled text of the engine's chunked-prefill step."""
    from repro.models import transformer
    slots = eng.max_slots
    args = transformer._chunk_call_args(
        np.zeros((slots, eng.prefill_chunk_tokens), np.int32), eng.cache,
        np.full((slots,), -1, np.int32), 0, None,
        np.zeros((slots,), np.int32))
    step = transformer._prefill_chunk_fn(
        eng.model.cfg, transformer.prefill_fused_mode(), mesh=eng.mesh)
    return step.lower(eng.params, eng.cache, *args).compile().as_text()


def has_decode_kernel(eng) -> bool:
    """Whether the engine's compiled decode step runs the Pallas paged
    decode kernel (a ``tpu_custom_call`` named after it)."""
    tokens = np.zeros((eng.max_slots,), np.int32)
    text = eng._decode.lower(eng.params, eng.cache, tokens).compile() \
        .as_text()
    return any("tpu_custom_call" in line and "paged_decode_attention" in line
               for line in text.splitlines())


def chunked_logits(eng, prompt) -> np.ndarray:
    """Last-position logits of ``prompt`` through the engine's own
    chunk step (same padded extent, pool shapes and mesh), on a fresh
    pool: chunk after chunk, as served."""
    import jax
    import jax.numpy as jnp

    from repro.distribution import sharding as shardlib
    model = eng.model
    slots, width, page = eng.max_slots, eng.prefill_chunk_tokens, \
        eng.page_size
    mb = -(-eng.max_seq // page)
    cache = model.init_paged_cache(slots, block_size=page,
                                   n_blocks=eng.n_pages,
                                   max_blocks_per_seq=mb)
    if eng.mesh is not None:
        cache = jax.device_put(cache, shardlib.to_shardings(
            shardlib.cache_specs(model.cfg, cache, eng.mesh), eng.mesh))
    pages = -(-len(prompt) // page)
    pt = np.full((slots, mb), -1, np.int32)
    pt[0, :pages] = np.arange(pages)
    cache["page_table"] = jnp.asarray(pt)
    row_slots = np.full((slots,), -1, np.int32)
    row_slots[0] = 0
    for off in range(0, len(prompt), width):
        piece = prompt[off:off + width]
        toks = np.zeros((slots, width), np.int32)
        toks[0, :len(piece)] = piece
        lens = np.zeros((slots,), np.int32)
        lens[0] = len(piece)
        offs = np.zeros((slots,), np.int32)
        offs[0] = off
        logits, cache = model.prefill_chunk_batch(
            eng.params, toks, cache, row_slots, offs, page_table=pt,
            chunk_lens=lens, mesh=eng.mesh)
    return np.asarray(logits[0])


def make_reference(model, params, max_seq: int):
    """``logits_before(prompt, served)``: the reference logits before
    each served token, (len(served), V) — one-shot ``prefill`` of the
    prompt, then dense decode teacher-forced on ``served``.  No Pallas
    runs on this path."""
    import jax
    import jax.numpy as jnp
    prefill = jax.jit(functools.partial(model.prefill, max_seq=max_seq))
    decode = jax.jit(model.decode_step, donate_argnums=(1,))

    def logits_before(prompt, served):
        logits, cache = prefill(params, {"tokens": jnp.asarray(prompt)[None]})
        rows = [np.asarray(logits[0])]
        for tok in served[:-1]:
            logits, cache = decode(params, cache,
                                   jnp.asarray([tok], jnp.int32))
            rows.append(np.asarray(logits[0]))
        return np.stack(rows)

    return logits_before


def greedy_agreement(ref: np.ndarray, served) -> tuple:
    """(tokens equal to the reference argmax, tokens the reference
    scores within LOGIT_TOL of its best)."""
    served = np.asarray(served)
    best = ref.max(axis=1)
    picked = ref[np.arange(len(served)), served]
    tol = LOGIT_TOL * np.abs(ref).max(axis=1)
    return (int(np.sum(ref.argmax(axis=1) == served)),
            int(np.sum(picked >= best - tol)))


def _listen_compiles():
    """Backend-compile seconds and count (persistent-cache reads
    included: a hit shows as a short compile), and cache hits."""
    import jax.monitoring
    seen = {"n": 0, "secs": 0.0, "cache_hits": 0}

    def on_duration(name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            seen["n"] += 1
            seen["secs"] += secs

    def on_event(name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            seen["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return seen


def _report_phases(tag, stats):
    for i, (toks, wall) in enumerate(stats):
        note = "compiles included" if i == 0 else "warm"
        print(f"[smoke] {tag} phase {i + 1} ({note}): {toks} tokens in "
              f"{wall:.3f} s = {toks / wall:.1f} tok/s wall "
              f"(smoke figure, not a benchmark)")


def run_one_chip(seed: int) -> None:
    from repro.models import transformer
    from repro.serving.engine import Engine
    model, params = build(seed)
    phases = workload(seed, model.cfg.vocab_size)
    mode = transformer.prefill_fused_mode()
    if mode != "kernel":
        fail(f"prefill_fused_mode() is {mode!r} on a TPU, not 'kernel'")
    eng = Engine(model, params, eos_id=-1)
    done, stats = serve(eng, phases)
    check_requests(done, phases)
    _report_phases("1 chip", stats)
    hit = eng.metrics["requests"][done["shared_b"].uid]
    if hit["cached_tokens"] < SHARED_LEN:
        fail(f"shared_b was admitted with {hit['cached_tokens']} cached "
             f"tokens, not the {SHARED_LEN}-token shared prefix")
    print(f"[smoke] prefix cache: shared_b admitted with "
          f"{hit['cached_tokens']} cached tokens")
    if "tpu_custom_call" not in chunk_step_text(eng):
        fail("the compiled chunk step holds no tpu_custom_call: the "
             "prefix read is not the Pallas kernel")
    print("[smoke] chunk step: compiled with the Pallas prefill kernel "
          "(tpu_custom_call)")
    if not has_decode_kernel(eng):
        fail("the compiled decode step holds no paged_decode_attention "
             "tpu_custom_call: decode attention is not the Pallas kernel")
    print("[smoke] decode step: compiled with the Pallas paged decode "
          "kernel (tpu_custom_call)")

    prompts = {**phases[0], **phases[1]}
    logits_before = make_reference(model, params, eng.max_seq)
    refs = {name: logits_before(prompts[name], done[name].output)
            for name in ("long", "shared_b", f"short_{SHORT_LENS[0]}")}
    long = prompts["long"]
    got = chunked_logits(eng, long)
    ref = refs["long"][0]
    err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
    print(f"[smoke] long prompt ({len(long)} tokens, 2 chunks) "
          f"last-position logits vs one-shot prefill: max |diff| / "
          f"max |ref| = {err:.3e} (tolerance {LOGIT_TOL})")
    if not err <= LOGIT_TOL:
        fail(f"chunked logits differ from one-shot prefill by {err:.3e} "
             f"of the largest logit (tolerance {LOGIT_TOL})")
    for name, ref in refs.items():
        served = done[name].output
        same, close = greedy_agreement(ref, served)
        print(f"[smoke] greedy agreement with the reference, {name}: "
              f"{same}/{len(served)} tokens equal to its argmax, "
              f"{close}/{len(served)} within tolerance")
        if close != len(served):
            fail(f"{name}: {len(served) - close} served tokens are not "
                 f"the reference's choice within {LOGIT_TOL}")


def run_mesh(seed: int, size: int) -> None:
    from repro.launch.mesh import make_serve_mesh
    from repro.serving.engine import Engine
    model, params = build(seed)
    phases = workload(seed, model.cfg.vocab_size)
    engines, streams = {}, {}
    for tag, mesh in (("1 chip", None),
                      (f"mesh {size}", make_serve_mesh(size))):
        eng = Engine(model, params, eos_id=-1, mesh=mesh)
        done, stats = serve(eng, phases)
        check_requests(done, phases)
        _report_phases(tag, stats)
        if "tpu_custom_call" not in chunk_step_text(eng):
            fail(f"{tag}: the compiled chunk step holds no "
                 "tpu_custom_call")
        if not has_decode_kernel(eng):
            fail(f"{tag}: the compiled decode step holds no "
                 "paged_decode_attention tpu_custom_call")
        engines[tag] = eng
        streams[tag] = {n: list(r.output) for n, r in done.items()}
    one, sharded = streams["1 chip"], streams[f"mesh {size}"]
    differ = sorted(n for n in one if one[n] != sharded[n])
    print(f"[smoke] greedy streams identical, mesh {size} vs 1 chip: "
          f"{len(one) - len(differ)}/{len(one)} requests")
    if differ:
        _explain_divergence(engines, phases, one, sharded, differ)
        fail(f"mesh {size} streams differ from 1 chip for {differ}")


def _explain_divergence(engines, phases, one, sharded, differ) -> None:
    """For each diverging request: whether the chunk step's logits
    already differ, and how the unsharded reference, teacher-forced on
    the 1-chip stream, scores the two tokens where the streams part."""
    eng1 = engines["1 chip"]
    prompts = {**phases[0], **phases[1]}
    logits_before = make_reference(eng1.model, eng1.params, eng1.max_seq)
    for name in differ:
        a, b = (chunked_logits(e, prompts[name]) for e in engines.values())
        t = next(i for i, (x, y) in enumerate(zip(one[name], sharded[name]))
                 if x != y)
        ref = logits_before(prompts[name], one[name][:t + 1])[t]
        scale = np.abs(ref).max()
        rel = np.abs(a - b).max() / np.abs(b).max()
        print(f"[smoke] {name}: chunk-step logits, mesh vs 1 chip: max "
              f"|diff| / max |logit| = {rel:.3e}; "
              f"streams part at token {t} ({one[name][t]} vs "
              f"{sharded[name][t]}), which the reference scores "
              f"{(ref.max() - ref[one[name][t]]) / scale:.3e} and "
              f"{(ref.max() - ref[sharded[name][t]]) / scale:.3e} "
              f"below its best, relative to max |logit|")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", type=int, default=0,
                    help="run only the N-chip serving mesh and its "
                         "1-chip twin (0 = the one-chip smoke run)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if "REPRO_FUSED_PREFILL" in os.environ:
        fail("REPRO_FUSED_PREFILL is set; the smoke run checks the "
             "path the platform selects")
    from repro.launch.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    compiles = _listen_compiles()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX runs on {dev.platform}")
    count = len(jax.devices())
    print(f"[smoke] device: {dev.device_kind} x{count}; jax "
          f"{jax.__version__}; compile cache {cache_dir}")
    if args.mesh:
        if args.mesh < 2 or args.mesh > count:
            fail(f"--mesh {args.mesh} needs 2..{count} devices")
        run_mesh(args.seed, args.mesh)
    else:
        run_one_chip(args.seed)
    print(f"[smoke] compile: {compiles['n']} executables in "
          f"{compiles['secs']:.2f} s, {compiles['cache_hits']} read from "
          f"the persistent cache")
    peak = dev.memory_stats().get("peak_bytes_in_use")
    print(f"[smoke] peak_bytes_in_use (device 0): {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))


if __name__ == "__main__":
    main()
