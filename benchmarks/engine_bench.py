"""Engine-level serving benchmark: mixed traffic on an oversubscribed
pool + a shared-prefix workload over the prefix cache.

Where kernels_bench tracks single-kernel decode costs, these scenarios
exercise the scheduler subsystem end to end:

  * **mixed** — short and long prompts submitted together against a
    paged pool sized at 3/8 of the full reservation, with a chunk budget
    far below the longest prompt: chunked prefill interleaved with
    decodes, block recycling, mid-decode preemption with
    recompute-on-resume,
  * **shared_prefix** — N requests over M distinct system prompts served
    twice, prefix caching on vs off: reports the hit rate, prefill
    tokens/blocks saved, and the TTFT deltas the cache buys (CI fails if
    the hit rate silently drops to zero — see ci/run_ci.sh),
  * **parallel_sampling** — ``n_samples=4`` best-of-n requests fanning
    out over ``BlockAllocator.fork``: each group prefills its prompt
    once and its four siblings share the prompt blocks read-only
    (diverging tails un-share via COW).  Reports peak live blocks
    against the ``prompt + n*tail`` sharing bound, blocks saved by fork
    sharing (CI fails at zero), decode tok/s, and verifies each sibling
    of the probe request is bit-identical to an independent
    (seed, stream=i) rerun,
  * **shape_churn** — a stream of prompts whose lengths all differ,
    deliberately churning the ``(B, chunk_len, pos_offset)`` triples
    the pre-shape-stable engine compiled per: reports the XLA compile
    count of the chunk step (must stay at ``compile_bound`` = one per
    pool key — CI fails above it), the legacy shape-key count it
    *would* have compiled, and TTFT p50/p99 for the churny traffic,
  * **long_context** — three 512–1024-token prompts chunk-prefilled on
    a 16-token-block pool: reports the prefix K/V bytes the
    chunk-attention step reads (live tiles through the page table)
    against the legacy full-extent-gather baseline (CI fails if the
    saving is zero), the chunk step's compile count against the
    one-per-pool-key bound, and a whole-prompt bitwise-identity probe
    through the fused Pallas kernel in interpret mode,
  * **fault_tolerance** — the same traffic served fault-free, with the
    fault layer enabled-but-idle, and under a seeded FaultPlan hitting
    one request per fault class: reports goodput (surviving tokens),
    blast radius per fault, leaked blocks after the faulted drain, and
    the two bit-exactness flags CI gates on (idle fault layer and fault
    survivors must both match the fault-free streams exactly),
  * **open_loop** — continuous-arrival serving through the async
    front-end (serving/async_serving.py): a seeded Poisson schedule at
    a rate calibrated to a fraction of measured closed-loop capacity,
    requests arriving and streaming back mid-flight.  Reports goodput
    and TTFT/TPOT percentiles measured from TRUE arrival time (the
    queueing-delay-aware numbers the drain-time workloads cannot see),
    plus the closed-vs-open bit-exactness flag, the
    negative-latency-sample count (the ``t_first_token == 0.0`` filter
    regression guard) and the prefill compile count under continuous
    arrivals — all CI-gated.

Writes machine-readable JSON (``BENCH_engine.json``, emitted into the CI
artifacts dir by ci/run_ci.sh) so the trajectory of serving-level
metrics is chartable across PRs:

  * TTFT p50/p99 (ms) — chunked admission exists to keep the p99 of
    short requests bounded while long prompts stream in,
  * decode throughput (tok/s over decode wall-clock),
  * preemption / prefill-chunk / batched-call / decode-step counts and
    pool size — the work the scheduler did to absorb the load,
  * prefix-cache hit rate, cached tokens, and prefill-tokens saved.

CPU wall-clock here is a smoke-level signal (the kernels are jnp paths,
not the TPU build); the counts are the stable part of the trajectory.
"""

from __future__ import annotations

import json

import numpy as np

PROMPT_LENS = (8, 72, 12, 64, 10, 80, 9, 48, 16, 96)

# shared-prefix workload: N requests drawing on M distinct system prompts
SP_SYSTEM_PROMPTS = 3
SP_REQUESTS = 12
SP_SYSTEM_LEN = 48           # 3 full blocks of 16 -> cacheable prefix
SP_SUFFIX_LEN = 8

# parallel-sampling workload: best-of-4 requests over a 3-block prompt
PS_REQUESTS = 3
PS_N_SAMPLES = 4
PS_PROMPT_LEN = 48           # 3 full blocks of 16, shared by all siblings
PS_MAX_NEW = 16              # each sibling's divergent tail: 1 block

# shape-churn workload: every prompt length distinct, spanning several
# chunk counts under a 48-token budget -> maximal (B, len, off) churn
SC_PROMPT_LENS = (5, 23, 41, 7, 66, 14, 90, 31, 11, 53, 77, 19)
SC_CHUNK_TOKENS = 48
SC_COMPILE_BOUND = 1         # executables per pool key (docs/BENCHMARKS.md)

# fault-tolerance workload: 6 singletons + one n_samples=2 group on an
# ample pool; a seeded FaultPlan implicates one request per fault class
FT_PROMPT_LENS = (8, 20, 12, 24, 10, 16, 14)   # last one is the group
FT_MAX_NEW = 12

# spec-decode workload: greedy requests with repetitive suffixes (the
# n-gram proposer's sweet spot — prompt-lookup drafts accept whenever
# the continuation revisits the pattern) mixed with seeded sampled
# traffic, served draft-then-verify on the fused kernel (interpret mode)
SD_PATTERN_LEN = 4
SD_PATTERN_REPS = 5          # 20-token repetitive prompts
SD_GREEDY = 3
SD_SAMPLED = 2
SD_MAX_NEW = 24
SD_SPEC_TOKENS = 4
SD_COMPILE_BOUND = 1         # verify executables per pool key

# long-context workload: few LONG prompts on a small-block pool — the
# regime where chunked prefill's prefix read dominates HBM traffic (each
# chunk re-reads its whole prefix); charts prefix_attn_bytes (live tiles
# through the page table) against the legacy full-extent gather baseline
LC_PROMPT_LENS = (512, 768, 1024)
LC_CHUNK_TOKENS = 64
LC_PAGE_SIZE = 16
LC_MAX_NEW = 4
LC_COMPILE_BOUND = 1         # same per-pool-key bound as shape_churn

# open-loop workload: OL_REQUESTS requests on a seeded Poisson schedule
# whose rate is OL_LOAD_FACTOR of measured closed-loop capacity (the
# closed pass doubles as the bit-exactness reference), plus one
# malformed request mid-schedule whose t_first_token stays 0.0 — the
# latency-filter regression guard (neg_latency_samples must be 0)
OL_REQUESTS = 12
OL_MAX_NEW = 8
OL_LOAD_FACTOR = 0.8
OL_SEED = 17
OL_COMPILE_BOUND = 0         # continuous arrivals over the closed pass

# multi-chip workload: the same mixed greedy/sampled traffic served
# unsharded and over a tensor-parallel mesh (model axis = largest of
# 1/2/4 that the local device count allows).  The CI multi-device lane
# runs it under XLA_FLAGS=--xla_force_host_platform_device_count=4 and
# gates on stream bit-exactness vs the unsharded engine, zero leaked
# blocks at drain, a clean allocator audit, and the chunk step's
# one-executable-per-(pool key, mesh shape) compile bound
MC_PROMPT_LENS = (5, 19, 11, 32, 8, 23)
MC_MAX_NEW = 8
MC_COMPILE_BOUND = 1         # executables per (pool key, mesh shape)


def _build_model():
    import jax

    from repro.configs import get_config, reduced
    from repro.models import build_model

    cfg = reduced(get_config("llama2-110m"))
    model = build_model(cfg)
    params = model.quantize(model.init(jax.random.PRNGKey(0)))
    return model, params


def run_shared_prefix(model, params, quiet: bool = False,
                      max_new_tokens: int = 8) -> dict:
    """Serve SP_REQUESTS requests over SP_SYSTEM_PROMPTS shared system
    prompts twice — prefix caching on, then off — and report what the
    cache bought: hit rate, prefill tokens/blocks saved, TTFT deltas."""
    from repro.serving.async_serving import first_token_latencies
    from repro.serving.engine import Engine

    rng = np.random.default_rng(1)
    systems = [rng.integers(4, 500, size=SP_SYSTEM_LEN).astype(np.int32)
               for _ in range(SP_SYSTEM_PROMPTS)]
    prompts = [np.concatenate([
        systems[i % SP_SYSTEM_PROMPTS],
        rng.integers(4, 500, size=SP_SUFFIX_LEN).astype(np.int32)])
        for i in range(SP_REQUESTS)]

    def serve(prefix_caching: bool):
        eng = Engine(model, params, max_slots=4, max_seq=128,
                     page_size=16, prefill_chunk_tokens=64,
                     prefix_caching=prefix_caching)
        # warmup passes populate the prefix index (pass 1) and compile
        # the warm-path chunk shapes (pass 2, whose plan sequence the
        # measured pass repeats); the measured pass then shows skipped
        # prefill compute rather than skipped compilation.
        for _ in range(2):
            for p in prompts:
                eng.submit(p, max_new_tokens=max_new_tokens,
                           temperature=0.0)
            assert all(r.error is None for r in eng.run())
        stats0 = dict(eng.scheduler.prefix_stats)
        blocks0 = eng.pager.stats["hit_blocks"]
        plans0 = len(eng.plan_log)
        uids = [eng.submit(p, max_new_tokens=max_new_tokens,
                           temperature=0.0) for p in prompts]
        done = {r.uid: r for r in eng.run()}
        assert all(done[u].error is None for u in uids)
        # via the filtered helper: a request that never produced a first
        # token keeps t_first_token == 0.0 and must not contribute a
        # (hugely negative) sample to the percentiles
        ttft = first_token_latencies([done[u] for u in uids]) * 1e3
        assert len(ttft) == len(uids)
        dstats = {k: eng.scheduler.prefix_stats[k] - stats0[k]
                  for k in stats0}
        dstats["hit_blocks"] = eng.pager.stats["hit_blocks"] - blocks0
        prefill_tokens = sum(e - s for plan in eng.plan_log[plans0:]
                             for (_, s, e) in plan["prefills"])
        return eng, ttft, dstats, prefill_tokens

    warm, ttft_warm, wstats, wtokens = serve(True)
    cold, ttft_cold, _, ctokens = serve(False)

    result = {
        "requests": SP_REQUESTS,
        "distinct_system_prompts": SP_SYSTEM_PROMPTS,
        "system_len": SP_SYSTEM_LEN,
        "suffix_len": SP_SUFFIX_LEN,
        "prefix_hit_rate": wstats["hits"] / max(1, wstats["admissions"]),
        "prefix_hits": wstats["hits"],
        "admissions": wstats["admissions"],
        "cached_tokens": wstats["cached_tokens"],
        "blocks_saved": wstats["hit_blocks"],
        "prefill_tokens_warm": wtokens,
        "prefill_tokens_cold": ctokens,
        "prompt_tokens_submitted": int(sum(len(p) for p in prompts)),
        "chunk_batch_calls_warm": warm.metrics["chunk_batch_calls"],
        "prefill_chunks_warm": warm.metrics["prefill_chunks"],
        "ttft_ms_p50_warm": float(np.percentile(ttft_warm, 50)),
        "ttft_ms_p50_cold": float(np.percentile(ttft_cold, 50)),
        "ttft_ms_p99_warm": float(np.percentile(ttft_warm, 99)),
        "ttft_ms_p99_cold": float(np.percentile(ttft_cold, 99)),
    }
    if not quiet:
        print(f"enginebench/prefix_hit_rate,"
              f"{result['prefix_hit_rate']:.2f},ratio"
              f" ({result['prefix_hits']}/{result['admissions']} admissions,"
              f" {result['cached_tokens']} tokens,"
              f" {result['blocks_saved']} blocks reused)")
        print(f"enginebench/prefill_tokens_saved,"
              f"{result['prefill_tokens_cold'] - result['prefill_tokens_warm']},"
              f"tokens (warm {result['prefill_tokens_warm']}"
              f" vs cold {result['prefill_tokens_cold']})")
        print(f"enginebench/ttft_ms_p50_warm,"
              f"{result['ttft_ms_p50_warm']:.1f},ms"
              f" (cold {result['ttft_ms_p50_cold']:.1f})")
    return result


def run_parallel_sampling(model, params, quiet: bool = False) -> dict:
    """Serve PS_REQUESTS ``n_samples=4`` requests twice on one engine
    (round 1 compiles + provides the cold reference streams; round 2 is
    measured) and report what fork sharing bought.

    Every group admits once, prefills its 48-token prompt once, and fans
    out into 4 siblings whose page tables all point at the same 3 prompt
    blocks — so a group's peak footprint is ``prompt + 4*tail`` blocks
    instead of 4 full copies.  The probe request's four siblings are
    re-served as independent (seed, stream=i) requests and must match
    bit for bit (the fanout bit-exactness acceptance bar — raises on
    violation rather than reporting a quietly-wrong speedup)."""
    from repro.serving.engine import Engine

    rng = np.random.default_rng(2)
    prompts = [rng.integers(4, 500, size=PS_PROMPT_LEN).astype(np.int32)
               for _ in range(PS_REQUESTS)]
    max_slots, max_seq, page = 8, 128, 16

    def submit_all(eng):
        return [eng.submit(p, max_new_tokens=PS_MAX_NEW, temperature=1.0,
                           seed=200 + i, n_samples=PS_N_SAMPLES)
                for i, p in enumerate(prompts)]

    eng = Engine(model, params, max_slots=max_slots, max_seq=max_seq,
                 page_size=page, prefill_chunk_tokens=64)
    uids = submit_all(eng)
    cold = {r.uid: r for r in eng.run()}
    assert all(cold[u].error is None for u in uids)

    # the probe group's siblings vs independent reruns (cold vs cold:
    # identical chunk boundaries, so the streams must be bit-identical)
    probe = cold[uids[0]].outputs
    for i in range(PS_N_SAMPLES):
        solo = Engine(model, params, max_slots=max_slots, max_seq=max_seq,
                      page_size=page, prefill_chunk_tokens=64)
        solo.submit(prompts[0], max_new_tokens=PS_MAX_NEW, temperature=1.0,
                    seed=200, stream=i)
        (r,) = solo.run()
        if r.output != probe[i]:
            raise AssertionError(
                f"sibling {i} diverged from its independent rerun:\n"
                f"  group: {probe[i]}\n  rerun: {r.output}")

    # measured round: decode is compiled now; deltas isolate the round
    eng.metrics["blocks_live_peak"] = 0
    eng.metrics["blocks_saved_by_sharing_peak"] = 0
    toks0, t0 = eng.metrics["tokens_out"], eng.metrics["t_decode"]
    uids = submit_all(eng)
    done = {r.uid: r for r in eng.run()}
    assert all(done[u].error is None for u in uids)
    tok_s = ((eng.metrics["tokens_out"] - toks0)
             / max(1e-9, eng.metrics["t_decode"] - t0))

    prompt_blocks = PS_PROMPT_LEN // page
    tail_blocks = -(-(PS_PROMPT_LEN + PS_MAX_NEW) // page) - prompt_blocks
    groups_at_once = max_slots // PS_N_SAMPLES
    bound = groups_at_once * (prompt_blocks + PS_N_SAMPLES * tail_blocks)
    naive = groups_at_once * PS_N_SAMPLES * (prompt_blocks + tail_blocks)
    peak = eng.metrics["blocks_live_peak"]
    if peak > bound:
        raise AssertionError(
            f"fanout peak {peak} blocks exceeds the sharing bound {bound} "
            f"(prompt {prompt_blocks} + {PS_N_SAMPLES}*{tail_blocks} tails "
            f"x {groups_at_once} concurrent groups)")

    result = {
        "requests": PS_REQUESTS,
        "n_samples": PS_N_SAMPLES,
        "prompt_len": PS_PROMPT_LEN,
        "max_new_tokens": PS_MAX_NEW,
        "page_size": page,
        "prompt_blocks": prompt_blocks,
        "tail_blocks_per_sibling": tail_blocks,
        "concurrent_groups": groups_at_once,
        "blocks_live_peak": peak,
        "blocks_bound_shared": bound,
        "blocks_naive_unshared": naive,
        "blocks_saved_by_sharing_peak":
            eng.metrics["blocks_saved_by_sharing_peak"],
        "fanouts": eng.metrics["fanouts"],
        "cow_copies": eng.metrics["cow_copies"],
        "decode_tok_s": float(tok_s),
        "siblings_bitexact": True,
    }
    if not quiet:
        print(f"enginebench/fanout_blocks_peak,{peak},blocks"
              f" (bound {bound}, unshared would be {naive})")
        print(f"enginebench/fanout_blocks_saved,"
              f"{result['blocks_saved_by_sharing_peak']},blocks"
              f" ({result['fanouts']} fanouts,"
              f" {result['cow_copies']} COW copies)")
        print(f"enginebench/fanout_decode_tok_s,{tok_s:.1f},tok/s")
    return result


def run_shape_churn(model, params, quiet: bool = False,
                    max_new_tokens: int = 4) -> dict:
    """Serve SC_PROMPT_LENS prompts — every length distinct — and report
    what shape-stable chunked prefill bought: the chunk step's XLA
    compile count (bounded at SC_COMPILE_BOUND per pool key; CI fails
    above it) against the legacy ``(B, chunk_len, pos_offset)`` key
    count this traffic would have compiled, plus TTFT percentiles.

    The compile count spans the COLD pass (that is where compilation
    happens); TTFT is measured on a second, warm pass so the percentiles
    track steady-state prefill latency rather than the one-time compile
    the cold pass exists to bound."""
    from repro.serving.async_serving import first_token_latencies
    from repro.serving.engine import Engine, legacy_chunk_shape_keys

    rng = np.random.default_rng(7)
    prompts = [rng.integers(4, 500, size=n).astype(np.int32)
               for n in SC_PROMPT_LENS]
    eng = Engine(model, params, max_slots=4, max_seq=128, page_size=16,
                 prefill_chunk_tokens=SC_CHUNK_TOKENS,
                 prefix_caching=False)
    compiles0 = eng.prefill_compile_count()
    for p in prompts:                      # cold pass: compiles count
        eng.submit(p, max_new_tokens=max_new_tokens, temperature=0.0)
    assert all(r.error is None for r in eng.run())
    compiles = eng.prefill_compile_count() - compiles0

    uids = [eng.submit(p, max_new_tokens=max_new_tokens, temperature=0.0)
            for p in prompts]              # warm pass: TTFT percentiles
    done = {r.uid: r for r in eng.run()}
    assert all(done[u].error is None for u in uids)
    # filtered helper: no request without a first token may contribute
    ttft = first_token_latencies([done[u] for u in uids]) * 1e3
    assert len(ttft) == len(uids)

    legacy = legacy_chunk_shape_keys(eng.plan_log)

    result = {
        "requests": len(prompts),
        "prompt_lens": list(SC_PROMPT_LENS),
        "prefill_chunk_tokens": SC_CHUNK_TOKENS,
        "prefill_compiles": compiles,
        "compile_bound": SC_COMPILE_BOUND,
        "legacy_shape_keys": len(legacy),
        "prefill_chunks": eng.metrics["prefill_chunks"],
        "chunk_batch_calls": eng.metrics["chunk_batch_calls"],
        "ttft_ms_p50": float(np.percentile(ttft, 50)),
        "ttft_ms_p99": float(np.percentile(ttft, 99)),
    }
    if not quiet:
        print(f"enginebench/shape_churn_compiles,{compiles},executables"
              f" (bound {SC_COMPILE_BOUND}; legacy keying would have"
              f" compiled {result['legacy_shape_keys']})")
        print(f"enginebench/shape_churn_ttft_ms_p50,"
              f"{result['ttft_ms_p50']:.1f},ms"
              f" (p99 {result['ttft_ms_p99']:.1f})")
    return result


def run_long_context(model, params, quiet: bool = False) -> dict:
    """Serve LC_PROMPT_LENS (512–1024 token prompts) through 64-token
    chunks on a 16-token-block pool and report what the fused paged
    prefix read buys: ``prefix_attn_bytes`` (bytes the chunk-attention
    step actually touches — live tiles through the page table) vs
    ``prefix_attn_bytes_gather`` (the legacy ``max_blocks × block_size``
    materialized-gather extent), alongside TTFT p50/p99 and decode
    tok/s.  CI fails if the saving hits zero, if the chunk step's
    compile count exceeds the one-per-pool-key bound, or if the
    whole-prompt bitwise-identity probe (single chunk through the FUSED
    kernel, interpret mode, vs one-shot ``prefill``) regresses."""
    import os

    import jax.numpy as jnp

    from repro.models import transformer
    from repro.serving.async_serving import first_token_latencies
    from repro.serving.engine import Engine

    rng = np.random.default_rng(11)
    prompts = [rng.integers(4, 500, size=n).astype(np.int32)
               for n in LC_PROMPT_LENS]
    max_seq = 1088                     # longest prompt + decode headroom

    # serve with the FUSED path enabled (Pallas interpret mode — the
    # kernel genuinely executes on CPU), so prefix_attn_bytes and the
    # compile count describe the fused kernel, not the jnp oracle
    prev = os.environ.get("REPRO_FUSED_PREFILL")
    os.environ["REPRO_FUSED_PREFILL"] = "interpret"
    try:
        fused_mode = transformer.prefill_fused_mode()
        eng = Engine(model, params, max_slots=2, max_seq=max_seq,
                     page_size=LC_PAGE_SIZE,
                     prefill_chunk_tokens=LC_CHUNK_TOKENS,
                     prefix_caching=False)
        compiles0 = eng.prefill_compile_count()
        uids = [eng.submit(p, max_new_tokens=LC_MAX_NEW, temperature=0.0)
                for p in prompts]
        done = {r.uid: r for r in eng.run()}
        assert all(done[u].error is None for u in uids), \
            [done[u].error for u in uids if done[u].error is not None]
        compiles = eng.prefill_compile_count() - compiles0

        # whole-prompt probe through the FUSED kernel: one 64-token
        # prompt as a single natural-extent chunk vs one-shot prefill,
        # to reassociation tolerance with the same argmax (two XLA
        # programs).  The contract is stated for f32 compute + f32 pools
        # (the bench model is quantized/bf16, where even the oracle path
        # carries a cast), so probe a small f32 build of the same config.
        import jax

        from repro.configs import get_config, reduced
        from repro.models import build_model
        f32m = build_model(reduced(get_config("llama2-110m")).with_(
            compute_dtype="float32"))
        f32p = f32m.init(jax.random.PRNGKey(0))
        probe = rng.integers(4, 500, size=LC_CHUNK_TOKENS).astype(np.int32)
        l_one, _ = f32m.prefill(f32p, {"tokens": jnp.asarray(probe)[None]},
                                max_seq=LC_CHUNK_TOKENS)
        nblk = LC_CHUNK_TOKENS // LC_PAGE_SIZE
        cache = f32m.init_paged_cache(1, block_size=LC_PAGE_SIZE,
                                      n_blocks=nblk + 1,
                                      max_blocks_per_seq=nblk)
        cache["page_table"] = jnp.asarray(
            np.arange(nblk, dtype=np.int32)[None])
        l_chunk, _ = f32m.prefill_chunk(f32p, jnp.asarray(probe),
                                        cache, 0, 0)
    finally:
        if prev is None:
            del os.environ["REPRO_FUSED_PREFILL"]
        else:
            os.environ["REPRO_FUSED_PREFILL"] = prev
    # filtered helper: no request without a first token may contribute
    ttft = first_token_latencies([done[u] for u in uids]) * 1e3
    assert len(ttft) == len(uids)
    saved = (eng.metrics["prefix_attn_bytes_gather"]
             - eng.metrics["prefix_attn_bytes"])
    l_chunk, l_one = np.asarray(l_chunk), np.asarray(l_one)
    whole_diff = float(np.max(np.abs(l_chunk - l_one)))
    whole_match = bool(np.allclose(l_chunk, l_one, rtol=1e-5, atol=1e-6)
                       and np.argmax(l_chunk) == np.argmax(l_one))

    result = {
        "requests": len(prompts),
        "prompt_lens": list(LC_PROMPT_LENS),
        "prefill_chunk_tokens": LC_CHUNK_TOKENS,
        "page_size": LC_PAGE_SIZE,
        "max_new_tokens": LC_MAX_NEW,
        "ttft_ms_p50": float(np.percentile(ttft, 50)),
        "ttft_ms_p99": float(np.percentile(ttft, 99)),
        "decode_tok_s": eng.throughput_tok_s(),
        "prefill_chunks": eng.metrics["prefill_chunks"],
        "chunk_batch_calls": eng.metrics["chunk_batch_calls"],
        "prefix_attn_bytes": eng.metrics["prefix_attn_bytes"],
        "prefix_attn_bytes_gather":
            eng.metrics["prefix_attn_bytes_gather"],
        "prefix_attn_bytes_saved": saved,
        "prefix_bytes_saved_frac":
            saved / max(eng.metrics["prefix_attn_bytes_gather"], 1),
        "prefill_compiles": compiles,
        "compile_bound": LC_COMPILE_BOUND,
        "fused_mode": fused_mode,
        "whole_prompt_match": whole_match,
        "whole_prompt_max_abs_diff": whole_diff,
    }
    if not quiet:
        print(f"enginebench/long_context_prefix_bytes_saved,{saved},bytes"
              f" ({result['prefix_bytes_saved_frac']:.0%} of the"
              f" {result['prefix_attn_bytes_gather']}-byte gather"
              f" baseline; mode {result['fused_mode']})")
        print(f"enginebench/long_context_ttft_ms_p50,"
              f"{result['ttft_ms_p50']:.1f},ms"
              f" (p99 {result['ttft_ms_p99']:.1f})")
        print(f"enginebench/long_context_whole_prompt_match,"
              f"{int(whole_match)},bool (whole-prompt chunk via fused"
              f" kernel vs one-shot prefill: max abs diff"
              f" {whole_diff:.3g}, rtol 1e-5 atol 1e-6, same argmax)")
    return result


def run_spec_decode(model, params, quiet: bool = False) -> dict:
    """Serve greedy repetitive-suffix prompts (plus seeded sampled
    traffic) draft-then-verify and report what speculation bought:

      1. non-speculative greedy reference — the streams every gate
         compares against, and the baseline ``tokens_per_joule``,
      2. speculative greedy (n-gram proposer, fused kernel in interpret
         mode) — must be **bit-identical** to run 1 (raises otherwise),
         with ``steps_per_token`` < 1.0 (fewer per-sequence device steps
         than emitted tokens: the whole point), ``accept_ratio`` > 0,
         and the verify entry within its one-per-pool-key compile bound
         — all CI-gated (ci/run_ci.sh),
      3. mixed greedy + sampled speculative traffic — the greedy streams
         must STILL match run 1 (acceptance counts are per-row private;
         batch composition cannot leak), and the sampled requests
         exercise per-position keyed acceptance under temperature.

    Energy is the roofline model (launch/roofline.step_joules) fed by
    the engine's per-call bytes/FLOPs accounting — ``tokens_per_joule``
    is the paper's headline metric, reported for runs 1 and 2 so the
    speculation win shows up in tokens/J, not just steps."""
    import os

    from repro.models import transformer
    from repro.serving.engine import Engine

    rng = np.random.default_rng(13)
    n_req = SD_GREEDY + SD_SAMPLED
    prompts = [np.tile(rng.integers(4, 500,
                                    size=SD_PATTERN_LEN).astype(np.int32),
                       SD_PATTERN_REPS) for _ in range(n_req)]

    def mk_engine(spec: bool):
        return Engine(model, params, max_slots=4, max_seq=96, page_size=8,
                      prefill_chunk_tokens=32, prefix_caching=False,
                      spec_tokens=SD_SPEC_TOKENS if spec else 0)

    def serve(eng, idx):
        uids = [eng.submit(prompts[i], max_new_tokens=SD_MAX_NEW,
                           temperature=0.0 if i < SD_GREEDY else 1.0,
                           seed=None if i < SD_GREEDY else 400 + i)
                for i in idx]
        done = {r.uid: r for r in eng.run()}
        assert all(done[u].error is None for u in uids), \
            [done[u].error for u in uids if done[u].error is not None]
        return [done[u].output for u in uids]

    greedy_idx = list(range(SD_GREEDY))
    prev = os.environ.get("REPRO_FUSED_PREFILL")
    os.environ["REPRO_FUSED_PREFILL"] = "interpret"
    try:
        fused_mode = transformer.prefill_fused_mode()
        eng0 = mk_engine(False)                    # 1: reference
        base = serve(eng0, greedy_idx)
        eng1 = mk_engine(True)                     # 2: speculative greedy
        compiles0 = eng1.verify_compile_count()
        spec = serve(eng1, greedy_idx)
        verify_compiles = eng1.verify_compile_count() - compiles0
        eng2 = mk_engine(True)                     # 3: mixed traffic
        mixed = serve(eng2, list(range(n_req)))
    finally:
        if prev is None:
            del os.environ["REPRO_FUSED_PREFILL"]
        else:
            os.environ["REPRO_FUSED_PREFILL"] = prev

    greedy_bitexact = spec == base
    mixed_greedy_bitexact = mixed[:SD_GREEDY] == base
    if not greedy_bitexact:
        raise AssertionError(
            f"speculative greedy streams diverged:\n  base: {base}\n"
            f"  spec: {spec}")

    m0, m1 = eng0.metrics, eng1.metrics
    tpj0 = m0["tokens_out"] / max(m0["energy_joules"], 1e-12)
    tpj1 = m1["tokens_out"] / max(m1["energy_joules"], 1e-12)

    result = {
        "requests_greedy": SD_GREEDY,
        "requests_sampled": SD_SAMPLED,
        "prompt_len": SD_PATTERN_LEN * SD_PATTERN_REPS,
        "max_new_tokens": SD_MAX_NEW,
        "spec_tokens": SD_SPEC_TOKENS,
        "proposer": "ngram",
        "fused_mode": fused_mode,
        "greedy_bitexact": bool(greedy_bitexact),
        "mixed_greedy_bitexact": bool(mixed_greedy_bitexact),
        "draft_tokens": m1["draft_tokens"],
        "accepted_tokens": m1["accepted_tokens"],
        "accept_ratio": m1["accept_ratio"],
        "steps_per_token": m1["steps_per_token"],
        "steps_per_token_nonspec": m0["steps_per_token"],
        "verify_steps": m1["verify_steps"],
        "spec_rollbacks": m1["spec_rollbacks"],
        "verify_compiles": verify_compiles,
        "compile_bound": SD_COMPILE_BOUND,
        "energy_joules": m1["energy_joules"],
        "energy_joules_nonspec": m0["energy_joules"],
        "tokens_per_joule": float(tpj1),
        "tokens_per_joule_nonspec": float(tpj0),
        "mixed_accept_ratio": eng2.metrics["accept_ratio"],
        "mixed_steps_per_token": eng2.metrics["steps_per_token"],
    }
    if not quiet:
        print(f"enginebench/spec_steps_per_token,"
              f"{result['steps_per_token']:.3f},steps/token"
              f" (non-spec 1.000; accept ratio"
              f" {result['accept_ratio']:.2f} ="
              f" {result['accepted_tokens']}/{result['draft_tokens']}"
              f" drafts, {result['spec_rollbacks']} rollbacks)")
        print(f"enginebench/spec_tokens_per_joule,{tpj1:.0f},tok/J"
              f" (non-spec {tpj0:.0f}, roofline model)")
        print(f"enginebench/spec_bitexact,"
              f"{int(greedy_bitexact and mixed_greedy_bitexact)},bool"
              f" (solo {greedy_bitexact}, mixed {mixed_greedy_bitexact};"
              f" verify compiles {verify_compiles})")
    return result


def run_fault_tolerance(model, params, quiet: bool = False) -> dict:
    """Serve FT_PROMPT_LENS (6 singletons + one n_samples=2 group) three
    times and report the fault layer's acceptance bars:

      1. no fault layer (wall clock) — the reference streams,
      2. fault layer ENABLED but with an empty plan, SimClock, per-step
         allocator audit — must be bit-identical to run 1
         (``faultfree_bitexact``; CI fails otherwise: the hooks must be
         free when nothing is armed),
      3. a seeded FaultPlan implicating one request per fault class
         (transient blip, persistent step fault, NaN row, page-table
         corruption, deadline expiry) — each class must fail exactly its
         target (``blast_radius_max`` <= 1 request; a sampling group
         counts as one), the survivors' streams must match run 1 bit for
         bit (``survivors_bitexact``), and the drained pool must hold
         zero leases and a clean audit (``leaked_blocks`` == 0,
         ``audit_clean``) — all CI-gated.

    Goodput is reported as surviving-request tokens (count + fraction of
    the fault-free total); run 3 runs on the simulated clock (the
    deadline fault needs it), so its wall-clock tok/s is not measurable
    — ``decode_tok_s_faultfree`` carries run 1's real throughput."""
    from repro.serving.engine import Engine
    from repro.serving.faults import FaultPlan, SimClock

    rng = np.random.default_rng(3)
    prompts = [rng.integers(4, 500, size=n).astype(np.int32)
               for n in FT_PROMPT_LENS]

    def mk_engine(**kw):
        return Engine(model, params, max_slots=8, max_seq=64, page_size=8,
                      prefill_chunk_tokens=32, prefix_caching=False, **kw)

    def submit_all(eng, deadlines: bool = False):
        for i, p in enumerate(prompts):
            uid = i + 1
            eng.submit(p, max_new_tokens=FT_MAX_NEW, temperature=1.0,
                       seed=300 + i, n_samples=2 if uid == 7 else 1,
                       deadline_ms=250.0 if deadlines and uid == 5
                       else None)

    eng0 = mk_engine()
    submit_all(eng0)
    base = {r.uid: r for r in eng0.run()}
    assert all(r.error is None for r in base.values())
    streams0 = {u: r.outputs for u, r in base.items()}

    eng1 = mk_engine(faults=FaultPlan(), clock=SimClock(),
                     audit_interval=1)
    submit_all(eng1)
    idle = {r.uid: r for r in eng1.run()}
    faultfree_bitexact = (
        all(r.error is None for r in idle.values())
        and {u: r.outputs for u, r in idle.items()} == streams0)

    plan = (FaultPlan(seed=3)
            .step_exception(step=2, times=1)              # transient blip
            .step_exception(step=4, uid=2, times=10**6)   # -> "fault"
            .nan_logits(step=5, uid=3)                    # -> "nan"
            .corrupt_pages(step=3, uid=4)                 # -> "audit"
            .advance_clock(step=6, ms=500.0))             # -> "deadline"
    eng = mk_engine(faults=plan, clock=SimClock(), audit_interval=1)
    submit_all(eng, deadlines=True)                       # uid 5: 250 ms
    done = {r.uid: r for r in eng.run()}

    failed = {u: r.error_kind for u, r in done.items()
              if r.error is not None}
    failed_by_kind: dict = {}
    for kind in failed.values():
        failed_by_kind[kind] = failed_by_kind.get(kind, 0) + 1
    survivors = sorted(u for u in done if u not in failed)
    survivors_bitexact = all(done[u].outputs == streams0[u]
                             for u in survivors)
    audit_clean = eng.pager.audit(repair=False).clean
    leaked = (eng.pager.cfg.n_blocks - eng.pager.n_free()
              + sum(1 for rc in eng.pager.refcount if rc))
    tokens_total = sum(len(o) for r in base.values() for o in r.outputs)
    goodput_tokens = sum(len(o) for u in survivors
                         for o in done[u].outputs)

    result = {
        "requests": len(prompts),
        "prompt_lens": list(FT_PROMPT_LENS),
        "max_new_tokens": FT_MAX_NEW,
        "injected_faults": sum(1 for f in plan.faults if f.fired),
        "step_retries": eng.metrics["step_retries"],
        "requests_failed": eng.metrics["requests_failed"],
        "failed_by_kind": failed_by_kind,
        "blast_radius_max": max(failed_by_kind.values(), default=0),
        "survivors": survivors,
        "faultfree_bitexact": bool(faultfree_bitexact),
        "survivors_bitexact": bool(survivors_bitexact),
        "leaked_blocks": int(leaked),
        "audit_clean": bool(audit_clean),
        "audit_repairs": eng.metrics["audit_repairs"],
        "deadline_misses": eng.metrics["deadline_misses"],
        "nan_rows": eng.metrics["nan_rows"],
        "goodput_tokens": int(goodput_tokens),
        "tokens_total_faultfree": int(tokens_total),
        "goodput_fraction": goodput_tokens / max(1, tokens_total),
        "decode_tok_s_faultfree": eng0.throughput_tok_s(),
    }
    if not quiet:
        print(f"enginebench/fault_goodput,{result['goodput_fraction']:.2f},"
              f"ratio ({goodput_tokens}/{tokens_total} tokens from"
              f" {len(survivors)}/{len(prompts)} surviving requests)")
        print(f"enginebench/fault_blast_radius,"
              f"{result['blast_radius_max']},requests/fault"
              f" ({failed_by_kind})")
        print(f"enginebench/fault_bitexact,"
              f"{int(faultfree_bitexact and survivors_bitexact)},bool"
              f" (faultfree {faultfree_bitexact},"
              f" survivors {survivors_bitexact};"
              f" {result['leaked_blocks']} leaked blocks,"
              f" audit clean {audit_clean})")
    return result


def run_open_loop_serving(model, params, quiet: bool = False) -> dict:
    """Continuous-arrival serving under load: OL_REQUESTS requests on a
    seeded Poisson schedule served through the async front-end
    (mid-flight submission, per-step streaming, the dispatch→sync
    overlap window), measured OPEN loop — goodput plus TTFT/TPOT
    percentiles charged from TRUE arrival time, the queueing-delay-aware
    numbers the drain-style workloads structurally cannot see.

    The same arrival order submitted up front and drained closed-loop
    provides both the rate calibration (arrivals at OL_LOAD_FACTOR of
    measured capacity) and the reference streams.  One malformed request
    is inserted mid-schedule: it is rejected with ``t_first_token`` left
    at 0.0, and ``neg_latency_samples`` proves the latency filter kept
    it out of the percentiles.  CI gates: bit-exactness vs closed,
    nonzero goodput, zero negative latency samples, and no prefill
    executables beyond what the closed pass compiled."""
    import time

    from repro.serving.async_serving import poisson_arrivals, run_open_loop
    from repro.serving.engine import Engine

    rng = np.random.default_rng(OL_SEED)
    prompts = [rng.integers(4, 500, size=int(rng.integers(8, 24)))
               .astype(np.int32) for _ in range(OL_REQUESTS)]
    kws = [{"max_new_tokens": OL_MAX_NEW, "seed": 1000 + i,
            "temperature": 0.0 if i % 2 == 0 else 1.0}
           for i in range(OL_REQUESTS)]

    def make_engine():
        return Engine(model, params, max_slots=4, max_seq=128,
                      page_size=16, prefill_chunk_tokens=32)

    def streams(req):
        outs = req.outputs if req.outputs is not None else [req.output or []]
        return tuple(tuple(o) for o in outs)

    # closed-loop pass: rate calibration + bit-exactness reference
    eng_c = make_engine()
    for p, kw in zip(prompts, kws):
        eng_c.submit(p, **kw)
    t0 = time.perf_counter()
    done_c = sorted(eng_c.run(), key=lambda r: r.uid)
    t_closed = max(time.perf_counter() - t0, 1e-6)
    assert all(r.error is None for r in done_c)
    ref = [streams(r) for r in done_c]
    rate = OL_LOAD_FACTOR * OL_REQUESTS / t_closed
    # compile baseline AFTER the closed pass: the gate is that
    # continuous arrivals reuse the closed pass's executables
    compiles0 = eng_c.prefill_compile_count()

    arrivals = poisson_arrivals(OL_SEED, OL_REQUESTS, rate)
    workload = [(float(t), p, kw)
                for t, p, kw in zip(arrivals, prompts, kws)]
    # the malformed mid-schedule request: rejected at submit, its
    # t_first_token stays 0.0 — the latency-filter regression guard
    bad_at = OL_REQUESTS // 2
    workload.insert(bad_at, (workload[bad_at][0], np.zeros(0, np.int32),
                             {"max_new_tokens": OL_MAX_NEW}))

    eng_o = make_engine()
    handles, report = run_open_loop(eng_o, workload)
    valid = [h for i, h in enumerate(handles) if i != bad_at]
    bitexact = [streams(h.req) for h in valid] == ref
    rejected = handles[bad_at].req
    assert rejected.error is not None
    assert rejected.t_first_token == 0.0
    compiles = eng_o.prefill_compile_count() - compiles0

    result = {
        "requests": OL_REQUESTS,
        "invalid_requests": 1,
        "max_new_tokens": OL_MAX_NEW,
        "load_factor": OL_LOAD_FACTOR,
        "arrival_seed": OL_SEED,
        "arrival_rate_req_s": float(report.arrival_rate_req_s),
        "closed_wall_s": float(t_closed),
        "wall_s": float(report.wall_s),
        "completed_ok": report.completed_ok,
        "failed": report.failed,
        "goodput_tok_s": float(report.goodput_tok_s),
        "goodput_req_s": float(report.goodput_req_s),
        "ttft_ms_p50": report.ttft_ms["p50"],
        "ttft_ms_p95": report.ttft_ms["p95"],
        "ttft_ms_p99": report.ttft_ms["p99"],
        "tpot_ms_p50": report.tpot_ms["p50"],
        "tpot_ms_p99": report.tpot_ms["p99"],
        "neg_latency_samples": report.neg_latency_samples,
        "midflight_submits": report.midflight_submits,
        "peak_queue_depth": report.peak_queue_depth,
        "closed_vs_open_bitexact": bool(bitexact),
        "prefill_compiles": compiles,
        "compile_bound": OL_COMPILE_BOUND,
    }
    if not quiet:
        print(f"enginebench/open_loop_goodput_tok_s,"
              f"{result['goodput_tok_s']:.1f},tok/s"
              f" ({result['goodput_req_s']:.2f} req/s ok at offered"
              f" {result['arrival_rate_req_s']:.2f} req/s,"
              f" {result['midflight_submits']} mid-flight arrivals)")
        print(f"enginebench/open_loop_ttft_ms_p50,"
              f"{result['ttft_ms_p50']:.1f},ms"
              f" (p99 {result['ttft_ms_p99']:.1f}; from true arrival)")
        print(f"enginebench/open_loop_tpot_ms_p50,"
              f"{result['tpot_ms_p50']:.1f},ms"
              f" (p99 {result['tpot_ms_p99']:.1f})")
        print(f"enginebench/open_loop_bitexact,"
              f"{int(result['closed_vs_open_bitexact'])},bool"
              f" ({result['neg_latency_samples']} negative latency"
              f" samples, {result['prefill_compiles']} extra prefill"
              f" compiles)")
    return result


def run_multi_chip(model, params, quiet: bool = False) -> dict:
    """Serve MC_PROMPT_LENS (alternating greedy / seeded sampled) twice
    — unsharded, then over a tensor-parallel mesh whose model axis is
    the largest of 1/2/4 the local device count allows — and report
    what the sharded engine must hold:

      * ``streams_bitexact`` — every request's token stream from the
        mesh engine matches the unsharded engine bit for bit (the
        storage-sharded / compute-replicated contract: all collectives
        are gathers, so no float reduction is reassociated across
        devices; raises on violation),
      * ``leaked_blocks`` / ``audit_clean`` — the host-side allocator is
        device-count-agnostic: drain leaves zero leases and a clean
        audit however many devices sit under the pool,
      * ``prefill_compiles`` — the chunk step stays at
        MC_COMPILE_BOUND executables for this (pool key, mesh shape).

    On one device the mesh degenerates to model=1 (placement through
    the same device_put/constraint path, no sharding) — still a real
    gate on the mesh code path; the CI lane forces 4 host devices so
    model=4 runs everywhere."""
    import jax

    from repro.launch.mesh import make_serve_mesh
    from repro.serving.engine import Engine

    n_dev = jax.device_count()
    msize = max(n for n in (1, 2, 4) if n <= n_dev)
    mesh = make_serve_mesh(msize)
    rng = np.random.default_rng(23)
    prompts = [rng.integers(4, 500, size=n).astype(np.int32)
               for n in MC_PROMPT_LENS]

    def serve(mesh_):
        eng = Engine(model, params, max_slots=4, max_seq=96, page_size=8,
                     prefill_chunk_tokens=24, mesh=mesh_)
        compiles0 = eng.prefill_compile_count()
        uids = [eng.submit(p, max_new_tokens=MC_MAX_NEW,
                           temperature=0.0 if i % 2 == 0 else 1.0,
                           seed=500 + i)
                for i, p in enumerate(prompts)]
        done = {r.uid: r for r in eng.run()}
        assert all(done[u].error is None for u in uids), \
            [done[u].error for u in uids if done[u].error is not None]
        streams = [tuple(tuple(o) for o in done[u].outputs) for u in uids]
        return eng, streams, eng.prefill_compile_count() - compiles0

    eng0, ref, _ = serve(None)
    eng, got, compiles = serve(mesh)
    if got != ref:
        bad = [i for i, (a, b) in enumerate(zip(got, ref)) if a != b]
        raise AssertionError(
            f"mesh={msize} streams diverged from unsharded on requests "
            f"{bad}")
    leaked = (eng.pager.cfg.n_blocks - eng.pager.n_free()
              + sum(1 for rc in eng.pager.refcount if rc))
    audit_clean = eng.pager.audit(repair=False).clean

    result = {
        "requests": len(prompts),
        "prompt_lens": list(MC_PROMPT_LENS),
        "max_new_tokens": MC_MAX_NEW,
        "n_devices": n_dev,
        "mesh_model": msize,
        "streams_bitexact": True,
        "leaked_blocks": int(leaked),
        "audit_clean": bool(audit_clean),
        "prefill_compiles": compiles,
        "compile_bound": MC_COMPILE_BOUND,
        "decode_tok_s": eng.throughput_tok_s(),
        "decode_tok_s_unsharded": eng0.throughput_tok_s(),
        "tokens_out": eng.metrics["tokens_out"],
        "preemptions": eng.metrics["preemptions"],
    }
    if not quiet:
        print(f"enginebench/multi_chip_bitexact,1,bool"
              f" (mesh model={msize} over {n_dev} devices vs unsharded,"
              f" {result['tokens_out']} tokens)")
        print(f"enginebench/multi_chip_leaked_blocks,"
              f"{result['leaked_blocks']},blocks"
              f" (audit clean {audit_clean})")
        print(f"enginebench/multi_chip_compiles,{compiles},executables"
              f" (bound {MC_COMPILE_BOUND} per pool key per mesh shape)")
        print(f"enginebench/multi_chip_decode_tok_s,"
              f"{result['decode_tok_s']:.1f},tok/s"
              f" (unsharded {result['decode_tok_s_unsharded']:.1f};"
              f" CPU smoke signal, not a TPU figure)")
    return result


def run(quiet: bool = False, json_path: str = "BENCH_engine.json",
        max_new_tokens: int = 16) -> dict:
    from repro.serving.async_serving import first_token_latencies
    from repro.serving.engine import Engine

    model, params = _build_model()
    rng = np.random.default_rng(0)

    max_slots, max_seq, page_size = 4, 128, 16
    full_reservation = max_slots * (max_seq // page_size)     # 32 blocks
    # 3/8 of the full reservation: deep enough oversubscription that
    # deferral alone cannot absorb it — mid-decode growth must preempt.
    n_pages = full_reservation * 3 // 8
    eng = Engine(model, params, max_slots=max_slots, max_seq=max_seq,
                 page_size=page_size, n_pages=n_pages,
                 prefill_chunk_tokens=32)
    for n in PROMPT_LENS:
        eng.submit(rng.integers(4, 500, size=n).astype(np.int32),
                   max_new_tokens=max_new_tokens, temperature=0.0)
    done = eng.run()
    ok = [r for r in done if r.error is None]
    assert len(ok) == len(PROMPT_LENS), \
        [r.error for r in done if r.error is not None]
    # filtered helper: no request without a first token may contribute
    ttft_ms = first_token_latencies(ok) * 1e3
    assert len(ttft_ms) == len(ok)

    result = {
        "requests": len(done),
        "prompt_lens": list(PROMPT_LENS),
        "max_new_tokens": max_new_tokens,
        "n_pages": n_pages,
        "full_reservation_pages": full_reservation,
        "prefill_chunk_tokens": 32,
        "ttft_ms_p50": float(np.percentile(ttft_ms, 50)),
        "ttft_ms_p99": float(np.percentile(ttft_ms, 99)),
        "decode_tok_s": eng.throughput_tok_s(),
        "tokens_out": eng.metrics["tokens_out"],
        "decode_steps": eng.metrics["decode_steps"],
        "prefill_chunks": eng.metrics["prefill_chunks"],
        "chunk_batch_calls": eng.metrics["chunk_batch_calls"],
        "preemptions": eng.metrics["preemptions"],
        "energy_joules": eng.metrics["energy_joules"],
        "tokens_per_joule": eng.metrics["tokens_out"]
                            / max(eng.metrics["energy_joules"], 1e-12),
    }
    result["shared_prefix"] = run_shared_prefix(model, params, quiet=quiet)
    result["parallel_sampling"] = run_parallel_sampling(model, params,
                                                        quiet=quiet)
    result["shape_churn"] = run_shape_churn(model, params, quiet=quiet)
    result["long_context"] = run_long_context(model, params, quiet=quiet)
    result["fault_tolerance"] = run_fault_tolerance(model, params,
                                                    quiet=quiet)
    result["spec_decode"] = run_spec_decode(model, params, quiet=quiet)
    result["open_loop"] = run_open_loop_serving(model, params, quiet=quiet)
    result["multi_chip"] = run_multi_chip(model, params, quiet=quiet)
    with open(json_path, "w") as fh:
        json.dump(result, fh, indent=2)
    if not quiet:
        print(f"enginebench/ttft_ms_p50,{result['ttft_ms_p50']:.1f},ms")
        print(f"enginebench/ttft_ms_p99,{result['ttft_ms_p99']:.1f},ms")
        print(f"enginebench/decode_tok_s,{result['decode_tok_s']:.1f},tok/s")
        print(f"enginebench/tokens_per_joule,"
              f"{result['tokens_per_joule']:.0f},tok/J"
              f" ({result['energy_joules']:.2e} J roofline,"
              f" mixed workload)")
        print(f"enginebench/preemptions,{result['preemptions']},count"
              f" (pool {n_pages}/{full_reservation} blocks,"
              f" {result['prefill_chunks']} chunks in"
              f" {result['chunk_batch_calls']} batched calls)")
    return result


WORKLOADS = {
    "shared_prefix": run_shared_prefix,
    "parallel_sampling": run_parallel_sampling,
    "shape_churn": run_shape_churn,
    "long_context": run_long_context,
    "fault_tolerance": run_fault_tolerance,
    "spec_decode": run_spec_decode,
    "open_loop": run_open_loop_serving,
    "multi_chip": run_multi_chip,
}


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", choices=[""] + sorted(WORKLOADS),
                    help="run a single workload (the CI multi-device "
                         "lane re-runs multi_chip under forced host "
                         "devices without repeating the full suite)")
    ap.add_argument("--json", default="BENCH_engine.json")
    args = ap.parse_args()
    if args.only:
        mdl, prms = _build_model()
        out = {args.only: WORKLOADS[args.only](mdl, prms)}
        with open(args.json, "w") as fh:
            json.dump(out, fh, indent=2)
    else:
        run(json_path=args.json)
