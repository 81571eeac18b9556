"""Serving engine: executes the Scheduler's step plans over the paged pool.

The paper serves batch-1 on an FPGA; its §5.2 names batched inference as
future work.  This engine is that future work, split into two layers:

  * :class:`~repro.serving.scheduler.Scheduler` (serving/scheduler.py)
    owns *policy*: waiting/running queues, admission (with prompt
    clamping and never-fits rejection), a per-step plan that carries up
    to ``prefill_chunk_tokens`` of prompt chunks **plus** every running
    decode (Sarathi-style chunked prefill — long prompts no longer stall
    the decode loop), and preemption (newest-first eviction with
    recompute-on-resume) when mid-decode growth finds the block pool
    exhausted.
  * :class:`Engine` (this file) owns *mechanism*: it executes each plan
    verbatim — all prompt chunks of a step via ONE padded
    ``model.prefill_chunk_batch`` call writing straight into the paged
    pool (attending the already-written prefix through the page table),
    running decodes as one batched ``decode_step`` — plus sampling, RNG,
    timing and metrics.

KV memory is **paged** by default (vLLM-style, serving/paged_cache.py):
the device cache is a pool of ``page_size``-token blocks shared by every
slot through a page table; the scheduler *leases* blocks to sequences as
their lengths grow (refcounted — a block may back several slots) and
drops the leases on finish *or preemption*, so an oversubscribed pool
(``n_pages`` below the full reservation) degrades to eviction + recompute
instead of raising ``OutOfBlocks``.  On top of the leases sits **automatic
prefix caching**: after executing a chunk or decode the engine registers
every freshly-filled full block into the allocator's hash-chained prefix
index (token-content addressed), admission maps a request's longest
cached prefix read-only into its page table, and the plan's chunks start
past it — the shared prefix runs zero prefill tokens and, because decode
attention already reads through the page table, needs no kernel changes.
The engine also executes the plan's copy-on-write pairs (device block
copies) before any write into a previously-shared block, and runs ALL of
a step's prefill chunks as ONE **shape-stable** batched
``prefill_chunk_batch`` device call: the batch is padded to a fixed
``(max_slots, prefill_chunk_tokens)`` extent and every row's
``(chunk_len, pos_offset)`` rides along as traced data, so the chunk
step compiles once per pool key instead of once per distinct
``(B, chunk_len, pos_offset)`` triple (``metrics["prefill_compiles"]``
and the per-step ``plan_log`` entries expose the count; see
docs/ARCHITECTURE.md for the shape-stability contract).  Families whose
cache is not a single attention bank (ssm / hybrid / audio /
interleaved-moe) fall back to the dense per-slot reservation, where
prompts are admitted as one whole-prompt chunk and preemption/caching
never trigger.

Sampling matches the paper's evaluation setup: temperature 1.0, top-p 1.0
(A.1) — but each request's ``temperature``/``top_p`` are honored, threaded
through one vectorized sampler call per step (no per-slot Python loops).

**Parallel sampling (the ``Request.n_samples`` contract).**  A request
with ``n_samples = n > 1`` is best-of-n: it admits once, prefills its
prompt once, and at the first sampled token fans out into ``n`` sibling
sequences — ``sample_logits_per_row`` draws ``n`` tokens from the ONE
prompt-logits row, then ``Scheduler.fork_group`` leases the parent's
blocks into ``n - 1`` reserved slots (prompt KV shared read-only,
refcounted; diverging tails un-share lazily through copy-on-write).  On
completion ``Request.outputs`` is a list of ``n`` token lists, one per
sibling, and ``Request.output`` aliases ``outputs[0]``; ``t_first_token``
stamps the fanout (all siblings share it) and the request is done when
its last sibling finishes.  ``outputs`` is populated (as ``[output]``)
for ``n_samples=1`` requests too.  Requires the paged pool —
``cache_kind="dense"`` rejects ``n > 1`` with ``.error``.

**Sampling streams.**  Every request owns a PRNG root: ``PRNGKey(seed)``
when ``Request.seed`` is set, else split off the engine key at submit.
Sibling ``i`` samples from the stream ``fold_in(root, stream + i)``
(``Request.stream`` defaults to 0), and its ``t``-th token uses
``fold_in(stream_key, t)`` — so a sibling's draw depends only on (root,
stream index, position), never on batch composition or scheduling order.
That is what makes fanout *bit-exact*: sibling ``i`` of an
``(seed=s, n_samples=n)`` request produces the identical token stream to
an independent ``(seed=s, stream=i, n_samples=1)`` request — proved in
tests/test_prefix_cache.py, exploited by ``Request.stream`` to shard one
logical best-of-n across engines.  Per-sibling ``stop_tokens`` (on top
of the global ``eos_id``) let siblings in one group retire on different
ids.

**Speculative decoding (``spec_tokens > 0``).**  Draft-then-verify on
the paged pool: a host-side proposer (serving/spec_decode.py — n-gram
prompt-lookup by default, a small draft model behind the same
``propose()`` interface) guesses up to ``spec_tokens`` next tokens per
running sequence, and the scheduler plans a :class:`SpecVerify` instead
of that slot's decode.  The engine verifies ALL drafts in one device
call by treating them as a k+1-token *chunk* — ``verify_chunk_batch``
is the all-positions-logits twin of ``prefill_chunk_batch``, padded to
a fixed ``(max_slots, spec_tokens + 1)`` extent, so it reuses the fused
paged chunk-attention kernel and holds its own one-executable-per-pool-
key bound (``metrics["verify_compiles"]``).  Acceptance re-samples
every position from the *verified* logits with the exact per-position
key non-speculative decode would have used (``fold_in(stream_key, t)``
for output position ``t``), so greedy speculative streams are
bit-identical to non-speculative streams and sampled streams stay
composition-independent however many drafts land; drafts only decide
how many tokens commit per step, never which.  Rejection rollback is
**block-pool truncation**: ``BlockAllocator.truncate`` shrinks the
slot's lease to the accepted length through the normal release path,
and since the engine registers prefix-index blocks only *after*
acceptance, speculative KV is never reachable from the prefix index.
``metrics`` reports ``draft_tokens`` / ``accepted_tokens`` /
``accept_ratio`` / ``steps_per_token`` (per-sequence device steps per
emitted token: 1.0 = plain decode, < 1.0 = speculation paying off).

Knobs: ``prefill_chunk_tokens`` bounds prompt work per step (the
prefill/decode interleaving grain); ``page_size``/``n_pages`` size the
pool; ``prefix_caching`` toggles the block index (on by default);
``spec_tokens``/``draft_proposer`` turn on speculative decoding;
``preempt_limit`` is the scheduler's starvation bound.  ``Engine.plan_log``
keeps the executed step plans (uids, chunk ranges, preemptions, COW
pairs, cached-prefix admissions, fanouts) for inspection — tests assert
chunk/decode interleaving and prefix skips on it, and
benchmarks/engine_bench.py reports preemption counts, prefix-cache hit
rates and fork-sharing block savings from it.

**Host spans.**  Each phase of a step runs inside a
``jax.profiler.TraceAnnotation`` named ``engine.<phase>`` (free when the
profiler is off), so a profile puts the device's idle time down to the
host work that held it: ``engine.step`` (carrying the step index that
``plan_log`` entries record as ``step``) holds ``engine.schedule``,
``engine.page_table``, ``engine.chunk`` (with ``engine.chunk_sync``,
``engine.register`` and ``engine.first_token``), ``engine.compile_probe``
and ``engine.decode_dispatch`` (with ``engine.sample``); the deferred
half of a pipelined step runs in ``engine.finish`` (``engine.sync``,
``engine.register``, ``engine.step_tail``).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation, annotate_function

from repro.launch.roofline import step_joules, tree_bytes
from repro.models.model import Model, count_params
from repro.runtime.health import StragglerDetector
from repro.serving.faults import (ERR_AUDIT, ERR_DEADLINE, ERR_FAULT,
                                  ERR_NAN, ERR_SHED, SITE_DECODE,
                                  SITE_PREFILL, FaultInjector,
                                  InjectedFault, SchedulerStall)
from repro.serving.paged_cache import (BlockAllocator, PagedConfig,
                                       chain_hash)
from repro.serving.scheduler import (PrefillChunk, Scheduler, SpecVerify,
                                     StepPlan, validate_request)
from repro.serving.spec_decode import build_proposer


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray            # (len,) int32
    max_new_tokens: int = 64
    temperature: float = 1.0
    top_p: float = 1.0
    n_samples: int = 1            # best-of-n: fork n siblings at token 1
    seed: Optional[int] = None    # PRNG root (None: engine-assigned)
    stream: int = 0               # sampling-stream offset (sibling i
    #                               draws stream ``stream + i``)
    stop_tokens: Optional[Sequence[int]] = None  # per-request stop ids
    #                               honored alongside the global eos_id
    deadline_ms: Optional[float] = None       # total budget since submit;
    #                               the watchdog fails the request (typed
    #                               .error) when it expires mid-flight
    ttft_deadline_ms: Optional[float] = None  # first-token budget
    # filled by the engine:
    output: Optional[List[int]] = None           # == outputs[0]
    outputs: Optional[List[List[int]]] = None    # one stream per sibling
    t_enqueue: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0
    error: Optional[str] = None   # set when the engine rejects the request
    error_kind: Optional[str] = None  # typed failure domain (faults.ERR_*)
    rng_key: Any = None           # PRNG root (derived from seed / engine)


def sample_logits(key, logits: jax.Array, temperature=1.0,
                  top_p=1.0) -> jax.Array:
    """Temperature + nucleus sampling; (B, V) -> (B,) int32.

    ``temperature``/``top_p`` may be scalars or per-row (B,) arrays — the
    engine passes one array per batch so heterogeneous requests sample
    correctly in a single vectorized call.  ``temperature <= 0`` rows are
    greedy (argmax)."""
    b = logits.shape[0]
    t = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (b,))
    p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (b,))
    p = jnp.maximum(p, 1e-6)                   # keep at least the top token

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(t, 1e-6)[:, None]
    sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    csum = jnp.cumsum(probs, axis=-1)
    # smallest k with cumulative prob >= top_p, per row
    keep = csum - probs < p[:, None]
    thresh = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1,
                     keepdims=True)
    masked = jnp.where(scaled >= thresh, scaled, -jnp.inf)
    sampled = jax.random.categorical(key, masked, axis=-1).astype(jnp.int32)
    return jnp.where(t <= 0.0, greedy, sampled)


def sample_logits_per_row(keys, logits: jax.Array, temperature=1.0,
                          top_p=1.0) -> jax.Array:
    """Per-row *keyed* temperature + nucleus sampling.

    ``keys`` is a stacked (B, key) array — one PRNG key per row — and
    row ``i``'s draw depends only on ``(keys[i], logits[i],
    temperature[i], top_p[i])``.  That row-independence is the engine's
    bit-exactness lever: a sequence's sampled stream is identical
    whether its row is batched with 0 or B-1 others, so a fork sibling
    replays exactly as an independent request and a preempted sequence
    resumes its stream unchanged.  (``sample_logits`` above draws the
    whole batch from ONE key, which ties each row's outcome to the batch
    composition.)"""
    b = logits.shape[0]
    t = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (b,))
    p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (b,))
    one = lambda k, l, tt, pp: sample_logits(k, l[None], tt, pp)[0]
    return jax.vmap(one)(keys, logits, t, p)


def legacy_chunk_shape_keys(plan_log) -> set:
    """The ``(B, chunk_len, pos_offset)`` compile keys a per-shape-grouped
    engine would have used for the chunks in ``plan_log`` — the
    counterfactual cost that shape-stable padding avoids.  Consumed by
    the shape_churn benchmark (CI gates on it being larger than the real
    compile count) and tests/test_compile_stability.py."""
    keys = set()
    for plan in plan_log:
        groups: Dict[Any, int] = {}
        for (_, s, e) in plan.get("prefills", []):
            groups[(e - s, s)] = groups.get((e - s, s), 0) + 1
        keys |= {(n, ln, off) for (ln, off), n in groups.items()}
    return keys


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_pool_blocks(attn, src, dst):
    """Copy whole pool blocks src -> dst across every layer (and scale
    pools for int8) — the device half of copy-on-write.  Buffers are
    (L, NB, BS, ...); donation keeps it in place."""
    return {kk: buf.at[:, dst].set(buf[:, src]) for kk, buf in attn.items()}


@dataclasses.dataclass
class _PendingDecode:
    """A dispatched-but-unsynced batched decode step.  ``nxt`` and
    ``finite`` are still device arrays (JAX async dispatch): the host
    has NOT blocked on the sampled tokens yet.  ``_decode_complete``
    converts them and runs all token-dependent bookkeeping."""

    slots: List[int]
    failed: List["Request"]
    nxt: Any = None                 # device array of sampled tokens
    finite: Any = None              # device array, per-row finiteness
    t0: float = 0.0


@dataclasses.dataclass
class _PendingStep:
    """An engine step whose decode host-sync was deferred by
    :meth:`Engine.step_async`.  Everything token-independent (plan,
    prefill chunks, COW copies, device dispatch of decode + sampling)
    already ran; :meth:`Engine.finish_step` blocks on the tokens and
    finishes the step's bookkeeping."""

    decode: _PendingDecode
    plan: Any
    t_step: float


class Engine:
    """Single-host continuous-batching engine (plan executor).

    ``decode_fn(params, cache, tokens) -> (logits, cache)`` and
    ``prefill_fn(params, batch, max_seq) -> (logits, cache)`` come from
    the (possibly jitted/sharded) model; the engine itself is pure
    orchestration and identical whether the steps run on 1 CPU or a pod.

    ``cache_kind="paged"`` (default) serves from the block pool when the
    model family supports it; ``"dense"`` forces the contiguous per-slot
    reservation.  ``n_pages`` sizes the pool (default: full reservation);
    shrinking it oversubscribes, which the scheduler absorbs by deferring
    admission and preempting on mid-decode growth.  Requests that could
    never run (prompt larger than the whole pool, ``max_new_tokens >=
    max_seq``, empty prompt) come back from :meth:`run` with ``.error``
    set instead of raising or spinning.
    """

    def __init__(self, model: Model, params: Any, max_slots: int = 8,
                 max_seq: int = 1024, eos_id: int = 2, seed: int = 0,
                 cache_kind: str = "paged", page_size: int = 64,
                 n_pages: Optional[int] = None,
                 prefill_chunk_tokens: int = 512,
                 prefix_caching: bool = True, preempt_limit: int = 3,
                 faults: Any = None, clock: Any = None,
                 nan_guard: bool = True, retry_limit: int = 2,
                 audit_interval: int = 0,
                 shed_after_preempts: Optional[int] = None,
                 stall_shed_limit: int = 3,
                 spec_tokens: int = 0, draft_proposer: Any = None,
                 mesh: Any = None):
        self.model = model
        self.params = params
        # -- tensor-parallel serving (mesh=None = single-device path) -----
        # Storage-sharded / compute-replicated: the paged pool shards its
        # KV-heads dim and weights are stored sharded, but every
        # cross-device collective the scheme induces is an all-gather, so
        # streams stay bit-identical to the unsharded engine.  The
        # allocator/scheduler never see the mesh — block ids, page
        # tables, leases and StepPlans are device-count-agnostic.
        self.mesh = mesh
        self._rep = None
        if mesh is not None:
            if cache_kind != "paged":
                raise ValueError("mesh serving requires the paged cache")
            self._rep = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec())
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.prefill_chunk_tokens = prefill_chunk_tokens
        # -- speculative decoding (module docstring) ----------------------
        # draft_proposer: None/str -> built by name ("ngram" default);
        # anything with .propose(prompt, output, k) is used as-is
        self.spec_tokens = spec_tokens
        if spec_tokens > 0 and (draft_proposer is None
                                or isinstance(draft_proposer, str)):
            draft_proposer = build_proposer(draft_proposer or "ngram")
        self.draft_proposer = draft_proposer
        # -- fault domain (serving/faults.py) ----------------------------
        # clock: None = wall time; else a callable or .now() object (a
        # SimClock makes deadlines and latency faults deterministic)
        if clock is None:
            self._now = time.perf_counter
        elif hasattr(clock, "now"):
            self._now = clock.now
        else:
            self._now = clock
        self._clock = clock
        if faults is not None and not isinstance(faults, FaultInjector):
            faults = FaultInjector(faults)     # accept a bare FaultPlan
        self.faults: Optional[FaultInjector] = faults
        self.nan_guard = nan_guard
        self.retry_limit = retry_limit         # pre-dispatch retries/step
        self.audit_interval = audit_interval   # 0 = no periodic audit
        self.shed_after_preempts = shed_after_preempts
        self.stall_shed_limit = stall_shed_limit
        self.fault_log: List[Dict[str, Any]] = []
        self.straggler = StragglerDetector(n_hosts=1)
        # decode is the hot loop: jit once (cache/params structures are
        # stable).  Donating the cache avoids a copy per token.  The named
        # function gives the program a stable name (``jit_decode_step``).
        step_fn = (model.decode_step if mesh is None else
                   functools.partial(model.decode_step, mesh=mesh))

        def decode_step(params, cache, tokens):
            return step_fn(params, cache, tokens)
        self._decode = jax.jit(decode_step, donate_argnums=(1,))
        self.key = jax.random.PRNGKey(seed)

        self.paged = (cache_kind == "paged"
                      and model.init_paged_cache is not None)
        self.pager: Optional[BlockAllocator] = None
        if self.paged:
            self.page_size = page_size
            mb = -(-max_seq // page_size)
            self.n_pages = n_pages or max_slots * mb
            self.pager = BlockAllocator(PagedConfig(
                n_layers=model.cfg.n_layers,
                n_kv_heads=model.cfg.n_kv_heads, head_dim=model.cfg.hd(),
                block_size=page_size, n_blocks=self.n_pages,
                max_slots=max_slots, max_blocks_per_seq=mb),
                enable_prefix_cache=prefix_caching)
            self.cache = model.init_paged_cache(
                max_slots, block_size=page_size, n_blocks=self.n_pages,
                max_blocks_per_seq=mb)
            if mesh is not None:
                # place the pool sharded (KV-heads over `model`) and the
                # host-authored control state replicated; params follow
                # the serve-mode specs.  Only placement changes — the
                # allocator addresses block ids exactly as before.
                from repro.distribution import sharding as shardlib
                cspecs = shardlib.cache_specs(model.cfg, self.cache, mesh)
                self.cache = jax.device_put(
                    self.cache, shardlib.to_shardings(cspecs, mesh))
                if mesh.shape.get("model", 1) <= 1:
                    # a size-1 `model` axis divides everything, so the
                    # serve specs would keep their axis names — and
                    # GSPMD propagates those (physically replicated but
                    # named) annotations from the weights onto jit
                    # outputs like the int8 scale pools, where they
                    # mismatch the replicated placement above and buy a
                    # second executable per pool key.  Replicate.
                    self.params = jax.device_put(params, self._rep)
                else:
                    pspecs = shardlib.param_specs(model.cfg, params, mesh,
                                                  mode="serve")
                    self.params = jax.device_put(
                        params, shardlib.to_shardings(pspecs, mesh))
        else:
            self.cache = model.init_cache(max_slots, max_seq)
        self.scheduler = Scheduler(
            max_slots=max_slots, max_seq=max_seq, pager=self.pager,
            prefill_chunk_tokens=prefill_chunk_tokens,
            preempt_limit=preempt_limit, spec_tokens=spec_tokens,
            draft_proposer=self.draft_proposer)
        # -- roofline energy model (launch/roofline.step_joules) ----------
        # every device call streams the weights once plus the KV rows it
        # touches; KV traffic is modeled for the paged pool only (dense
        # families fall back to weight streaming, which dominates anyway)
        self._param_bytes = float(tree_bytes(params))
        self._n_params = float(count_params(params))
        if self.paged:
            per_pos = (2 * model.cfg.n_kv_heads * model.cfg.hd()
                       * self.cache["attn"]["k"].dtype.itemsize)
            if "ks" in self.cache["attn"]:
                per_pos += 2 * model.cfg.n_kv_heads * 4   # dequant scales
            self._kv_row_bytes = per_pos * model.cfg.n_layers
        else:
            self._kv_row_bytes = 0
        self.plan_log: List[Dict[str, Any]] = []
        self.metrics = {"tokens_out": 0, "requests_done": 0,
                        "decode_steps": 0, "t_decode": 0.0,
                        "prefill_chunks": 0, "preemptions": 0,
                        "chunk_batch_calls": 0, "cow_copies": 0,
                        "prefix_hits": 0, "prefix_cached_tokens": 0,
                        "fanouts": 0,
                        "blocks_live_peak": 0,
                        "blocks_saved_by_sharing_peak": 0,
                        "prefill_compiles": 0,
                        # roofline accounting: prefix K/V bytes the
                        # chunk-attention step reads — live tiles through
                        # the page table vs the legacy full-extent gather
                        "prefix_attn_bytes": 0,
                        "prefix_attn_bytes_gather": 0,
                        # the same for the decode step's attention: live
                        # pages through the page table vs every decode
                        # row's full page-table span
                        "decode_attn_bytes": 0,
                        "decode_attn_bytes_gather": 0,
                        # speculative decoding: drafts proposed/accepted,
                        # verify device calls + their compile bound, and
                        # rejection rollbacks (block-pool truncations).
                        # seq_steps counts per-SEQUENCE device steps, so
                        # steps_per_token = seq_steps / tokens_out is
                        # exactly 1.0 for plain decode and dips below it
                        # only when verification lands >1 token per step
                        "draft_tokens": 0, "accepted_tokens": 0,
                        "verify_steps": 0, "spec_rollbacks": 0,
                        "verify_compiles": 0, "seq_steps": 0,
                        "accept_ratio": 0.0, "steps_per_token": 0.0,
                        # modeled energy (roofline.step_joules) + per-
                        # request prefix-cache attribution
                        # (uid -> {cached_tokens, cache_hit})
                        "energy_joules": 0.0,
                        "requests": {},
                        # fault-domain counters
                        "step_retries": 0, "requests_failed": 0,
                        "requests_rejected": 0, "nan_rows": 0,
                        "deadline_misses": 0, "shed_requests": 0,
                        "stalls": 0, "audit_repairs": 0,
                        "slow_steps": 0}
        self._host_pt: Optional[np.ndarray] = None
        self._done_at_prefill: List[Request] = []  # first-token stops
        self._uid = 0
        self._step = 0                     # monotonic step index (faults
        #                                    key their schedules on it)
        self._rejected: List[Request] = [] # submit-time rejections, drained
        #                                    into run()'s done list
        self._pending: Optional[_PendingStep] = None  # step_async() in
        #                                    flight, awaiting finish_step()
        self._stall_streak = 0
        self._preempt_streak = 0
        if self.faults is not None:
            self.faults.bind(clock=self._clock, pager=self.pager)

    def _put(self, x, dtype=None):
        """Host -> device upload for step operands (tokens, lens, page
        tables, COW indices).  Under a mesh these must be *committed*
        replicated arrays — an uncommitted ``jnp.asarray`` upload would
        leave placement to jit and wobble the compile key; replication
        matches the engine's host-authored-control-state contract."""
        arr = np.asarray(x, dtype)
        if self.mesh is None:
            return jnp.asarray(arr)
        return jax.device_put(arr, self._rep)

    # -- public API ---------------------------------------------------------
    def submit(self, prompt: np.ndarray, **kw) -> int:
        """Enqueue a request; returns its uid.  Malformed requests
        (empty prompt, ``max_new_tokens`` that leaves no prompt room,
        ``n_samples < 1``, groups wider than the slot table or on the
        dense cache, prompts that could never fit the pool) get
        ``.error`` set here and come back from the next :meth:`run`
        without ever entering the scheduler; admission re-checks as the
        run-time backstop.

        Legal at ANY time, including between :meth:`step_async` and
        :meth:`finish_step` while a device step is in flight — the
        arrival enters the waiting queue and is considered at the next
        ``schedule()``.  Pass ``t_enqueue`` to stamp the request's TRUE
        arrival time (open-loop serving releases arrivals between
        steps, possibly after their scheduled instant; queueing delay
        and deadlines must be charged from arrival, not release)."""
        self._uid += 1
        t_enq = kw.pop("t_enqueue", None)
        req = Request(uid=self._uid, prompt=np.asarray(prompt, np.int32),
                      t_enqueue=self._now() if t_enq is None else t_enq,
                      output=[], **kw)
        if req.seed is not None:
            req.rng_key = jax.random.PRNGKey(req.seed)
        else:
            self.key, req.rng_key = jax.random.split(self.key)
        err = validate_request(req, self.max_seq, self.max_slots,
                               self.pager)
        if err is not None:
            req.error, req.error_kind = err
            self._rejected.append(req)
            return req.uid
        self.scheduler.add(req)
        return req.uid

    def submit_request(self, prompt: np.ndarray, **kw) -> Request:
        """:meth:`submit`, but returning the :class:`Request` object
        itself.  The async front-end holds it to stream ``output`` /
        ``outputs`` deltas per step while the request is mid-flight."""
        uid = self.submit(prompt, **kw)
        if self._rejected and self._rejected[-1].uid == uid:
            return self._rejected[-1]
        req = self.scheduler.request(uid)
        assert req is not None, f"submitted uid {uid} vanished"
        return req

    def run(self, max_steps: int = 10_000) -> List[Request]:
        """Serve until the scheduler drains.  Rejected requests (clamped
        ``max_new_tokens``, empty prompt, or a sequence the pool could
        never hold) come back in the done list with ``.error`` set — as
        do requests failed mid-flight by the fault layer (persistent
        step faults, NaN rows, deadline expiry, audit quarantine, load
        shedding), each with a typed ``.error_kind`` while the rest of
        the batch keeps serving.

        ``run()`` is the closed-loop surface: a plain loop over
        :meth:`step`.  Continuous-arrival serving drives
        :meth:`step_async` / :meth:`finish_step` instead (see
        serving/async_serving.py)."""
        done: List[Request] = []
        for _ in range(max_steps):
            out = self.step()
            if out is None:
                break
            done.extend(out)
        return done

    def step(self) -> Optional[List[Request]]:
        """Execute ONE scheduler step synchronously: drain submit-time
        rejections, schedule, run the planned chunk/decode/verify work,
        and return the requests that completed or failed during the
        step (possibly an empty list).  Returns ``None`` when the
        engine is idle — no queued rejections and no scheduler work."""
        done, pending = self._step_impl(sync=True)
        assert pending is None
        return done

    def step_async(self):
        """Like :meth:`step`, but WITHOUT blocking on the decode's
        sampled tokens: returns ``(done, pending)`` where ``pending``
        (when not None) holds the dispatched-but-unsynced device work.
        JAX async dispatch means the device is computing the decode and
        the per-row sampling while the host is free — the front-end
        uses that window to ingest new arrivals and flush streamed
        tokens from earlier steps, then calls :meth:`finish_step` to
        block on the tokens and finish the token-dependent bookkeeping
        (append, block registration, stop detection).  Steps whose host
        effects are token-coupled within the step (speculative
        verifies, the fault layer's intra-step isolation) run fully
        synchronously and return ``pending=None``."""
        return self._step_impl(sync=False)

    def finish_step(self, pending: Optional[_PendingStep] = None
                    ) -> List[Request]:
        """Complete a :meth:`step_async` step: block on the sampled
        tokens, append them, register completed blocks, retire stops,
        and close out the step's accounting.  No-op (returns ``[]``)
        when nothing is pending."""
        if pending is None:
            pending = self._pending
        if pending is None:
            return []
        self._pending = None
        with TraceAnnotation("engine.finish"):
            done = self._decode_complete(pending.decode)
            self._step_tail(pending.plan, pending.t_step)
        return done

    def _step_impl(self, sync: bool):
        """One scheduler step.  Returns ``(done, pending)``;
        ``done is None`` means the engine was idle.  ``sync=False``
        defers the decode host-sync into ``pending`` when the step
        allows it (see :meth:`step_async`)."""
        if self._pending is not None:
            raise RuntimeError(
                "finish_step() must complete the in-flight step before "
                "the next one is dispatched")
        done: List[Request] = []
        if self._rejected:
            now = self._now()
            for req in self._rejected:
                req.t_done = now
                self.metrics["requests_rejected"] += 1
                done.append(req)
            self._rejected = []
        if not self.scheduler.has_work():
            return (done if done else None), None
        self._step += 1
        with TraceAnnotation("engine.step", step=self._step):
            return self._run_step(sync, done)

    def _run_step(self, sync: bool, done: List[Request]):
        """The body of :meth:`_step_impl` for a step with work: plan it,
        execute the plan, and return ``(done, pending)``."""
        stalled = (self.faults is not None
                   and self.faults.pre_step(self._step, self.scheduler))
        if (self.paged and self.audit_interval
                and self._step % self.audit_interval == 0):
            # BEFORE schedule(): a corrupted block must be caught and
            # quarantined before the allocator can hand it out again
            done.extend(self._run_audit())
            if not self.scheduler.has_work():
                return done, None
        # an injected stall skips scheduling — the engine sees the
        # idle plan a wedged scheduler would have produced
        if stalled:
            plan = StepPlan()
        else:
            with TraceAnnotation("engine.schedule"):
                plan = self.scheduler.schedule()
        now = self._now()
        for req in plan.rejected:
            req.t_done = now
            self.metrics["requests_rejected"] += 1
            done.append(req)
        expired = self._enforce_deadlines(plan)
        done.extend(expired)
        if not plan.made_progress() and not expired:
            # the scheduler's contract is defer-preempt-or-reject; an
            # idle plan with work pending means that contract broke.
            # Fault layer on: degrade (shed the lowest-value waiter,
            # keep serving) — off: raise the typed stall with the
            # queue snapshot (the seed engine spun here).
            done.extend(self._handle_stall(stalled))
            return done, None
        self._stall_streak = 0
        if plan.preempted and self.shed_after_preempts is not None:
            self._preempt_streak += 1
            if self._preempt_streak >= self.shed_after_preempts:
                # preemption thrash: repeated evict/recompute cycles
                # mean demand exceeds the pool — shed load instead
                done.extend(self._shed(
                    f"{self._preempt_streak} consecutive preempting "
                    "steps (thrash)"))
                self._preempt_streak = 0
        elif not plan.preempted:
            self._preempt_streak = 0
        self.plan_log.append(dict(plan.summary(), step=self._step))
        for uid, cached in plan.admitted:
            # first admission wins: a preempt-resume re-admission must
            # not overwrite the request's original cache attribution
            self.metrics["requests"].setdefault(
                uid, {"cached_tokens": int(cached),
                      "cache_hit": cached > 0})
        self.metrics["preemptions"] = self.scheduler.n_preempted
        self.metrics["prefix_hits"] = \
            self.scheduler.prefix_stats["hits"]
        self.metrics["prefix_cached_tokens"] = \
            self.scheduler.prefix_stats["cached_tokens"]
        if self.paged and (plan.has_work() or plan.cows):
            with TraceAnnotation("engine.page_table"):
                if plan.has_work():
                    # one republish per step covers this step's
                    # allocations, COW remaps, and any releases
                    # (finish/preempt) since the last one; the host copy
                    # is kept for chunk addressing so the batched calls
                    # never read the table back off-device.
                    self._host_pt = self.pager.page_table()
                    self.cache["page_table"] = self._put(self._host_pt)
                if plan.cows:
                    # copy-on-write: duplicate the shared blocks' rows
                    # before this step's writes land in the fresh copies.
                    # (Counted here, not from allocator stats — a
                    # retracted victim's pair never reaches execution.)
                    src = self._put([s for s, _ in plan.cows], np.int32)
                    dst = self._put([d for _, d in plan.cows], np.int32)
                    self.cache["attn"] = _copy_pool_blocks(
                        self.cache["attn"], src, dst)
                    self.metrics["cow_copies"] += len(plan.cows)
        t_step = self._now()
        if plan.prefills:
            done.extend(self._run_chunks(plan.prefills))
            # shape-stability probe: the chunk step's distinct-XLA-
            # executable count must stay pinned at one per pool key
            # however traffic churns chunk lengths / offsets / batch
            # width (gated by tests + the shape_churn benchmark)
            with TraceAnnotation("engine.compile_probe"):
                self.metrics["prefill_compiles"] = \
                    self.prefill_compile_count()
            self.plan_log[-1]["prefill_compiles"] = \
                self.metrics["prefill_compiles"]
        if self._done_at_prefill:
            # sequences whose FIRST sampled token was terminal (stop
            # id / eos / max_new_tokens=1) retired inside the chunk
            done.extend(self._done_at_prefill)
            self._done_at_prefill = []
        if plan.decodes:
            if sync or plan.verifies or self.faults is not None:
                done.extend(self._decode_once(plan.decodes))
            else:
                # pipelined: decode + sampling are dispatched (device
                # busy), the host returns WITHOUT blocking on tokens.
                # Verify steps are excluded — their truncate/register
                # ordering is token-coupled within the step — as is the
                # fault layer, whose intra-step isolation hooks must
                # observe each row's outcome before the step closes.
                self._pending = _PendingStep(
                    decode=self._decode_dispatch(plan.decodes),
                    plan=plan, t_step=t_step)
                return done, self._pending
        if plan.verifies:
            # AFTER decodes: a verify's truncation frees blocks that
            # only re-enter circulation at the next schedule(), so
            # nothing executed this step can observe the rollback
            done.extend(self._run_verifies(plan.verifies))
            with TraceAnnotation("engine.compile_probe"):
                self.metrics["verify_compiles"] = \
                    self.verify_compile_count()
            self.plan_log[-1]["verify_compiles"] = \
                self.metrics["verify_compiles"]
        self._step_tail(plan, t_step)
        return done, None

    @functools.partial(annotate_function, name="engine.step_tail")
    def _step_tail(self, plan: StepPlan, t_step: float) -> None:
        """Per-step accounting that must run after the step's tokens
        have landed (spec ratios read ``tokens_out``; sharing peaks
        read post-release refcounts)."""
        drafted = self.metrics["draft_tokens"]
        self.metrics["accept_ratio"] = (
            self.metrics["accepted_tokens"] / drafted if drafted
            else 0.0)
        self.metrics["steps_per_token"] = (
            self.metrics["seq_steps"]
            / max(1, self.metrics["tokens_out"]))
        if plan.has_work() and self.straggler.record_slow(
                0, self._now() - t_step):
            self.metrics["slow_steps"] += 1
        if self.paged:
            # fork-sharing accounting: each lease beyond a block's
            # first is a block NOT copied (shared prompt KV)
            live = shared = 0
            for rc in self.pager.refcount:
                if rc > 0:
                    live += 1
                    shared += rc - 1
            self.metrics["blocks_live_peak"] = max(
                self.metrics["blocks_live_peak"], live)
            self.metrics["blocks_saved_by_sharing_peak"] = max(
                self.metrics["blocks_saved_by_sharing_peak"], shared)

    def cache_utilization(self) -> float:
        """Fraction of the KV pool in use (slots-occupied for dense)."""
        if self.paged:
            return self.pager.utilization()
        return len(self.scheduler.running) / self.max_slots

    def throughput_tok_s(self) -> float:
        """DECODE-ONLY throughput: ``tokens_out / t_decode``, where
        ``t_decode`` is wall time inside the batched decode step
        (dispatch to token sync) and excludes prefill, scheduling, and
        host bookkeeping.  This is the figure BENCH_engine.json records
        as ``decode_tok_s`` and the CI gates compare; end-to-end
        tokens-per-wall-second is always lower and must be computed by
        the caller (the `[serve]` banner prints both, labeled)."""
        t = self.metrics["t_decode"]
        return self.metrics["tokens_out"] / t if t > 0 else 0.0

    def prefill_compile_count(self) -> int:
        """Distinct XLA compiles of the chunked-prefill step so far in
        this process (shared across engines with the same model config —
        that sharing is the point: one pool key, one executable)."""
        if self.model.prefill_compile_count is None:
            return 0
        return self.model.prefill_compile_count(mesh=self.mesh)

    def verify_compile_count(self) -> int:
        """Distinct XLA compiles of the speculative verify step (the
        all-positions-logits chunk entry) — same one-per-pool-key bar
        as the prefill chunk, probed separately because the two entries
        are distinct executables."""
        if self.model.verify_compile_count is None:
            return 0
        return self.model.verify_compile_count(mesh=self.mesh)

    # -- fault domain ---------------------------------------------------
    def _fail_request(self, req: Request, msg: str, kind: str,
                      plan: Any = None, quarantine: bool = False
                      ) -> Request:
        """Fail ONE request (its whole sampling group) while the rest of
        the batch keeps serving: quarantine its self-written KV blocks
        when their content is suspect (NaN), retract anything it still
        has planned, release every lease, stamp the typed error."""
        if self.paged and quarantine:
            bs = self.page_size
            for slot, seq in list(self.scheduler.running.items()):
                if seq.req is req:
                    self.pager.quarantine(slot, seq.cached_len // bs)
        self.scheduler.fail_request(req, plan)
        req.error = msg
        req.error_kind = kind
        req.t_done = self._now()
        self.metrics["requests_failed"] += 1
        return req

    def _survive_faults(self, site: str, items: List[Any], uid_of,
                        alive) -> tuple:
        """Pre-dispatch fault gate for one device batch.  Injected step
        exceptions fire *before* the (donating) device call, so a retry
        is always clean; a fault that persists past ``retry_limit``
        isolates its target request (``.error`` set, leases released)
        and the surviving rows dispatch without it.  Returns (surviving
        items, failed requests)."""
        failed: List[Request] = []
        attempts = 0
        while items:
            try:
                self.faults.raise_if_armed(
                    site, self._step, [uid_of(x) for x in items])
                break
            except InjectedFault as exc:
                attempts += 1
                self.metrics["step_retries"] += 1
                self.fault_log.append(
                    {"step": self._step, "kind": "retry", "site": site,
                     "uid": exc.uid, "attempt": attempts})
                if attempts <= self.retry_limit:
                    continue
                if exc.uid is None:
                    raise      # untargeted persistent fault: device loss,
                    #            nothing to isolate — propagate
                req = next(s.req for s in
                           self.scheduler.running.values()
                           if s.req.uid == exc.uid)
                failed.append(self._fail_request(
                    req, f"persistent {site}-step fault "
                         f"({attempts} attempts)", ERR_FAULT))
                self.fault_log.append(
                    {"step": self._step, "kind": "isolated", "site": site,
                     "uid": exc.uid, "attempts": attempts})
                items = [x for x in items if alive(x)]
                attempts = 0
        return items, failed

    def _enforce_deadlines(self, plan: Any) -> List[Request]:
        """The per-step watchdog: fail every in-flight request past its
        TTFT or total deadline (work it had planned this step retracts;
        survivors' streams are unaffected — their sampling is per-row
        keyed)."""
        failed: List[Request] = []
        now = self._now()
        reqs: Dict[int, Request] = {}
        for seq in list(self.scheduler.running.values()) \
                + list(self.scheduler.waiting):
            reqs.setdefault(seq.req.uid, seq.req)
        for req in reqs.values():
            if req.error is not None:
                continue
            age_ms = (now - req.t_enqueue) * 1e3
            if (req.ttft_deadline_ms is not None
                    and req.t_first_token == 0.0
                    and age_ms > req.ttft_deadline_ms):
                which, budget = "ttft", req.ttft_deadline_ms
            elif req.deadline_ms is not None and age_ms > req.deadline_ms:
                which, budget = "total", req.deadline_ms
            else:
                continue
            self.metrics["deadline_misses"] += 1
            self.fault_log.append({"step": self._step, "kind": "deadline",
                                   "uid": req.uid, "budget": which})
            failed.append(self._fail_request(
                req, f"{which} deadline of {budget:g} ms exceeded "
                     f"({age_ms:.1f} ms since submit)", ERR_DEADLINE,
                plan=plan))
        return failed

    def _shed(self, reason: str) -> List[Request]:
        """Admission-reject the lowest-value waiter (typed .error)."""
        shed: List[Request] = []
        for req in self.scheduler.shed_load(1):
            req.error = f"load shed: {reason}"
            req.error_kind = ERR_SHED
            req.t_done = self._now()
            self.metrics["shed_requests"] += 1
            self.metrics["requests_failed"] += 1
            self.fault_log.append({"step": self._step, "kind": "shed",
                                   "uid": req.uid})
            shed.append(req)
        return shed

    def _handle_stall(self, injected: bool) -> List[Request]:
        """An idle plan with work pending.  Fault layer off: raise the
        typed :class:`SchedulerStall` (contract violation).  On: shed
        the lowest-value waiter and keep serving — bounded by
        ``stall_shed_limit`` consecutive stalls with nothing sheddable,
        after which the stall is genuine wedge and raises anyway."""
        self.metrics["stalls"] += 1
        self._stall_streak += 1
        waiting, running = (len(self.scheduler.waiting),
                            len(self.scheduler.running))
        snapshot = {
            "step": self._step, "injected": injected,
            "waiting": [s.req.uid for s in self.scheduler.waiting],
            "running": {slot: seq.req.uid for slot, seq
                        in sorted(self.scheduler.running.items())}}
        if self.faults is None:
            raise SchedulerStall(
                "scheduler made no progress with work pending "
                f"(waiting={waiting}, running={running})", snapshot)
        shed = self._shed("scheduler stall with work pending")
        self.fault_log.append({"step": self._step, "kind": "stall",
                               "injected": injected,
                               "shed": [r.uid for r in shed]})
        if not shed and self._stall_streak > self.stall_shed_limit:
            raise SchedulerStall(
                f"scheduler stalled {self._stall_streak} consecutive "
                f"steps with nothing left to shed (waiting={waiting}, "
                f"running={running})", snapshot)
        return shed

    def _run_audit(self) -> List[Request]:
        """Periodic allocator self-audit (every ``audit_interval``
        steps, before scheduling).  A dirty report repairs in place —
        corrupted blocks quarantined, free list/LRU/refcounts rebuilt —
        and fails exactly the requests leasing corrupted blocks; the
        pool is coherent again before any new block is handed out."""
        report = self.pager.audit(repair=True)
        if report.clean:
            return []
        self.metrics["audit_repairs"] += 1
        victims: Dict[int, Request] = {}
        for slot in report.victim_slots:
            seq = self.scheduler.running.get(slot)
            if seq is not None:
                victims.setdefault(seq.req.uid, seq.req)
        self.fault_log.append(
            {"step": self._step, "kind": "audit",
             "violations": list(report.violations),
             "corrupted_blocks": list(report.corrupted_blocks),
             "victims": sorted(victims)})
        return [self._fail_request(
                    req, "KV blocks quarantined by allocator audit "
                         f"({len(report.corrupted_blocks)} corrupted)",
                    ERR_AUDIT)
                for req in victims.values()]

    # -- internals ------------------------------------------------------
    def _account_energy(self, n_tokens: float, attn_pairs: float,
                        kv_rows_read: float) -> None:
        """Accumulate modeled energy for ONE device call
        (``metrics["energy_joules"]``, roofline.step_joules): the call
        streams the weights once plus the touched KV rows
        (``kv_rows_read`` reads + one write per token), and runs
        ``2·P`` FLOPs per token plus ``4·H·hd`` per (query, key)
        attention pair per layer.  benchmarks/engine_bench.py divides
        tokens by the total for the paper's tokens/J metric."""
        if n_tokens <= 0:
            return
        cfg = self.model.cfg
        bytes_moved = (self._param_bytes
                       + (kv_rows_read + n_tokens) * self._kv_row_bytes)
        flops = (2.0 * self._n_params * n_tokens
                 + 4.0 * cfg.n_heads * cfg.hd() * cfg.n_layers
                 * attn_pairs)
        self.metrics["energy_joules"] += step_joules(bytes_moved, flops)

    def _account_prefix_bytes(self, offs: np.ndarray,
                              lens: np.ndarray) -> None:
        """Roofline estimate of the prefix K/V traffic one chunk step
        reads, per layer and row: the fused kernel fetches
        ``ceil(prefix/block_size)`` live tiles through the page table
        (dead tiles are index_map-clamped revisits — no DMA), where the
        legacy path gathered every row's full ``max_blocks × block_size``
        extent.  Both go into ``metrics`` so BENCH_engine.json can chart
        bytes actually touched vs the gather baseline."""
        _, _, bs, kvh, hd = self.cache["attn"]["k"].shape
        mb = self.pager.cfg.max_blocks_per_seq
        n_layers = self.model.cfg.n_layers
        per_pos = 2 * kvh * hd * self.cache["attn"]["k"].dtype.itemsize
        if "ks" in self.cache["attn"]:
            per_pos += 2 * kvh * 4               # f32 dequant scales
        live = lens > 0
        live_tiles = int((-(-offs[live] // bs)).sum())
        self.metrics["prefix_attn_bytes"] += (
            live_tiles * bs * per_pos * n_layers)
        self.metrics["prefix_attn_bytes_gather"] += (
            int(live.sum()) * mb * bs * per_pos * n_layers)
        # same per-call numbers feed the energy model: prefix tiles are
        # the KV reads, and each row self-attends causally within its
        # own chunk (len·off cross pairs + len(len+1)/2 within-chunk)
        ln = lens.astype(np.int64)
        pairs = float((ln * offs + ln * (ln + 1) // 2).sum())
        self._account_energy(float(ln.sum()), pairs,
                             float(live_tiles * bs))

    def _account_decode_bytes(self, kv_lens: np.ndarray) -> None:
        """K/V bytes one decode step's attention reads for its decode
        rows, whose attention lengths (this step's token included) are
        ``kv_lens``: ``ceil(len/block_size)`` live pages each through the
        page table (the Pallas kernel), against every row's full
        ``max_blocks × block_size`` span (the jnp gather)."""
        bs = self.page_size
        page = bs * self._kv_row_bytes
        mb = self.pager.cfg.max_blocks_per_seq
        self.metrics["decode_attn_bytes"] += int(
            (-(-kv_lens // bs)).sum()) * page
        self.metrics["decode_attn_bytes_gather"] += len(kv_lens) * mb * page

    @functools.partial(annotate_function, name="engine.chunk")
    def _run_chunks(self, chunks: List[PrefillChunk]) -> List[Request]:
        """Execute ALL of this step's planned chunks — paged: one
        shape-stable batched ``prefill_chunk_batch`` call, padded to the
        fixed ``(max_slots, prefill_chunk_tokens)`` extent with per-row
        valid lengths/offsets as data (padding rows carry slot -1 and
        write nothing), writing every row's KV straight into its pool
        blocks; dense: per-sequence whole-prompt prefill merged into the
        slot.  Returns the requests the fault layer failed (persistent
        injected prefill faults, non-finite logits rows)."""
        failed: List[Request] = []
        if self.paged:
            if self.faults is not None:
                chunks, failed = self._survive_faults(
                    SITE_PREFILL, list(chunks),
                    uid_of=lambda c: c.seq.req.uid,
                    alive=lambda c:
                        self.scheduler.running.get(c.seq.slot) is c.seq)
                if not chunks:
                    return failed
            nrows, width = self.max_slots, self.prefill_chunk_tokens
            toks = np.zeros((nrows, width), np.int32)
            lens = np.zeros((nrows,), np.int32)
            offs = np.zeros((nrows,), np.int32)
            slots = np.full((nrows,), -1, np.int32)
            for i, c in enumerate(chunks):
                lens[i] = c.end - c.start
                toks[i, :lens[i]] = c.seq.tokens[c.start:c.end]
                offs[i] = c.start
                slots[i] = c.seq.slot
            logits, self.cache = self.model.prefill_chunk_batch(
                self.params, toks, self.cache, slots, offs,
                page_table=self._host_pt, chunk_lens=lens,
                mesh=self.mesh)
            self.metrics["chunk_batch_calls"] += 1
            self._account_prefix_bytes(offs, lens)
            if self.faults is not None:
                row_uids = [c.seq.req.uid for c in chunks]
                logits = self.faults.corrupt_logits(
                    SITE_PREFILL, self._step, logits, row_uids)
            finite = None
            if self.nan_guard:
                with TraceAnnotation("engine.chunk_sync"):
                    finite = np.asarray(
                        jnp.all(jnp.isfinite(logits), axis=-1))
            for i, c in enumerate(chunks):
                seq = c.seq
                if self.scheduler.running.get(seq.slot) is not seq:
                    continue     # torn down by an earlier row's failure
                if finite is not None and not finite[i]:
                    # a non-finite row means the KV this chunk wrote is
                    # poison: quarantine before anything registers, fail
                    # the request (its whole group) — the other rows of
                    # this very batch are unaffected
                    self.metrics["nan_rows"] += 1
                    self.fault_log.append(
                        {"step": self._step, "kind": "nan",
                         "site": SITE_PREFILL, "uid": seq.req.uid})
                    failed.append(self._fail_request(
                        seq.req, "non-finite logits during prefill",
                        ERR_NAN, quarantine=True))
                    continue
                self._register_blocks(seq)
                self._finish_chunk(c, logits[i:i + 1])
        else:
            for c in chunks:
                toks = jnp.asarray(c.seq.tokens[c.start:c.end], jnp.int32)
                logits, pcache = self.model.prefill(
                    self.params, {"tokens": toks[None, :]},
                    max_seq=self.max_seq)
                self._merge_slot_cache(c.seq.slot, pcache, c.end)
                self._finish_chunk(c, logits)
        return failed

    def _stop_hit(self, seq, tok: int) -> bool:
        """The per-token finish predicate — shared by the decode loop
        and the first-token sample so a stop id (or ``max_new_tokens=1``)
        retires a sequence no matter where the token came from."""
        req = seq.req
        return (tok == self.eos_id
                or (req.stop_tokens is not None and tok in req.stop_tokens)
                or len(seq.output) >= req.max_new_tokens
                or seq.kv_len >= self.max_seq - 1)

    def _finish_seq(self, seq) -> Optional[Request]:
        """Retire one sequence; returns the Request when it completed the
        whole request (its group's last sibling, or a singleton)."""
        req = seq.req
        self.scheduler.finish(seq.slot)
        if seq.group is not None:
            seq.group.finished += 1
            if seq.group.finished < seq.group.n:
                return None      # request done only when ALL siblings are
        req.t_done = self._now()
        if req.outputs is None:
            req.outputs = [seq.output]
        self.metrics["requests_done"] += 1
        self._preempt_streak = 0     # completions prove we are not thrashing
        return req

    def _seq_key(self, seq) -> jax.Array:
        """The sequence's sampling-stream root:
        ``fold_in(request_root, stream + sibling_index)`` — position
        ``t`` then draws with ``fold_in(stream_root, t)``."""
        if seq.sample_key is None:
            seq.sample_key = jax.random.fold_in(
                seq.req.rng_key, seq.req.stream + seq.sibling_index)
        return seq.sample_key

    def _finish_chunk(self, chunk: PrefillChunk, logits) -> None:
        """Per-chunk bookkeeping after the device call: count it and, on
        the prompt's last chunk, sample the first output token — for an
        ``n_samples > 1`` request, ``n`` tokens from this ONE logits row,
        then fan the sequence out into its fork siblings."""
        seq, req = chunk.seq, chunk.seq.req
        self.metrics["prefill_chunks"] += 1
        if not chunk.last:
            return
        if seq.resuming:
            # recompute-on-resume: the token after this prefix was
            # already sampled before preemption; decode re-feeds it.
            seq.resuming = False
            return
        n = req.n_samples
        with TraceAnnotation("engine.first_token"):
            keys = jnp.stack([jax.random.fold_in(self._seq_key(seq), 0)]
                             if n == 1 else
                             [jax.random.fold_in(
                                 jax.random.fold_in(req.rng_key,
                                                    req.stream + i), 0)
                              for i in range(n)])
            first = np.asarray(sample_logits_per_row(
                keys, jnp.broadcast_to(logits[:1], (n, logits.shape[-1])),
                req.temperature, req.top_p))
        if n == 1:
            sibs = [seq]
            seq.output.append(int(first[0]))
            req.outputs = [seq.output]
        else:
            sibs = self.scheduler.fork_group(seq)
            for i, s in enumerate(sibs):
                s.output.append(int(first[i]))
            req.outputs = [s.output for s in sibs]
            self.metrics["fanouts"] += 1
            self.plan_log[-1].setdefault("forked", []).append((req.uid, n))
            # sibling rows must carry the shared prompt length before
            # their first decode; their page-table rows publish at the
            # next step's republish (decode this step drops them: the
            # device still sees -1 in row 0 and pins the len back to 0,
            # which the post-decode resync overwrites)
            rows = jnp.asarray([s.slot for s in sibs[1:]], jnp.int32)
            self.cache["lens"] = jnp.asarray(self.cache["lens"]) \
                .at[rows].set(seq.kv_len)
        req.t_first_token = self._now()
        for s in sibs:
            # a first token can already be terminal (a stop id, eos, or
            # max_new_tokens=1) — retire the sibling here instead of
            # decoding past its stop
            if self._stop_hit(s, s.output[-1]):
                done = self._finish_seq(s)
                if done is not None:
                    self._done_at_prefill.append(done)

    def _register_blocks(self, seq) -> None:
        """Publish every freshly-filled FULL block of ``seq`` into the
        allocator's prefix index (hash chained on the block's whole token
        prefix).  Rows past ``kv_len`` are untouched garbage, so only
        blocks completely below it qualify; partial tails stay mutable
        and unregistered."""
        if self.pager is None or not self.pager.enable_prefix_cache:
            return
        bs = self.page_size
        full = seq.kv_len // bs
        if full <= seq.registered:
            return
        with TraceAnnotation("engine.register"):
            # token id at pool row i is concat(prompt, output)[i]: prefill
            # rows hold (possibly resumed) prompt tokens, each decode row
            # holds the token fed that step — output[-1] at planning time.
            ids = np.concatenate(
                [seq.prompt, np.asarray(seq.output or [], np.int32)])
            for j in range(seq.registered, full):
                parent = seq.block_hashes[j - 1] if j else None
                block = ids[j * bs:(j + 1) * bs]
                h = chain_hash(parent, block)
                seq.block_hashes.append(h)
                self.pager.register_block(seq.slot, j, h, block)
            seq.registered = full

    def _merge_slot_cache(self, slot: int, pcache: Any, plen: int) -> None:
        """Copy a (1, …) prefill cache into slot ``slot`` of the dense
        batch cache.  Buffer layouts put batch right after the
        layer-stack dims, so we match on dim position by name."""
        def merge(dst, src, path=""):
            if isinstance(dst, dict):
                return {k: merge(dst[k], src[k], path + "/" + k)
                        for k in dst}
            if isinstance(dst, tuple):
                return tuple(merge(d, s, path) for d, s in zip(dst, src))
            if path.endswith("lens"):
                return dst.at[slot].set(jnp.asarray(plen, dst.dtype))
            # find the batch dim: it is where shapes differ (src has 1)
            for ax in range(dst.ndim):
                if src.shape[ax] == 1 and dst.shape[ax] == self.max_slots:
                    idx = [slice(None)] * dst.ndim
                    idx[ax] = slot
                    return dst.at[tuple(idx)].set(
                        jnp.squeeze(src, ax).astype(dst.dtype))
            return dst
        self.cache = merge(self.cache, pcache)

    def _decode_once(self, slots: List[int]) -> List[Request]:
        """One batched decode step for the planned ``slots``.  The device
        step touches every row; rows outside ``slots`` (free slots, or a
        mid-prefill sequence whose next chunk overwrites the same
        position) are ignored and their lengths re-synced after.
        Sampling is per-row keyed (``sample_logits_per_row``) so each
        sequence draws from its own stream regardless of who shares the
        batch — which is also what makes fault isolation bit-exact: a
        row leaving the batch (failed request) cannot change any
        survivor's draws.

        Implemented as dispatch + complete so :meth:`step_async` can
        return between the two with the device still computing; calling
        them back to back (here) is the synchronous path and is
        bit-identical — the split only moves WHERE the host blocks, not
        what it computes."""
        return self._decode_complete(self._decode_dispatch(slots))

    @functools.partial(annotate_function, name="engine.decode_dispatch")
    def _decode_dispatch(self, slots: List[int]) -> _PendingDecode:
        """Token-independent half of the decode step: build the padded
        row inputs, dispatch the device decode and the per-row-keyed
        sampling, and return WITHOUT forcing the results to host.
        ``nxt``/``finite`` in the returned struct are device arrays
        still being computed under JAX async dispatch."""
        failed: List[Request] = []
        if self.faults is not None:
            slots, failed = self._survive_faults(
                SITE_DECODE, list(slots),
                uid_of=lambda s: self.scheduler.running[s].req.uid,
                alive=lambda s: s in self.scheduler.running)
            if not slots:
                return _PendingDecode(slots=[], failed=failed)
        tokens = np.zeros((self.max_slots,), np.int32)
        temps = np.ones((self.max_slots,), np.float32)
        top_ps = np.ones((self.max_slots,), np.float32)
        key_rows: List[Any] = [None] * self.max_slots
        row_uids: List[Optional[int]] = [None] * self.max_slots
        for i in slots:
            seq = self.scheduler.running[i]
            tokens[i] = seq.output[-1]
            temps[i] = seq.req.temperature
            top_ps[i] = seq.req.top_p
            row_uids[i] = seq.req.uid
        with TraceAnnotation("engine.sample"):
            for i in slots:
                seq = self.scheduler.running[i]
                key_rows[i] = jax.random.fold_in(self._seq_key(seq),
                                                 len(seq.output))
            zero = jax.random.PRNGKey(0)
            keys = jnp.stack([k if k is not None else zero
                              for k in key_rows])

        t0 = self._now()
        if self.faults is not None:
            self.faults.latency(self._step)   # simulated slow device step
        logits, self.cache = self._decode(
            self.params, self.cache, self._put(tokens))
        if self.faults is not None:
            logits = self.faults.corrupt_logits(
                SITE_DECODE, self._step, logits, row_uids)
        with TraceAnnotation("engine.sample"):
            finite = (jnp.all(jnp.isfinite(logits), axis=-1)
                      if self.nan_guard else None)
            nxt = sample_logits_per_row(
                keys, logits, jnp.asarray(temps), jnp.asarray(top_ps))
        self.metrics["decode_steps"] += 1
        self.metrics["seq_steps"] += len(slots)
        kv_lens = np.asarray([self.scheduler.running[i].kv_len
                              for i in slots if i in self.scheduler.running],
                             np.int64)
        kv_now = int(kv_lens.sum())
        self._account_energy(float(len(slots)), float(kv_now),
                             float(kv_now))
        if self.paged:
            self._account_decode_bytes(kv_lens)
        return _PendingDecode(slots=slots, failed=failed, nxt=nxt,
                              finite=finite, t0=t0)

    def _decode_complete(self, p: _PendingDecode) -> List[Request]:
        """Token-dependent half: block on the sampled tokens, append
        them, register completed blocks, retire stops, resync lengths.
        ``t_decode`` is charged dispatch→here, so in pipelined serving
        it includes the host's overlap window — wall time the device
        was busy either way."""
        if not p.slots:
            self.cache["lens"] = self._put(
                self.scheduler.device_lens(), np.int32)
            return p.failed
        slots, failed = p.slots, p.failed
        with TraceAnnotation("engine.sync"):
            finite = np.asarray(p.finite) if p.finite is not None else None
            nxt = np.asarray(p.nxt)
        self.metrics["t_decode"] += self._now() - p.t0

        finished: List[Request] = []
        for i in slots:
            seq = self.scheduler.running.get(i)
            if seq is None or seq.req.error is not None:
                continue        # torn down by an earlier row this step
            if finite is not None and not finite[i]:
                # NaN/inf logits on this row: its sampled token is
                # garbage and the KV row it just wrote is suspect —
                # quarantine + fail the request (group retires as a
                # unit), everyone else's draws are independent
                self.metrics["nan_rows"] += 1
                self.fault_log.append(
                    {"step": self._step, "kind": "nan",
                     "site": SITE_DECODE, "uid": seq.req.uid})
                failed.append(self._fail_request(
                    seq.req, "non-finite logits during decode", ERR_NAN,
                    quarantine=True))
                continue
            tok = int(nxt[i])
            seq.output.append(tok)
            self.metrics["tokens_out"] += 1
            # the step's KV row is in the pool now; if it completed a
            # block, publish it (before a finish drops the lease).
            self._register_blocks(seq)
            if self._stop_hit(seq, tok):
                done_req = self._finish_seq(seq)
                if done_req is not None:
                    finished.append(done_req)
        finished.extend(failed)
        # the scheduler's lengths are authoritative: decoded rows were
        # advanced at planning time, finished/free rows drop to 0, and a
        # mid-prefill row whose position the batched step bumped gets its
        # prefill progress back (its garbage KV row is overwritten by the
        # next chunk, or dropped when the block isn't allocated yet).
        self.cache["lens"] = self._put(self.scheduler.device_lens(),
                                       np.int32)
        return finished

    def _run_verifies(self, verifies: List[SpecVerify]) -> List[Request]:
        """Execute this step's speculative verify calls — ONE batched
        ``verify_chunk_batch`` padded to the fixed
        ``(max_slots, spec_tokens + 1)`` extent (padding rows carry slot
        -1 and write nothing, same contract as the prefill chunk).

        Each row feeds ``[output[-1], drafts...]`` at positions
        ``start..start+k`` and gets logits for all k+1 positions; every
        position ``j`` is then sampled with the exact key non-speculative
        decode would have used for output position ``m + j`` (``m`` =
        tokens emitted so far), so the emitted stream is independent of
        the drafts — they only decide how many positions commit.  The
        acceptance walk appends emitted tokens while they agree with the
        drafts (the position-``j`` logits conditioned on drafts ``< j``,
        so agreement up to ``j-1`` makes row ``j`` trustworthy), always
        commits the first token (a verify step never emits fewer tokens
        than the plain decode it replaced), and on disagreement or stop
        rolls the slot's lease back to the accepted length via
        ``BlockAllocator.truncate`` — BEFORE ``_register_blocks``, so the
        prefix index can never serve speculative KV."""
        failed: List[Request] = []
        if self.faults is not None:
            verifies, failed = self._survive_faults(
                SITE_DECODE, list(verifies),
                uid_of=lambda v: v.seq.req.uid,
                alive=lambda v:
                    self.scheduler.running.get(v.seq.slot) is v.seq)
            if not verifies:
                self.cache["lens"] = self._put(
                    self.scheduler.device_lens(), np.int32)
                return failed
        nrows, width = self.max_slots, self.spec_tokens + 1
        toks = np.zeros((nrows, width), np.int32)
        lens = np.zeros((nrows,), np.int32)
        offs = np.zeros((nrows,), np.int32)
        slots = np.full((nrows,), -1, np.int32)
        temps = np.ones((nrows,), np.float32)
        top_ps = np.ones((nrows,), np.float32)
        zero = jax.random.PRNGKey(0)
        key_flat: List[Any] = [zero] * (nrows * width)
        row_uids: List[Optional[int]] = [None] * nrows
        for i, v in enumerate(verifies):
            seq = v.seq
            k = len(v.drafts)
            lens[i] = k + 1
            toks[i, 0] = seq.output[-1]
            toks[i, 1:k + 1] = v.drafts
            offs[i] = v.start
            slots[i] = seq.slot
            temps[i] = seq.req.temperature
            top_ps[i] = seq.req.top_p
            row_uids[i] = seq.req.uid
            m = len(seq.output)
            for j in range(k + 1):
                key_flat[i * width + j] = jax.random.fold_in(
                    self._seq_key(seq), m + j)
        keys = jnp.stack(key_flat)

        t0 = self._now()
        if self.faults is not None:
            self.faults.latency(self._step)
        logits, self.cache = self.model.verify_chunk_batch(
            self.params, toks, self.cache, slots, offs,
            page_table=self._host_pt, chunk_lens=lens,
            mesh=self.mesh)
        if self.faults is not None:
            logits = self.faults.corrupt_logits(
                SITE_DECODE, self._step, logits, row_uids)
        finite = (np.asarray(jnp.all(jnp.isfinite(logits), axis=-1))
                  if self.nan_guard else None)        # (nrows, width)
        emitted = np.asarray(sample_logits_per_row(
            keys, logits.reshape(nrows * width, logits.shape[-1]),
            jnp.asarray(np.repeat(temps, width)),
            jnp.asarray(np.repeat(top_ps, width)))).reshape(nrows, width)
        self.metrics["verify_steps"] += 1
        self.metrics["seq_steps"] += len(verifies)
        self.metrics["t_decode"] += self._now() - t0
        # the verify reads the prefix through the same paged path as a
        # prefill chunk — account its tile traffic (and energy) the same
        self._account_prefix_bytes(offs, lens)

        finished: List[Request] = []
        for i, v in enumerate(verifies):
            seq = v.seq
            if self.scheduler.running.get(seq.slot) is not seq \
                    or seq.req.error is not None:
                continue         # torn down by an earlier row this step
            k = len(v.drafts)
            if finite is not None and not bool(finite[i, :k + 1].all()):
                # any poisoned position taints the whole row: its KV
                # writes are suspect — quarantine + fail, same rule as
                # the decode path (survivors' draws are independent)
                self.metrics["nan_rows"] += 1
                self.fault_log.append(
                    {"step": self._step, "kind": "nan",
                     "site": SITE_DECODE, "uid": seq.req.uid})
                failed.append(self._fail_request(
                    seq.req, "non-finite logits during verify", ERR_NAN,
                    quarantine=True))
                continue
            appended = 0
            stop = False
            for j in range(k + 1):
                tok = int(emitted[i, j])
                seq.output.append(tok)
                appended += 1
                self.metrics["tokens_out"] += 1
                seq.kv_len = v.start + appended
                stop = self._stop_hit(seq, tok)
                if stop or j >= k or v.drafts[j] != tok:
                    break
            self.metrics["draft_tokens"] += k
            self.metrics["accepted_tokens"] += appended - 1
            if appended <= k:
                self.metrics["spec_rollbacks"] += 1
            # rollback-as-truncation: shrink the lease to the accepted
            # length first, then register — rejected rows can neither
            # stay leased nor reach the prefix index
            self.pager.truncate(seq.slot, seq.kv_len)
            self._register_blocks(seq)
            if stop:
                done_req = self._finish_seq(seq)
                if done_req is not None:
                    finished.append(done_req)
        finished.extend(failed)
        self.cache["lens"] = self._put(self.scheduler.device_lens(),
                                       np.int32)
        return finished
