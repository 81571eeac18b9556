"""Shape-stable chunked prefill: bounded compile counts + padding
bit-exactness.

The serving tentpole this file gates: ``prefill_chunk_batch`` used to
recompile per distinct ``(B, chunk_len, pos_offset)`` triple and
``flash_prefill``'s ``q_offset`` was a static kernel argument, so
production traffic with varied prompt lengths paid unbounded XLA
compiles.  Now every extent is traced data and the engine pads each
step's chunks to one fixed ``(max_slots, prefill_chunk_tokens)`` extent:

  * compile count is O(pool keys) — ONE executable per pool
    configuration however traffic churns lengths/offsets/batch width,
    counted two ways (the jit lowering cache via
    ``transformer.prefill_chunk_compiles`` and a ``jax.monitoring``
    backend-compile listener);
  * a valid row's results do not depend on the padding around it —
    batch-composition invariance is asserted *bitwise*, and padded vs
    unpadded calls agree to the same reassociation tolerance class the
    multi-chunk-vs-one-shot contract already carries (greedy argmax
    identical; int8 pools code-for-code within the +-1 rounding step);
  * the flash-prefill kernel accepts per-row offsets/valid extents via
    scalar prefetch and matches the jnp oracle row for row.
"""

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving.paged_cache import BlockAllocator, PagedConfig

# -- jax.monitoring cross-check: one process-wide listener, gated -----------
_BACKEND_COMPILES = {"n": 0, "armed": False}


def _on_event(name, secs, **kw):
    if _BACKEND_COMPILES["armed"] and \
            name == "/jax/core/compile/backend_compile_duration":
        _BACKEND_COMPILES["n"] += 1


jax.monitoring.register_event_duration_secs_listener(_on_event)


class _count_backend_compiles:
    def __enter__(self):
        _BACKEND_COMPILES["n"] = 0
        _BACKEND_COMPILES["armed"] = True
        return self

    def __exit__(self, *exc):
        _BACKEND_COMPILES["armed"] = False
        self.n = _BACKEND_COMPILES["n"]
        return False

    @property
    def so_far(self):
        return _BACKEND_COMPILES["n"]


def _model(kv_dtype=None):
    from repro.configs import get_config, reduced
    from repro.models import build_model
    cfg = reduced(get_config("llama2-110m")).with_(compute_dtype="float32")
    if kv_dtype:
        cfg = cfg.with_(kv_cache_dtype=kv_dtype)
    m = build_model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def _paged(m, bs=8, n_blocks=32, slots=4, mb=8):
    alloc = BlockAllocator(PagedConfig(
        n_layers=m.cfg.n_layers, n_kv_heads=m.cfg.n_kv_heads,
        head_dim=m.cfg.hd(), block_size=bs, n_blocks=n_blocks,
        max_slots=slots, max_blocks_per_seq=mb))
    cache = m.init_paged_cache(slots, block_size=bs, n_blocks=n_blocks,
                               max_blocks_per_seq=mb)
    return alloc, cache


def _run_batch(m, params, cache, rows, pad_rows=0, pad_width=0):
    """Execute one prefill_chunk_batch call for ``rows`` of
    (slot, tokens, off), optionally padded out to a larger fixed extent
    (the engine's shape-stable form)."""
    from repro.models import transformer
    width = max(max(len(t) for _, t, _ in rows), pad_width)
    nrows = max(len(rows), pad_rows)
    toks = np.zeros((nrows, width), np.int32)
    lens = np.zeros((nrows,), np.int32)
    offs = np.zeros((nrows,), np.int32)
    slots = np.full((nrows,), -1, np.int32)
    for i, (slot, t, off) in enumerate(rows):
        toks[i, :len(t)] = t
        lens[i] = len(t)
        offs[i] = off
        slots[i] = slot
    return transformer.prefill_chunk_batch(
        params, m.cfg, toks, cache, slots, offs, chunk_lens=lens)


def _fill(alloc, cache, slot, upto):
    alloc.ensure(slot, upto)
    cache = dict(cache)
    cache["page_table"] = jnp.asarray(alloc.page_table())
    return cache


def _pool_rows(cache, alloc, slot, upto, key):
    blocks = alloc.owned[slot]
    pool = np.asarray(cache["attn"][key])
    nl, _, bs = pool.shape[:3]
    return pool[:, blocks].reshape(nl, len(blocks) * bs,
                                   *pool.shape[3:])[:, :upto]


# ---------------------------------------------------------------------------
# padding invariance (function level)
# ---------------------------------------------------------------------------


def test_batch_composition_invariance_is_bitwise_f32():
    """A valid row's logits and written KV are BITWISE independent of
    what else shares the padded batch — the property that lets the
    engine batch arbitrary chunk mixes (and pad with dead rows) without
    perturbing any stream."""
    m, params = _model()
    rng = np.random.default_rng(0)
    ta = rng.integers(4, 500, size=13).astype(np.int32)
    tb = rng.integers(4, 500, size=9).astype(np.int32)

    alloc1, cache1 = _paged(m)
    cache1 = _fill(alloc1, cache1, 0, 13)
    l_alone, cache1 = _run_batch(m, params, cache1, [(0, ta, 0)],
                                 pad_rows=4, pad_width=16)

    alloc2, cache2 = _paged(m)
    alloc2.ensure(0, 13)
    cache2 = _fill(alloc2, cache2, 2, 9)
    l_both, cache2 = _run_batch(m, params, cache2,
                                [(0, ta, 0), (2, tb, 0)],
                                pad_rows=4, pad_width=16)

    np.testing.assert_array_equal(np.asarray(l_alone[0]),
                                  np.asarray(l_both[0]))
    for kk in ("k", "v"):
        np.testing.assert_array_equal(
            _pool_rows(cache1, alloc1, 0, 13, kk),
            _pool_rows(cache2, alloc2, 0, 13, kk))


@pytest.mark.parametrize("kv", [None, "int8"])
def test_padded_call_matches_unpadded_per_shape_calls(kv):
    """The engine's padded single call vs the legacy per-shape-grouped
    unpadded calls, identical chunk boundaries: greedy argmax identical,
    logits/KV within the reassociation tolerance (int8 pools store the
    same codes up to the +-1 step a last-ulp difference can tip)."""
    m, params = _model(kv)
    rng = np.random.default_rng(1)
    ta = rng.integers(4, 500, size=16).astype(np.int32)   # 2 full blocks
    tb = rng.integers(4, 500, size=11).astype(np.int32)

    def serve(pad_rows, pad_width):
        alloc, cache = _paged(m)
        # step 1: first chunks at offset 0, different lengths
        alloc.ensure(0, 8)
        cache = _fill(alloc, cache, 2, 11)
        (_, cache) = _run_batch(m, params, cache,
                                [(0, ta[:8], 0), (2, tb, 0)],
                                pad_rows=pad_rows, pad_width=pad_width)
        # step 2: ta's second chunk at offset 8
        cache = _fill(alloc, cache, 0, 16)
        logits, cache = _run_batch(m, params, cache, [(0, ta[8:], 8)],
                                   pad_rows=pad_rows, pad_width=pad_width)
        return logits[0], cache, alloc

    # unpadded "legacy grouping": every call exactly its natural extent
    l_ref, cache_ref, alloc_ref = serve(pad_rows=0, pad_width=0)
    # padded shape-stable form: every call (4 rows, 24 tokens)
    l_pad, cache_pad, alloc_pad = serve(pad_rows=4, pad_width=24)

    assert int(jnp.argmax(l_ref)) == int(jnp.argmax(l_pad)), \
        "padding must not change the greedy token"
    np.testing.assert_allclose(np.asarray(l_pad), np.asarray(l_ref),
                               rtol=1e-5, atol=5e-6)
    for slot, upto in ((0, 16), (2, 11)):
        for kk in ("k", "v"):
            got = _pool_rows(cache_pad, alloc_pad, slot, upto, kk)
            want = _pool_rows(cache_ref, alloc_ref, slot, upto, kk)
            if kv == "int8":
                assert np.abs(got.astype(np.int32)
                              - want.astype(np.int32)).max() <= 1
            else:
                np.testing.assert_allclose(got, want, rtol=1e-5, atol=5e-6)
        if kv == "int8":
            for kk in ("ks", "vs"):
                np.testing.assert_allclose(
                    _pool_rows(cache_pad, alloc_pad, slot, upto, kk),
                    _pool_rows(cache_ref, alloc_ref, slot, upto, kk),
                    rtol=1e-4)


def test_padding_rows_write_nothing():
    """Dead rows (slot -1) and positions past a row's valid length must
    not touch the pool or the device lens — padding is invisible."""
    m, params = _model()
    rng = np.random.default_rng(2)
    toks = rng.integers(4, 500, size=5).astype(np.int32)
    alloc, cache = _paged(m)
    cache = _fill(alloc, cache, 1, 5)
    before_free = {kk: np.asarray(cache["attn"][kk]).copy()
                   for kk in ("k", "v")}
    used = alloc.owned[1]
    _, cache = _run_batch(m, params, cache, [(1, toks, 0)],
                          pad_rows=4, pad_width=16)
    assert np.asarray(cache["lens"]).tolist() == [0, 5, 0, 0]
    for kk in ("k", "v"):
        after = np.asarray(cache["attn"][kk])
        untouched = np.ones(after.shape[1], bool)
        untouched[used] = False
        np.testing.assert_array_equal(after[:, untouched],
                                      before_free[kk][:, untouched])


# ---------------------------------------------------------------------------
# compile-count regression (engine level)
# ---------------------------------------------------------------------------


def test_engine_compile_count_bounded_under_shape_churn():
    """Mixed prompt lengths produce many distinct legacy compile keys
    ``(B, chunk_len, pos_offset)`` but at most ONE fresh executable for
    the chunk step (one pool key), counted via the jit lowering cache
    AND a jax.monitoring backend-compile listener; the engine's
    ``metrics``/``plan_log`` carry the probe."""
    from repro.serving.engine import Engine, legacy_chunk_shape_keys
    m, params = _model()
    rng = np.random.default_rng(3)

    eng = Engine(m, params, max_slots=3, max_seq=64, page_size=8,
                 prefill_chunk_tokens=16)
    c0 = eng.prefill_compile_count()
    # wave 1: compiles everything once (chunk step, decode step, and the
    # small eager-op executables around them)
    for n in (5, 19, 11):
        eng.submit(rng.integers(4, 500, size=n).astype(np.int32),
                   max_new_tokens=3, temperature=0.0)
    assert all(r.error is None for r in eng.run())
    wave1_plans = len(eng.plan_log)
    grew = eng.prefill_compile_count() - c0
    assert grew <= 1, \
        f"chunk step compiled {grew}x in one pool key (bound: 1)"

    # wave 2: entirely new prompt lengths -> new legacy shape keys, but
    # ZERO fresh chunk-step executables and ~zero backend compiles
    with _count_backend_compiles() as probe:
        for n in (30, 7, 23):
            eng.submit(rng.integers(4, 500, size=n).astype(np.int32),
                       max_new_tokens=3, temperature=0.0)
        assert all(r.error is None for r in eng.run())
    k1 = legacy_chunk_shape_keys(eng.plan_log[:wave1_plans])
    k2 = legacy_chunk_shape_keys(eng.plan_log[wave1_plans:])
    assert k2 - k1, "wave 2 must introduce new legacy shape keys"
    assert len(k1 | k2) > 3, f"workload too uniform: {k1 | k2}"
    assert eng.prefill_compile_count() == c0 + grew, \
        "new chunk shapes must not compile new chunk-step executables"
    assert probe.n <= 2, \
        f"{probe.n} backend compiles for {len(k2 - k1)} new shape keys"

    assert eng.metrics["prefill_compiles"] >= 1
    probed = [p["prefill_compiles"] for p in eng.plan_log
              if "prefill_compiles" in p]
    assert probed and probed[-1] == eng.prefill_compile_count()


def test_engine_reuses_compile_across_engines_same_pool_key():
    """A second engine with the same pool configuration serves entirely
    from the first engine's executable — zero new chunk-step compiles."""
    from repro.serving.engine import Engine
    m, params = _model()
    rng = np.random.default_rng(4)

    def serve():
        eng = Engine(m, params, max_slots=3, max_seq=64, page_size=8,
                     prefill_chunk_tokens=16)
        for n in (6, 17, 9):
            eng.submit(rng.integers(4, 500, size=n).astype(np.int32),
                       max_new_tokens=2, temperature=0.0)
        assert all(r.error is None for r in eng.run())
        return eng

    serve()                                   # warm (may compile)
    eng = serve()
    c0 = eng.prefill_compile_count()
    serve()
    assert eng.prefill_compile_count() == c0, \
        "same pool key must not compile again"


# ---------------------------------------------------------------------------
# flash-prefill kernel: per-row offsets/extents as data
# ---------------------------------------------------------------------------


def test_flash_prefill_per_row_offsets_match_oracle():
    """Per-row q_offset/q_lens/k_lens (scalar prefetch) vs the jnp
    oracle applied row by row on each valid rectangle."""
    from repro.kernels import ops
    from repro.models.layers import AttnConfig, attention_scores_blockwise
    b, sq, sk, h, kvh, d = 3, 128, 256, 4, 2, 64
    key = jax.random.PRNGKey(11)
    q = jax.random.normal(key, (b, sq, h, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, sk, kvh, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, sk, kvh, d))
    offs = np.array([0, 64, 128], np.int32)
    qlens = np.array([128, 96, 128], np.int32)
    klens = np.array([128, 160, 256], np.int32)

    out = ops.flash_prefill(q, k, v, causal=True, q_offset=offs,
                            q_lens=qlens, k_lens=klens, interpret=True)
    acfg = AttnConfig(h, kvh, d, q_chunk=64)
    for i in range(b):
        ql, kl, off = int(qlens[i]), int(klens[i]), int(offs[i])
        want = attention_scores_blockwise(
            q[i:i + 1, :ql] * d ** -0.5, k[i:i + 1, :kl], v[i:i + 1, :kl],
            acfg, q_offset=off)
        np.testing.assert_allclose(np.asarray(out[i, :ql]),
                                   np.asarray(want[0]),
                                   rtol=1e-4, atol=1e-4)


def test_flash_prefill_one_compile_across_offsets():
    """Offsets/extents are data, not compile keys: after the first call
    at a shape, different offset/length mixes add ZERO jit-cache
    entries."""
    from repro.kernels import ops
    b, sq, sk, h, kvh, d = 2, 64, 128, 2, 1, 32
    key = jax.random.PRNGKey(12)
    q = jax.random.normal(key, (b, sq, h, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, sk, kvh, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, sk, kvh, d))
    ops.flash_prefill(q, k, v, causal=True,
                      q_offset=np.zeros(b, np.int32),
                      q_lens=np.full(b, sq, np.int32),
                      k_lens=np.full(b, sk, np.int32), interpret=True)
    c0 = ops.flash_prefill._cache_size()
    for offs in ([0, 64], [32, 0], [64, 64]):
        ops.flash_prefill(q, k, v, causal=True,
                          q_offset=np.asarray(offs, np.int32),
                          q_lens=np.asarray([sq, sq // 2], np.int32),
                          k_lens=np.asarray([sk, sk // 2], np.int32),
                          interpret=True)
    assert ops.flash_prefill._cache_size() == c0, \
        "q_offset/q_lens/k_lens leaked into the compile key"


# ---------------------------------------------------------------------------
# fused paged prefix kernel path (REPRO_FUSED_PREFILL)
# ---------------------------------------------------------------------------


def test_fused_prefill_matches_oracle_and_compiles_once(monkeypatch):
    """The fused kernel path (interpret mode — the Pallas kernel really
    executes) vs the jnp gather oracle over the SAME padded batched
    calls: logits within the reassociation tolerance class, and the
    fused executable count stays at one per pool key while lengths,
    offsets and batch composition churn."""
    from repro.models import transformer
    m, params = _model()
    rng = np.random.default_rng(7)
    ta = rng.integers(4, 500, size=16).astype(np.int32)
    tb = rng.integers(4, 500, size=11).astype(np.int32)

    def serve():
        alloc, cache = _paged(m)
        alloc.ensure(0, 8)
        cache = _fill(alloc, cache, 2, 11)
        _, cache = _run_batch(m, params, cache,
                              [(0, ta[:8], 0), (2, tb, 0)],
                              pad_rows=4, pad_width=24)
        cache = _fill(alloc, cache, 0, 16)
        logits, cache = _run_batch(m, params, cache, [(0, ta[8:], 8)],
                                   pad_rows=4, pad_width=24)
        return np.asarray(logits[0])

    monkeypatch.setenv("REPRO_FUSED_PREFILL", "oracle")
    l_oracle = serve()
    monkeypatch.setenv("REPRO_FUSED_PREFILL", "interpret")
    assert transformer.prefill_fused_mode() == "interpret"
    c0 = transformer.prefill_chunk_compiles(m.cfg)
    l_fused = serve()
    np.testing.assert_allclose(l_fused, l_oracle, rtol=1e-5, atol=5e-6)

    # churn lengths/offsets/composition at the same padded extent:
    # zero fresh fused executables
    for rows in ([(1, tb[:7], 0)],
                 [(0, ta[:5], 0), (1, tb[7:], 7)],
                 [(3, ta[5:9], 0), (0, ta[:8], 0), (2, tb[:6], 0)]):
        alloc, cache = _paged(m)
        for slot, t, off in rows:
            alloc.ensure(slot, off + len(t))
        cache = dict(cache)
        cache["page_table"] = jnp.asarray(alloc.page_table())
        _run_batch(m, params, cache, rows, pad_rows=4, pad_width=24)
    grew = transformer.prefill_chunk_compiles(m.cfg) - c0
    assert grew <= 1, \
        f"fused chunk step compiled {grew}x in one pool key (bound: 1)"


def test_fused_prefill_batch_composition_invariance_bitwise(monkeypatch):
    """Under the fused kernel a valid row's logits and written KV remain
    BITWISE independent of what else shares the padded batch — the
    kernel's grid rows share nothing, so the oracle-path invariance
    carries over exactly."""
    monkeypatch.setenv("REPRO_FUSED_PREFILL", "interpret")
    m, params = _model()
    rng = np.random.default_rng(8)
    ta = rng.integers(4, 500, size=13).astype(np.int32)
    tb = rng.integers(4, 500, size=9).astype(np.int32)

    alloc1, cache1 = _paged(m)
    cache1 = _fill(alloc1, cache1, 0, 13)
    l_alone, cache1 = _run_batch(m, params, cache1, [(0, ta, 0)],
                                 pad_rows=4, pad_width=16)

    alloc2, cache2 = _paged(m)
    alloc2.ensure(0, 13)
    cache2 = _fill(alloc2, cache2, 2, 9)
    l_both, cache2 = _run_batch(m, params, cache2,
                                [(0, ta, 0), (2, tb, 0)],
                                pad_rows=4, pad_width=16)

    np.testing.assert_array_equal(np.asarray(l_alone[0]),
                                  np.asarray(l_both[0]))
    for kk in ("k", "v"):
        np.testing.assert_array_equal(
            _pool_rows(cache1, alloc1, 0, 13, kk),
            _pool_rows(cache2, alloc2, 0, 13, kk))


@pytest.mark.parametrize("kv", [None, "int8"])
def test_fused_whole_prompt_bitexact_vs_oneshot(monkeypatch, kv):
    """A whole prompt served as ONE natural-extent chunk through the
    fused kernel matches one-shot prefill (f32) to reassociation
    tolerance with the same argmax.  The kernel's empty-prefix state
    merges with weight exactly zero (bitwise, see
    test_attention_chunk_merge_accepts_kernel_state), but the fused
    chunk step is a different XLA program from one-shot prefill, and the
    compiler may fuse and reorder its element-wise math differently, so
    the end-to-end contract is a tolerance.  int8 pools agree to the
    oracle tolerance (one-shot uses a float cache, so code-for-code
    identity is covered by the multi-chunk tests)."""
    monkeypatch.setenv("REPRO_FUSED_PREFILL", "interpret")
    m, params = _model(kv)
    rng = np.random.default_rng(9)
    prompt = rng.integers(4, 500, size=21).astype(np.int32)
    l_one, _ = m.prefill(params, {"tokens": jnp.asarray(prompt)[None]},
                         max_seq=21)
    alloc, cache = _paged(m)
    cache = _fill(alloc, cache, 1, 21)
    l_chunk, _ = m.prefill_chunk(params, jnp.asarray(prompt), cache, 1, 0)
    if kv is None:
        np.testing.assert_allclose(np.asarray(l_chunk),
                                   np.asarray(l_one),
                                   rtol=1e-5, atol=1e-6)
        assert int(jnp.argmax(l_chunk)) == int(jnp.argmax(l_one))
    else:
        np.testing.assert_allclose(np.asarray(l_chunk),
                                   np.asarray(l_one),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# tensor-parallel meshes: one executable per (pool key, mesh shape)
# ---------------------------------------------------------------------------


def _avail_mesh_sizes():
    return [n for n in (1, 2, 4) if jax.device_count() >= n]


def _serve_traffic(m, params, mesh, lens, seed, kv=None):
    from repro.serving.engine import Engine
    rng = np.random.default_rng(seed)
    eng = Engine(m, params, max_slots=3, max_seq=64, page_size=8,
                 prefill_chunk_tokens=16, mesh=mesh)
    for n in lens:
        eng.submit(rng.integers(4, 500, size=n).astype(np.int32),
                   max_new_tokens=2, temperature=0.0)
    assert all(r.error is None for r in eng.run())
    return eng


def _chunk_count(m, mesh):
    # The compile probe is process-global per (cfg, mesh): earlier tests
    # in the suite may already have warmed this fn with OTHER pool keys,
    # so mesh tests below assert deltas against this snapshot, never
    # absolute counts.
    return m.prefill_compile_count(mesh=mesh)


def test_sharded_engine_one_executable_per_mesh_shape():
    """Under a mesh, the chunk step stays at ONE executable per
    (pool key, mesh shape): traffic churn in prompt lengths, offsets
    and batch composition never grows the count.  Mesh sizes beyond the
    local device count are skipped here and exercised by the CI
    multi-device lane."""
    from repro.launch.mesh import make_serve_mesh
    m, params = _model()
    for msize in _avail_mesh_sizes():
        mesh = make_serve_mesh(msize)
        c0 = _chunk_count(m, mesh)
        eng = _serve_traffic(m, params, mesh, (5, 19, 11), seed=11)
        grew = eng.prefill_compile_count() - c0
        # <= 1, not == 1: an earlier test serving this same (pool key,
        # mesh) already paid the one executable, leaving zero to grow
        assert grew <= 1, \
            f"mesh={msize}: {grew} fresh chunk executables (bound: 1)"
        # wave 2: all-new lengths on a FRESH engine -> zero fresh
        # executables (reuse holds across engines, per mesh)
        eng2 = _serve_traffic(m, params, mesh, (30, 7, 23), seed=12)
        assert eng2.prefill_compile_count() == c0 + grew, \
            f"mesh={msize}: new traffic shapes recompiled the chunk step"


def test_mesh_switch_never_recompiles_other_mesh():
    """Each mesh shape owns an isolated jit entry: serving over mesh B
    must not invalidate or grow mesh A's executable, and returning to A
    serves fully warm.  Requires >=2 devices (the CI multi-device
    lane); on one device the mesh-1-vs-unsharded half still runs."""
    from repro.launch.mesh import make_serve_mesh
    m, params = _model()
    mesh1 = make_serve_mesh(1)

    c_un0 = _chunk_count(m, None)
    c1_0 = _chunk_count(m, mesh1)
    e0 = _serve_traffic(m, params, None, (6, 17, 9), seed=13)
    c_unsharded = e0.prefill_compile_count()
    assert c_unsharded - c_un0 <= 1
    # unsharded serving never touches the mesh-1 entry...
    assert _chunk_count(m, mesh1) == c1_0
    e1 = _serve_traffic(m, params, mesh1, (6, 17, 9), seed=13)
    c1 = e1.prefill_compile_count()
    # ...and mesh-1 serving pays at most its own one executable while
    # leaving the unsharded entry untouched (distinct jit entries)
    assert c1 - c1_0 <= 1
    assert e0.prefill_compile_count() == c_unsharded

    if jax.device_count() >= 2:
        mesh2 = make_serve_mesh(2)
        c2_0 = _chunk_count(m, mesh2)
        e2 = _serve_traffic(m, params, mesh2, (6, 17, 9), seed=13)
        assert e2.prefill_compile_count() - c2_0 <= 1
        c2 = e2.prefill_compile_count()
        # mesh-2 serving left mesh-1's (and unsharded's) entries alone
        assert e1.prefill_compile_count() == c1
        assert e0.prefill_compile_count() == c_unsharded
        # switch back: mesh-1 serves warm, count pinned
        e1b = _serve_traffic(m, params, mesh1, (6, 17, 9), seed=13)
        assert e1b.prefill_compile_count() == c1
        assert e2.prefill_compile_count() == c2


@pytest.mark.skipif(jax.device_count() < 2, reason="needs >=2 devices "
                    "(run under the CI multi-device lane)")
def test_sharded_pool_key_compiles_are_per_quantization(monkeypatch):
    """int8 KV is a different pool key: serving it over the same mesh
    adds exactly one more executable to that mesh's entry and leaves
    the f32 count alone."""
    from repro.launch.mesh import make_serve_mesh
    mesh = make_serve_mesh(2)
    m, params = _model()
    e_f32 = _serve_traffic(m, params, mesh, (5, 19), seed=14)
    c_f32 = e_f32.prefill_compile_count()
    mq, pq = _model("int8")
    pq = mq.quantize(pq)
    cq_0 = _chunk_count(mq, mesh)
    e_q = _serve_traffic(mq, pq, mesh, (5, 19), seed=14)
    # the quantized cfg is its own lru entry; serving it pays at most
    # its own one-per-pool-key executable and leaves f32's count alone
    assert e_q.prefill_compile_count() - cq_0 <= 1
    assert e_f32.prefill_compile_count() == c_f32
