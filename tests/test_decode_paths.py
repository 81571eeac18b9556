"""Decode hot path: length-pruned attention, paged attention, fused
weights, and the engine on the paged cache.

Covers the PR-1 acceptance criteria:
  * the pruned kernel provably skips KV tiles beyond each row's length
    (tile-count output in interpret mode) and is bit-exact vs. the full
    scan,
  * paged decode attention matches the dense reference to <=1e-5 (f32 KV)
    / <=1e-2 (int8 KV) for ragged lens including len=0 dead slots,
  * fused QKV / gate-up weights leave model outputs unchanged and drop
    per-layer decode weight GEMVs from 7 to 4,
  * the engine produces identical greedy streams on paged vs. dense
    caches and honors per-request sampling params.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

I = dict(interpret=True)


def _rand_kv(key, b, s, kvh, d):
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, kvh, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, kvh, d))
    return k, v


def _quant_kv(kf, vf):
    absk = jnp.max(jnp.abs(kf), -1, keepdims=True)
    absv = jnp.max(jnp.abs(vf), -1, keepdims=True)
    kq = jnp.round(kf / jnp.where(absk > 0, absk, 1.0) * 127).astype(jnp.int8)
    vq = jnp.round(vf / jnp.where(absv > 0, absv, 1.0) * 127).astype(jnp.int8)
    return kq, vq, absk[..., 0] / 127.0, absv[..., 0] / 127.0


# ---------------------------------------------------------------------------
# length pruning
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("lens", [[1, 300, 511], [0, 512, 64], [512, 0, 1]])
def test_pruned_bit_exact_vs_full_scan(lens):
    b, s, kvh, hq, d = 3, 512, 2, 2, 64
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, kvh * hq, d)) / np.sqrt(d)
    k, v = _rand_kv(key, b, s, kvh, d)
    lens = jnp.asarray(lens, jnp.int32)
    full = ops.decode_attention(q, k, v, lens, prune=False, block_s=128, **I)
    pruned = ops.decode_attention(q, k, v, lens, prune=True, block_s=128, **I)
    assert bool(jnp.all(full == pruned)), "pruning must be bit-exact"


def test_pruned_tile_counts_skip_dead_tiles():
    """The kernel must execute exactly ceil(len/block_s) of the n_s grid
    tiles per (batch, kv_head) — everything past the length is skipped."""
    b, s, kvh, hq, d = 4, 2048, 2, 2, 64
    block_s = 256
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (b, kvh * hq, d)) / np.sqrt(d)
    k, v = _rand_kv(key, b, s, kvh, d)
    lens = jnp.asarray([1, 200, 2048, 0], jnp.int32)
    _, counts = ops.decode_attention(q, k, v, lens, block_s=block_s,
                                     return_tile_counts=True, **I)
    expect = np.array([-(-int(l) // block_s) for l in [1, 200, 2048, 0]])
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.broadcast_to(expect[:, None], (b, kvh)))
    # and the skip is real: 1+1+8+0 tiles ran out of a 4*8 tile grid
    assert int(counts.sum()) == kvh * int(expect.sum()) < b * kvh * (s // block_s)


def test_pruned_int8_kv_matches_reference():
    b, s, kvh, hq, d = 2, 512, 2, 4, 64
    key = jax.random.PRNGKey(2)
    q = jax.random.normal(key, (b, kvh * hq, d)) / np.sqrt(d)
    kf, vf = _rand_kv(key, b, s, kvh, d)
    kq, vq, ks, vs = _quant_kv(kf, vf)
    lens = jnp.asarray([37, 512], jnp.int32)
    out = ops.decode_attention(q, kq, vq, lens, ks, vs, block_s=128, **I)
    want = ref.ref_decode_attention(q.reshape(b, kvh, hq, d), kq, vq,
                                    lens.reshape(b, 1), ks, vs
                                    ).reshape(b, kvh * hq, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# paged attention kernel
# ---------------------------------------------------------------------------


def _paged_setup(key, nb, bs, kvh, d, b, mb, hq, lens, dtype=jnp.float32):
    """Build a pool + page table with slot block lists packed arbitrarily."""
    kp = jax.random.normal(key, (nb, bs, kvh, d)).astype(dtype)
    vp = jax.random.normal(jax.random.fold_in(key, 1),
                           (nb, bs, kvh, d)).astype(dtype)
    q = jax.random.normal(jax.random.fold_in(key, 2), (b, kvh * hq, d)) / 8
    rng = np.random.default_rng(int(jax.random.fold_in(key, 3)[0]))
    free = list(rng.permutation(nb))
    pt = np.full((b, mb), -1, np.int32)
    for row, ln in enumerate(lens):
        for i in range(-(-ln // bs)):
            pt[row, i] = free.pop()
    return q, kp, vp, jnp.asarray(pt), jnp.asarray(lens, jnp.int32)


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _pallas_calls(sub)


# (kvh, hq): GQA groups, SmolLM2's 32 heads of one query each (the kernel's
# all-heads form), Yi-1.5-6B's 4 KV heads of 8 queries
HEADS = [(2, 4), (32, 1), (4, 8)]
# lens: ragged with a released slot (0: page-table row of -1), all dead,
# and 1 / a page boundary / a page boundary + 1
LENS = [[170, 20, 0], [0, 0, 0], [256, 1, 64], [1, 64, 65]]


@pytest.mark.parametrize("kvh,hq", HEADS)
@pytest.mark.parametrize("lens", LENS)
def test_paged_matches_dense_reference_f32(lens, kvh, hq):
    nb, bs, d, b, mb = 16, 64, 64, 3, 4
    q, kp, vp, pt, lens = _paged_setup(jax.random.PRNGKey(3), nb, bs, kvh, d,
                                       b, mb, hq, lens)
    out = ops.paged_decode_attention(q, kp, vp, pt, lens, **I)
    want = ref.ref_paged_decode_attention(
        q.reshape(b, kvh, hq, d), kp, vp, pt, lens).reshape(b, kvh * hq, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("kvh,hq", HEADS)
def test_paged_bf16_pool_takes_no_scale_operands(kvh, hq):
    """A bf16 pool goes to the kernel as it is: no scale pools are built
    or passed (the pallas_call reads the page table, the lengths, q, K and
    V only), and the f32 math matches the reference on the same codes."""
    nb, bs, d, b, mb = 16, 64, 64, 3, 4
    q, kp, vp, pt, lens = _paged_setup(jax.random.PRNGKey(7), nb, bs, kvh, d,
                                       b, mb, hq, [130, 64, 0], jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda *a: ops.paged_decode_attention(*a, **I))(
        q, kp, vp, pt, lens)
    calls = list(_pallas_calls(jaxpr.jaxpr))
    assert len(calls) == 1 and len(calls[0].invars) == 5
    out = ops.paged_decode_attention(q, kp, vp, pt, lens, **I)
    want = ref.ref_paged_decode_attention(
        q.reshape(b, kvh, hq, d), kp, vp, pt, lens).reshape(b, kvh * hq, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("kvh,hq", HEADS)
def test_paged_matches_dense_reference_int8(kvh, hq):
    nb, bs, d, b, mb = 12, 32, 64, 3, 4
    q, kp, vp, pt, lens = _paged_setup(jax.random.PRNGKey(4), nb, bs, kvh, d,
                                       b, mb, hq, [100, 128, 0])
    kq, vq, ks, vs = _quant_kv(kp, vp)
    out = ops.paged_decode_attention(q, kq, vq, pt, lens, ks, vs, **I)
    # dense reference on the DEQUANTIZED gathered view
    safe = jnp.maximum(pt, 0)
    kd = (kq.astype(jnp.float32) * ks[..., None])[safe].reshape(
        b, mb * bs, kvh, d)
    vd = (vq.astype(jnp.float32) * vs[..., None])[safe].reshape(
        b, mb * bs, kvh, d)
    want = ref.ref_decode_attention(q.reshape(b, kvh, hq, d), kd, vd,
                                    lens.reshape(b, 1)).reshape(b, kvh * hq, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-2)


@pytest.mark.parametrize("kvh,hq", HEADS)
@pytest.mark.parametrize("lens", [[130, 64, 0], [1, 64, 65]])
def test_paged_tile_counts_skip_unowned_blocks(lens, kvh, hq):
    nb, bs, d, b, mb = 16, 64, 64, 3, 8
    q, kp, vp, pt, lens_j = _paged_setup(jax.random.PRNGKey(5), nb, bs, kvh,
                                         d, b, mb, hq, lens)
    _, counts = ops.paged_decode_attention(q, kp, vp, pt, lens_j,
                                           return_tile_counts=True, **I)
    expect = np.array([-(-l // bs) for l in lens])
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.broadcast_to(expect[:, None], (b, kvh)))


def test_paged_ignores_other_slots_blocks():
    """Perturbing blocks owned by OTHER slots must not change a row."""
    nb, bs, kvh, d, b, mb, hq = 8, 32, 1, 64, 2, 4, 2
    q, kp, vp, pt, lens = _paged_setup(jax.random.PRNGKey(6), nb, bs, kvh, d,
                                       b, mb, hq, [64, 32])
    out0 = ops.paged_decode_attention(q, kp, vp, pt, lens, **I)
    owned0 = set(int(x) for x in np.asarray(pt[0]) if x >= 0)
    victim = next(i for i in range(nb) if i not in owned0)
    kp2 = kp.at[victim].set(99.0)
    vp2 = vp.at[victim].set(-99.0)
    out1 = ops.paged_decode_attention(q, kp2, vp2, pt, lens, **I)
    np.testing.assert_array_equal(np.asarray(out0[0]), np.asarray(out1[0]))


@pytest.mark.parametrize("kv,kvh", [(None, 4), ("int8", 4), (None, 2)],
                         ids=["mha", "mha-int8", "gqa"])
def test_decode_step_kernel_matches_oracle(kv, kvh):
    """The paged decode step with its attention in the Pallas kernel
    (interpret mode) against the jnp gather, over several steps of rows
    at mixed lengths (one across a page boundary, a released slot):
    logits within f32 tolerance and the same greedy tokens."""
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.models import transformer as T
    cfg = reduced(get_config("llama2-110m")).with_(
        compute_dtype="float32", n_kv_heads=kvh)
    if kv:
        cfg = cfg.with_(kv_cache_dtype=kv)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    cache = m.init_paged_cache(4, block_size=8, n_blocks=16,
                               max_blocks_per_seq=4)
    key = jax.random.PRNGKey(1)
    attn = {}
    for i, (name, buf) in enumerate(sorted(cache["attn"].items())):
        x = jax.random.normal(jax.random.fold_in(key, i), buf.shape)
        attn[name] = ((x * 40).astype(jnp.int8) if buf.dtype == jnp.int8
                      else jnp.abs(x) * 0.02 if name in ("ks", "vs")
                      else x.astype(buf.dtype))
    pt = np.full((4, 4), -1, np.int32)
    pt[0, :3] = [5, 2, 9]            # 15 rows -> crosses into page 2
    pt[1, :1] = [0]                  # 6 rows
    pt[3, :2] = [11, 3]              # 7 rows, a second page to grow into
    cache = dict(cache, attn=attn, page_table=jnp.asarray(pt),
                 lens=jnp.asarray([15, 6, 0, 7], jnp.int32))
    steps = {mode: jax.jit(lambda p, c, t, mode=mode: T._decode_step_paged(
        p, cfg, c, t, None, mode=mode)) for mode in ("oracle", "interpret")}
    caches = {mode: cache for mode in steps}
    toks = {mode: jnp.asarray([3, 4, 5, 6], jnp.int32) for mode in steps}
    for _ in range(3):
        logits = {}
        for mode, step in steps.items():
            logits[mode], caches[mode] = step(params, caches[mode],
                                              toks[mode])
            toks[mode] = jnp.argmax(logits[mode], -1).astype(jnp.int32)
        np.testing.assert_allclose(np.asarray(logits["interpret"]),
                                   np.asarray(logits["oracle"]),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(toks["interpret"]),
                                      np.asarray(toks["oracle"]))


# ---------------------------------------------------------------------------
# fused decode weights
# ---------------------------------------------------------------------------


def _tiny_model():
    from repro.configs import get_config, reduced
    from repro.models import build_model
    cfg = reduced(get_config("llama2-110m"))
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return m, params


def test_fused_weights_identical_outputs_quantized():
    m, params = _tiny_model()
    toks = jnp.array([5, 9], jnp.int32)
    l_unf, _ = m.decode_step(m.quantize(params, fuse_decode=False),
                             m.init_cache(2, 32), toks)
    l_fus, _ = m.decode_step(m.quantize(params, fuse_decode=True),
                             m.init_cache(2, 32), toks)
    np.testing.assert_allclose(np.asarray(l_unf), np.asarray(l_fus),
                               rtol=1e-5, atol=1e-5)


def test_fused_weights_identical_outputs_float():
    from repro.models import transformer as T
    m, params = _tiny_model()
    toks = jnp.array([1, 2], jnp.int32)
    l_unf, _ = m.decode_step(params, m.init_cache(2, 32), toks)
    l_fus, _ = m.decode_step(T.fuse_decode_weights(params, m.cfg),
                             m.init_cache(2, 32), toks)
    np.testing.assert_allclose(np.asarray(l_unf), np.asarray(l_fus),
                               rtol=1e-5, atol=1e-5)


def test_fused_drops_gemvs_from_7_to_4_per_layer(monkeypatch):
    """Count quantized weight GEMV/einsum calls in one decode-step trace.

    The layer stack is a lax.scan, so its body traces once regardless of
    depth: unfused = 7 weight matmuls (q/k/v/o + w1/w3/w2) + 1 lm_head;
    fused = 4 (wqkv / wo_f / w13 / w2) + 1 lm_head.
    """
    from repro.core.quantization import QuantizedTensor
    from repro.models import layers as L
    from repro.models import transformer as T

    m, params = _tiny_model()
    counts = {"n": 0}

    def counting(fn):
        def wrapped(*args):
            if isinstance(args[-1], QuantizedTensor):
                counts["n"] += 1
            return fn(*args)
        return wrapped

    from repro.core.qlinear import qdot, qeinsum
    monkeypatch.setattr(T, "qdot", counting(qdot))
    monkeypatch.setattr(T, "qeinsum", counting(qeinsum))
    monkeypatch.setattr(L, "qdot", counting(qdot))

    toks = jnp.array([3, 4], jnp.int32)

    counts["n"] = 0
    m.decode_step(m.quantize(params, fuse_decode=False),
                  m.init_cache(2, 32), toks)
    unfused = counts["n"]

    counts["n"] = 0
    m.decode_step(m.quantize(params, fuse_decode=True),
                  m.init_cache(2, 32), toks)
    fused = counts["n"]

    assert unfused == 7 + 1, f"unfused traced {unfused} weight GEMVs"
    assert fused == 4 + 1, f"fused traced {fused} weight GEMVs"


def test_fusion_preserves_quantized_values_exactly():
    """wqkv rows must dequantize to exactly wq/wk/wv rows (structural
    concat, no requantization)."""
    from repro.models import transformer as T
    m, params = _tiny_model()
    qp = m.quantize(params, fuse_decode=True)
    blk = jax.tree_util.tree_map(lambda x: x, qp["blocks"])  # stacked (L,…)
    attn = blk["attn"]
    cfg = m.cfg
    hd, nh, kvh = cfg.hd(), cfg.n_heads, cfg.n_kv_heads
    l = 0
    fused = jax.tree_util.tree_map(lambda x: x[l], attn["wqkv"]).dequantize()
    wq = jax.tree_util.tree_map(lambda x: x[l], attn["wq"]).dequantize()
    wk = jax.tree_util.tree_map(lambda x: x[l], attn["wk"]).dequantize()
    np.testing.assert_array_equal(
        np.asarray(fused[: nh * hd]),
        np.asarray(wq.reshape(nh * hd, -1)))
    np.testing.assert_array_equal(
        np.asarray(fused[nh * hd: (nh + kvh) * hd]),
        np.asarray(wk.reshape(kvh * hd, -1)))


# ---------------------------------------------------------------------------
# engine on the paged cache
# ---------------------------------------------------------------------------


def _serve_model():
    from repro.configs import get_config, reduced
    from repro.models import build_model
    cfg = reduced(get_config("llama2-110m"))
    m = build_model(cfg)
    return m, m.quantize(m.init(jax.random.PRNGKey(0)))


def _greedy_outputs(m, params, kind, prompts, **eng_kw):
    from repro.serving.engine import Engine
    eng = Engine(m, params, max_slots=2, max_seq=64, cache_kind=kind,
                 page_size=8, **eng_kw)
    for p in prompts:
        eng.submit(p, max_new_tokens=5, temperature=0.0)
    done = sorted(eng.run(), key=lambda r: r.uid)
    return [r.output for r in done], eng


def test_engine_paged_matches_dense_greedy():
    m, params = _serve_model()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(4, 500, size=n).astype(np.int32)
               for n in (8, 3, 17, 5)]
    paged, eng = _greedy_outputs(m, params, "paged", prompts)
    dense, _ = _greedy_outputs(m, params, "dense", prompts)
    assert eng.paged
    assert paged == dense
    # all slots drained -> every block back in the pool
    assert eng.cache_utilization() == 0.0


def test_engine_paged_small_pool_recycles_blocks():
    """A pool far smaller than max_slots*max_seq serves sequential traffic
    by recycling released blocks — the memory win paging exists for."""
    m, params = _serve_model()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(4, 500, size=6).astype(np.int32)
               for _ in range(6)]
    outs, eng = _greedy_outputs(m, params, "paged", prompts, n_pages=4)
    assert len(outs) == 6 and all(len(o) == 5 for o in outs)
    dense, _ = _greedy_outputs(m, params, "dense", prompts)
    assert outs == dense


def test_engine_per_request_sampling_params():
    """temperature=0 rows must be argmax even when batched with hot rows
    (the seed engine silently sampled everyone at defaults)."""
    from repro.serving.engine import Engine
    m, params = _serve_model()
    rng = np.random.default_rng(2)
    prompt = rng.integers(4, 500, size=6).astype(np.int32)

    greedy_ref, _ = _greedy_outputs(m, params, "paged", [prompt])

    eng = Engine(m, params, max_slots=2, max_seq=64, page_size=8, seed=123)
    eng.submit(prompt, max_new_tokens=5, temperature=0.0)
    eng.submit(prompt, max_new_tokens=5, temperature=5.0, top_p=1.0)
    done = sorted(eng.run(), key=lambda r: r.uid)
    assert done[0].output == greedy_ref[0]


def test_sample_logits_vectorized_params():
    from repro.serving.engine import sample_logits
    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05],
                                  [0.05, 0.15, 0.3, 0.5]]))
    t = jnp.asarray([1.0, 0.0])
    p = jnp.asarray([0.6, 1.0])
    seen0 = set()
    for i in range(64):
        tok = sample_logits(jax.random.PRNGKey(i), logits, t, p)
        seen0.add(int(tok[0]))
        assert int(tok[1]) == 3          # greedy row: always argmax
    assert seen0 <= {0, 1}               # nucleus of row 0 at top_p=0.6


def test_engine_int8_kv_paged():
    from repro.configs import get_config, reduced
    from repro.models import build_model
    cfg = reduced(get_config("llama2-110m")).with_(kv_cache_dtype="int8")
    m = build_model(cfg)
    params = m.quantize(m.init(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(4, 500, size=n).astype(np.int32) for n in (9, 4)]
    paged, _ = _greedy_outputs(m, params, "paged", prompts)
    dense, _ = _greedy_outputs(m, params, "dense", prompts)
    assert paged == dense


def test_engine_counts_decode_attention_bytes():
    """``decode_attn_bytes`` counts the live pages each decode row's
    attention reads through the page table, ``decode_attn_bytes_gather``
    every decode row's full page-table span.  A 5-token prompt with pages
    of 8 decodes at attention lengths 6, 7, 8 and 9: 1 + 1 + 1 + 2 live
    pages, against 4 x 8 pages of span."""
    m, params = _serve_model()
    _, eng = _greedy_outputs(m, params, "paged",
                             [np.arange(4, 9, dtype=np.int32)])
    cfg, item = m.cfg, eng.cache["attn"]["k"].dtype.itemsize
    page = 8 * 2 * cfg.n_kv_heads * cfg.hd() * item * cfg.n_layers
    assert eng.metrics["decode_attn_bytes"] == 5 * page
    assert eng.metrics["decode_attn_bytes_gather"] == 4 * 8 * page
