"""Shared neural-net layers, functional style.

Everything here is a pure function ``f(params_subtree, inputs, cfg) -> out``.
Weights are stored contraction-last ``(out, in)`` so ``repro.core.qlinear.qdot``
can transparently take either float (training) or QuantizedTensor (serving)
leaves — the paper's PTQ flow means one code path serves both.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.qlinear import as_float, qdot

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    # gamma stays fp32 — the paper keeps RMSNorm params un-quantized.
    return (x32 * lax.rsqrt(ms + eps) * gamma.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: jax.Array, gamma: jax.Array, beta: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(x.dtype)


def apply_norm(x, p, kind: str, eps: float = 1e-5):
    if kind == "rmsnorm":
        return rms_norm(x, p["gamma"], eps)
    return layer_norm(x, p["gamma"], p["beta"], eps)


# ---------------------------------------------------------------------------
# Rotary embeddings (standard + M-RoPE)
# ---------------------------------------------------------------------------


def rope_angles(positions: jax.Array, head_dim: int, theta: float
                ) -> tuple[jax.Array, jax.Array]:
    """positions (…,) -> cos/sin (…, head_dim) in rotate-half layout."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (…, half)
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (..., H, D); cos/sin broadcastable (..., 1, D)."""
    d = x.shape[-1]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : d // 2], x32[..., d // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return (x32 * cos + rot * sin).astype(x.dtype)


def mrope_angles(positions: jax.Array, head_dim: int, theta: float,
                 sections: tuple[int, ...]) -> tuple[jax.Array, jax.Array]:
    """Qwen2-VL multimodal RoPE.

    positions: (3, …) — temporal / height / width position streams.
    ``sections`` gives the number of *rotation pairs* per stream (summing to
    head_dim // 2); each frequency band takes its position from its stream.
    """
    half = head_dim // 2
    assert sum(sections) == half, (sections, half)
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    # stream index per frequency band
    stream = jnp.repeat(
        jnp.arange(len(sections)),
        jnp.array(sections),
        total_repeat_length=half)                             # (half,)
    pos = positions.astype(jnp.float32)                       # (3, …)
    pos_per_band = jnp.take(pos, stream, axis=0)              # (half, …)? no:
    # take along stream axis: result (half, …) -> move to (…, half)
    pos_per_band = jnp.moveaxis(pos_per_band, 0, -1)
    ang = pos_per_band * freqs
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


class AttnConfig(NamedTuple):
    n_heads: int
    n_kv_heads: int
    head_dim: int
    causal: bool = True
    q_chunk: int = 1024       # blockwise-attention chunk (memory bound)
    window: int = 0           # >0: sliding-window attention


def attention_scores_blockwise(q, k, v, cfg: AttnConfig,
                               q_offset: int = 0) -> jax.Array:
    """Memory-efficient causal attention: scan over query chunks.

    q: (B, S, H, D) pre-scaled; k/v: (B, T, KVH, D).  Scores for one chunk
    are (B, H, qc, T) — never the full S×T square.  The scan body is
    rematerialized in the backward pass (wrapped by the caller's remat
    policy), which is what bounds training memory at 4k–32k context.

    GQA KV heads are broadcast to the full H before the einsum: with the
    head axis TP-sharded this keeps every contraction head-uniform (no
    (KVH, HQ) re-grouping of a sharded dim), and XLA fuses the broadcast
    into the dot so no repeated KV is materialized.
    """
    b, s, h, d = q.shape
    t = k.shape[1]
    kvh = cfg.n_kv_heads
    hq = h // kvh
    qc = min(cfg.q_chunk, s)
    while s % qc:
        qc -= 1
    n_chunks = s // qc

    kg = jnp.repeat(k, hq, axis=2).astype(q.dtype)      # (B, T, H, D)
    vg = jnp.repeat(v, hq, axis=2).astype(q.dtype)
    qg = q.reshape(b, n_chunks, qc, h, d)

    # checkpoint: the backward pass recomputes scores/softmax per chunk
    # instead of saving (B,H,qc,T) f32 residuals for every chunk — this is
    # what keeps training memory flat in T (flash-attention-style remat).
    @jax.checkpoint
    def chunk_fn(carry, inputs):
        qi, idx = inputs                                # (B, qc, H, D)
        scores = jnp.einsum("bqhd,bthd->bhqt", qi.astype(jnp.float32),
                            kg.astype(jnp.float32))
        qpos = q_offset + idx * qc + jnp.arange(qc)[:, None]
        kpos = jnp.arange(t)[None, :]
        mask = jnp.ones((qc, t), bool)
        if cfg.causal:
            mask &= kpos <= qpos
        if cfg.window > 0:
            mask &= kpos > qpos - cfg.window
        scores = jnp.where(mask[None, None], scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqt,bthd->bqhd", p.astype(q.dtype), vg)
        return carry, out

    _, outs = lax.scan(chunk_fn, None,
                       (jnp.moveaxis(qg, 1, 0), jnp.arange(n_chunks)))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, s, h, d)
    return out


def attention_chunk_merge(q, k_pfx, v_pfx, k_chunk, v_chunk,
                          cfg: AttnConfig, q_pos, pfx_valid,
                          chunk_valid, pfx_state=None) -> jax.Array:
    """Shape-stable chunked-prefill attention: a fixed-extent *prefix*
    segment merged with the chunk's own keys by exact softmax
    renormalization.

    q: (B, C, H, D) pre-scaled chunk queries at global positions
    ``q_pos`` (B, C); k/v_chunk: (B, C, KVH, D) the chunk's own
    (pre-quantization) keys, live where ``chunk_valid`` (B, C);
    k/v_pfx: (B, P, KVH, D) the row's gathered pool extent — pool row
    ``t`` sits at global position ``t`` — live where ``pfx_valid``
    (B, P).  All extents are traced data, so chunk length, position
    offset and batch padding never enter the compile key.

    Numerics contract (what makes budget-padded serving trustworthy):

      * the chunk segment is element-for-element
        :func:`attention_scores_blockwise` — same einsums, same f32
        softmax — and each segment's masked keys get *exactly zero*
        probability mass (``exp(-1e30 - m)`` underflows to 0);
      * the two segments merge as ``w_p * out_p + w_c * out_c`` with
        ``w = alpha * l / (alpha_p l_p + alpha_c l_c)`` (flash-style
        max/denominator renormalization).  An all-masked prefix gives
        ``alpha_p == 0.0`` and ``w_c == l_c / l_c == 1.0`` *exactly*, so
        a zero-offset row is **bit-identical** to the plain blockwise
        oracle — which is how the whole-prompt chunk stays bit-identical
        to one-shot prefill while the compile count stays shape-stable;
      * a fully-padded row (everything masked) degrades to finite
        garbage that the caller discards — the pool never holds
        non-finite values, so no NaNs can leak through the ``0 * out_p``
        term.

    Rows with a non-empty prefix reassociate the softmax reduction
    (prefix and chunk are reduced separately, then merged), so they
    match a concatenated-key reference to last-ulp tolerance rather
    than bitwise — the same tolerance class multi-chunk prefill already
    carries vs one-shot.

    ``pfx_state`` replaces the gathered prefix segment with a
    pre-computed flash state ``(out_p, m_p, l_p)`` — out_p (B, C, H, D),
    m_p/l_p (B, H, C, 1), the layout `kernels.ops.paged_prefill_attention`
    returns — and ``k_pfx``/``v_pfx``/``pfx_valid`` may then be None.
    The merge arithmetic is identical either way, and the empty-prefix
    state (out=0, m=-1e30, l=0) reproduces the exact ``w_c == 1.0``
    bit-identity above, so the fused kernel inherits both contracts.
    """
    b, c, h, d = q.shape
    kvh = cfg.n_kv_heads
    hq = h // kvh
    qc = min(cfg.q_chunk, c)
    while c % qc:
        qc -= 1
    n_chunks = c // qc

    kgc = jnp.repeat(k_chunk, hq, axis=2).astype(q.dtype)   # (B, C, H, D)
    vgc = jnp.repeat(v_chunk, hq, axis=2).astype(q.dtype)
    if pfx_state is None:
        p_len = k_pfx.shape[1]
        kgp = jnp.repeat(k_pfx, hq, axis=2).astype(q.dtype)  # (B, P, H, D)
        vgp = jnp.repeat(v_pfx, hq, axis=2).astype(q.dtype)
        k_pos_p = jnp.arange(p_len, dtype=jnp.int32)[None]   # pool rows
    k_pos_c = q_pos                                          # chunk keys
    qg = q.reshape(b, n_chunks, qc, h, d)
    qp = q_pos.reshape(b, n_chunks, qc)

    def segment(qi, qpos, kg, vg, k_pos, k_valid, causal):
        """Masked softmax attention over one key segment; returns the
        normalized output plus (max, denominator) for the merge."""
        scores = jnp.einsum("bqhd,bthd->bhqt", qi.astype(jnp.float32),
                            kg.astype(jnp.float32))
        mask = k_valid[:, None, :]                           # (B, qc?, T)
        if causal:
            mask = mask & (k_pos[:, None, :] <= qpos[:, :, None])
        if cfg.window > 0:
            mask = mask & (k_pos[:, None, :] > qpos[:, :, None] - cfg.window)
        scores = jnp.where(mask[:, None], scores, -1e30)
        m = jnp.max(scores, axis=-1, keepdims=True)          # (B,H,qc,1)
        e = jnp.exp(scores - m)
        l = jnp.sum(e, axis=-1, keepdims=True)
        p = e / l
        out = jnp.einsum("bhqt,bthd->bqhd", p.astype(q.dtype), vg)
        return out, m, l

    def merge(out_c, m_c, l_c, out_p, m_p, l_p):
        m = jnp.maximum(m_p, m_c)
        a_p = jnp.exp(m_p - m) * l_p
        a_c = jnp.exp(m_c - m) * l_c
        l = a_p + a_c
        w_p = jnp.moveaxis(a_p / l, 1, 2)               # (B, qc, H, 1)
        w_c = jnp.moveaxis(a_c / l, 1, 2)
        return w_p * out_p + w_c * out_c

    if pfx_state is None:
        @jax.checkpoint
        def chunk_fn(carry, inputs):
            qi, qpos = inputs                           # (B,qc,H,D), (B,qc)
            out_c, m_c, l_c = segment(qi, qpos, kgc, vgc, k_pos_c,
                                      chunk_valid, cfg.causal)
            # prefix keys sit strictly below every live query position, so
            # validity already implies causality; the window (if any) still
            # applies
            out_p, m_p, l_p = segment(qi, qpos, kgp, vgp, k_pos_p,
                                      pfx_valid, False)
            return carry, merge(out_c, m_c, l_c, out_p, m_p, l_p)

        xs = (jnp.moveaxis(qg, 1, 0), jnp.moveaxis(qp, 1, 0))
    else:
        out_p_all, m_p_all, l_p_all = pfx_state
        # slice the precomputed state to the same per-q-chunk scan layout
        op = jnp.moveaxis(out_p_all.reshape(b, n_chunks, qc, h, d), 1, 0)
        mp = jnp.moveaxis(m_p_all.reshape(b, h, n_chunks, qc, 1), 2, 0)
        lp = jnp.moveaxis(l_p_all.reshape(b, h, n_chunks, qc, 1), 2, 0)

        @jax.checkpoint
        def chunk_fn(carry, inputs):
            qi, qpos, out_p, m_p, l_p = inputs
            out_c, m_c, l_c = segment(qi, qpos, kgc, vgc, k_pos_c,
                                      chunk_valid, cfg.causal)
            return carry, merge(out_c, m_c, l_c, out_p, m_p, l_p)

        xs = (jnp.moveaxis(qg, 1, 0), jnp.moveaxis(qp, 1, 0), op, mp, lp)

    _, outs = lax.scan(chunk_fn, None, xs)
    return jnp.moveaxis(outs, 0, 1).reshape(b, c, h, d)


def attention_decode(q, k_cache, v_cache, length, cfg: AttnConfig,
                     k_scale=None, v_scale=None) -> jax.Array:
    """Single-position attention against a cache (jnp path — shardable).

    q: (B, H, D) pre-scaled; caches (B, S, KVH, D); length (B,) or scalar.
    Optional per-(position, kv-head) scales dequantize an int8 cache.
    """
    b, h, d = q.shape
    s = k_cache.shape[1]
    kvh = cfg.n_kv_heads
    hq = h // kvh
    qg = q.reshape(b, kvh, hq, d).astype(jnp.float32)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale[..., None]
        vf = vf * v_scale[..., None]
    scores = jnp.einsum("bkhd,bskd->bkhs", qg, kf)
    pos = jnp.arange(s)[None, :]
    lens = jnp.broadcast_to(jnp.asarray(length), (b,))[:, None]
    mask = (pos < lens)[:, None, None, :]
    scores = jnp.where(mask, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    p = jnp.where(mask, p, 0.0)
    out = jnp.einsum("bkhs,bskd->bkhd", p, vf)
    return out.reshape(b, h, d).astype(q.dtype)


def paged_attention_decode(q, k_pool, v_pool, page_table, length,
                           cfg: AttnConfig, ks_pool=None, vs_pool=None
                           ) -> jax.Array:
    """Single-position attention against a paged block pool (jnp path).

    q: (B, H, D) pre-scaled; k/v_pool: (NB, BS, KVH, D); page_table (B, MB)
    int32 (-1 = unassigned; such blocks read pool block 0 and are masked by
    ``length``); length (B,).  Gathers each row's whole page-table span
    into a contiguous view and defers to :func:`attention_decode`.  This
    is the decode step's oracle path (mode "oracle" of
    ``transformer.prefill_fused_mode``, the default off a TPU); on a TPU
    the step runs the Pallas kernel (kernels/paged_decode_attention.py)
    instead, which matches these numerics and reads only live pages
    through the page table.  The gather runs under the named scope
    ``kv_gather``, the attention under ``attend``.
    """
    nb, bs, kvh, d = k_pool.shape
    b, mb = page_table.shape
    with jax.named_scope("kv_gather"):
        safe = jnp.maximum(page_table, 0)
        k = k_pool[safe].reshape(b, mb * bs, kvh, d)
        v = v_pool[safe].reshape(b, mb * bs, kvh, d)
        ks = vs = None
        if ks_pool is not None:
            ks = ks_pool[safe].reshape(b, mb * bs, kvh)
            vs = vs_pool[safe].reshape(b, mb * bs, kvh)
    with jax.named_scope("attend"):
        return attention_decode(q, k, v, length, cfg, ks, vs)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def swiglu_mlp(p, x) -> jax.Array:
    """w1/w3: (F, D); w2: (D, F) — SwiGLU as in Llama (paper-faithful).

    When ``w13`` (the fused [w1; w3] from fuse_decode_weights) is present,
    gate and up projections run as ONE GEMV — identical math, since each
    output row's dot product is independent of the others."""
    if "w13" in p:
        h13 = qdot(x, p["w13"])
        f = h13.shape[-1] // 2
        h = jax.nn.silu(h13[..., :f]) * h13[..., f:]
    else:
        h = jax.nn.silu(qdot(x, p["w1"])) * qdot(x, p["w3"])
    return qdot(h.astype(x.dtype), p["w2"]).astype(x.dtype)


def gelu_mlp(p, x) -> jax.Array:
    """w1: (F, D); w2: (D, F) — whisper-style."""
    h = jax.nn.gelu(qdot(x, p["w1"]))
    return qdot(h.astype(x.dtype), p["w2"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# Mixture of Experts (GShard-style grouped einsum dispatch)
# ---------------------------------------------------------------------------


def moe_mlp(p, x, *, n_experts: int, top_k: int, group_size: int = 512,
            capacity_factor: float = 1.25, dense_dispatch: bool = False
            ) -> jax.Array:
    """Token-choice MoE with capacity-bounded einsum dispatch.

    p: router (E, D); w1/w3 (E, F, D); w2 (E, D, F).
    x: (B, S, D).

    ``dense_dispatch`` computes *every* expert for every token and mixes by
    gate weight — wasteful in FLOPs but optimal in HBM bytes when the batch
    is small and decode is bandwidth-bound (every expert's weights are read
    regardless); used by the decode path.
    """
    b, s, d = x.shape
    e = n_experts
    router = p["router"].astype(jnp.float32)
    logits = jnp.einsum("bsd,ed->bse", x.astype(jnp.float32), router)
    gates, idx = lax.top_k(logits, top_k)                  # (B,S,K)
    gates = jax.nn.softmax(gates, axis=-1)

    if dense_dispatch:
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)     # (B,S,K,E)
        combine = jnp.einsum("bske,bsk->bse", onehot, gates)   # (B,S,E)
        h1 = jnp.einsum("bsd,efd->bsef", x.astype(jnp.float32),
                        as_float(p["w1"]))
        h3 = jnp.einsum("bsd,efd->bsef", x.astype(jnp.float32),
                        as_float(p["w3"]))
        hh = jax.nn.silu(h1) * h3
        y = jnp.einsum("bsef,edf,bse->bsd", hh, as_float(p["w2"]), combine)
        return y.astype(x.dtype)

    # ---- grouped GShard dispatch --------------------------------------
    g_sz = min(group_size, s)
    while s % g_sz:
        g_sz -= 1
    g = (b * s) // g_sz
    cap = max(int(capacity_factor * g_sz * top_k / e), 1)
    # round capacity to a multiple of 4 for tiling friendliness
    cap = (cap + 3) & ~3

    xg = x.reshape(g, g_sz, d)
    gates_g = gates.reshape(g, g_sz, top_k)
    idx_g = idx.reshape(g, g_sz, top_k)

    # position of each (token, k) within its expert queue
    onehot = jax.nn.one_hot(idx_g, e, dtype=jnp.int32)        # (G,Sg,K,E)
    flat = onehot.reshape(g, g_sz * top_k, e)
    pos_in_e = jnp.cumsum(flat, axis=1) - flat                # (G,Sg*K,E)
    pos = jnp.sum(pos_in_e * flat, axis=-1).reshape(g, g_sz, top_k)
    keep = pos < cap
    gates_kept = jnp.where(keep, gates_g, 0.0)

    # dispatch (G, Sg, E, C): 1 where token routed to slot (e, c)
    oh_e = jax.nn.one_hot(idx_g, e, dtype=jnp.float32)          # (G,Sg,K,E)
    oh_c = jax.nn.one_hot(jnp.where(keep, pos, cap), cap,
                          dtype=jnp.float32)                    # (G,Sg,K,C)
    disp = jnp.einsum("gske,gskc->gsec", oh_e, oh_c)
    combine = jnp.einsum("gske,gskc,gsk->gsec", oh_e, oh_c, gates_kept)

    xin = jnp.einsum("gsec,gsd->egcd", disp.astype(x.dtype), xg)  # (E,G,C,D)
    h1 = jnp.einsum("egcd,efd->egcf", as_float(xin), as_float(p["w1"]))
    h3 = jnp.einsum("egcd,efd->egcf", as_float(xin), as_float(p["w3"]))
    hh = jax.nn.silu(h1) * h3
    yo = jnp.einsum("egcf,edf->egcd", hh, as_float(p["w2"]))       # (E,G,C,D)
    y = jnp.einsum("gsec,egcd->gsd", combine.astype(jnp.float32), yo)
    return y.reshape(b, s, d).astype(x.dtype)





# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------


def embed_lookup(table, tokens: jax.Array) -> jax.Array:
    """table (V, D) possibly quantized; tokens int32 (…,)."""
    from repro.core.quantization import QuantizedTensor, _unpack_nibbles
    if isinstance(table, QuantizedTensor):
        q = jnp.take(table.q, tokens, axis=0)     # (…, D) int8 / (…, D/2) q4
        if table.bits == 4:
            q = _unpack_nibbles(q)
        s = jnp.take(table.scale, tokens, axis=0)              # (…, G)
        g = table.orig_dim // table.group_size
        qf = q.reshape(*q.shape[:-1], g, table.group_size).astype(jnp.float32)
        return (qf * s[..., None]).reshape(*qf.shape[:-2], table.orig_dim)
    return jnp.take(table, tokens, axis=0)


def lm_head(w, x) -> jax.Array:
    """w: (V, D) (often tied with the embedding); x (…, D) -> logits f32."""
    return qdot(x, w).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, out_dim: int, in_dim: int, dtype=jnp.float32) -> jax.Array:
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, (out_dim, in_dim)) * scale).astype(dtype)
