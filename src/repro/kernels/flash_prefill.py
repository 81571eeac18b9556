"""Flash-attention forward Pallas kernel (prefill / training).

The decode path has its own kernel (decode_attention.py); this one covers
the other half of the paper's workload when scaled up: full-sequence
attention during prefill, where the (S, S) score matrix must never touch
HBM.  Standard flash structure on TPU:

  grid = (batch*heads, q_blocks, k_blocks)   k innermost (sequential)
  VMEM scratch: running max m, running sum l, accumulator acc
  causal masking prunes fully-masked k-blocks via @pl.when

Chunked prefill (serving/scheduler.py) attends a chunk of S_q queries at
global positions ``q_offset .. q_offset + S_q - 1`` against S_k >= S_q
keys (the already-written prefix plus the chunk itself), so the kernel
supports rectangular q/k extents with the causal diagonal shifted by
``q_offset``.  **Shape stability:** the offset and the per-row valid
extents (``q_lens``/``k_lens``) ride in via scalar prefetch
(``pltpu.PrefetchScalarGridSpec``, the same pattern the paged decode
kernel uses for per-row lengths) rather than as static kernel arguments
— serving traffic that churns chunk lengths and position offsets reuses
ONE compiled executable per padded extent, matching the
``models/transformer.prefill_chunk_batch`` contract (its jnp oracle is
``layers.attention_chunk_merge``).  Tiles past a row's valid extent, or
entirely above its shifted causal diagonal, are skipped: the compute is
``@pl.when``-guarded on the prefetched scalars and the BlockSpec
index_map clamps dead tiles onto the last live one, which Pallas
recognizes as a revisit and elides the DMA — padding costs neither
bytes nor FLOPs.

The jnp oracle is layers.attention_scores_blockwise (same math, scan
form); tests sweep shapes (including per-row offsets/lengths) and assert
allclose in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(off_ref, qlen_ref, klen_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, block_q: int, block_k: int,
            n_k_blocks: int, causal: bool, scale: float):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    off = off_ref[b]
    qlen = qlen_ref[b]
    klen = klen_ref[b]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip tiles past the valid extents, and — causal — tiles entirely
    # above the (offset-shifted) diagonal; all three bounds are data
    run = (ki * block_k < klen) & (qi * block_q < qlen)
    if causal:
        run &= ki * block_k <= off + (qi + 1) * block_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bq, bk)
        kpos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = kpos < klen
        if causal:
            qpos = off + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask &= kpos <= qpos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == n_k_blocks - 1)
    def _finish():
        l = l_scr[:, :1]
        o_ref[0] = (acc_scr[...] /
                    jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def flash_prefill_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, q_offset=0,
                         q_lens=None, k_lens=None,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool = False) -> jax.Array:
    """q: (BH, S_q, D); k/v: (BH, S_k, D) flat batch*heads (wrapper
    repeats GQA KV heads).  Returns (BH, S_q, D) f32; q is scaled by
    1/sqrt(D) inside.

    ``q_offset`` — an int or a per-row (BH,) int32 array — gives the
    global position of each row's first query for chunked prefill: query
    row i attends keys ``<= q_offset + i``.  ``q_lens``/``k_lens``
    (optional (BH,) arrays, default = the full extents) mark each row's
    valid rectangle; rows/keys past them are skipped (their output is
    garbage the caller discards).  All three are *data* — scalar
    prefetch, not compile keys — so one executable serves every offset /
    length mix at a given padded shape.  The one-shot case is
    ``S_q == S_k`` with everything defaulted."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    if (causal and isinstance(q_offset, int) and q_lens is None
            and k_lens is None and q_offset + sq > sk):
        raise ValueError(f"q_offset {q_offset} + S_q {sq} exceeds S_k {sk}")
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(f"S_q={sq}/S_k={sk} must divide blocks "
                         f"({block_q},{block_k})")
    nq, nk = sq // block_q, sk // block_k
    scale = d ** -0.5

    offs = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (bh,))
    qlens = (jnp.full((bh,), sq, jnp.int32) if q_lens is None
             else jnp.asarray(q_lens, jnp.int32).reshape(bh))
    klens = (jnp.full((bh,), sk, jnp.int32) if k_lens is None
             else jnp.asarray(k_lens, jnp.int32).reshape(bh))

    def kv_map(b, i, j, off_ref, qlen_ref, klen_ref):
        # clamp dead tiles onto the last live one (revisit -> no DMA):
        # a row needs keys below its valid length and — causal — at or
        # below its q block's shifted diagonal
        limit = klen_ref[b]
        if causal:
            limit = jnp.minimum(limit, off_ref[b] + (i + 1) * block_q)
        last = jnp.maximum(pl.cdiv(limit, block_k) - 1, 0)
        return (b, jnp.minimum(j, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda b, i, j, off, ql, kl: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda b, i, j, off, ql, kl: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )

    return pl.pallas_call(
        functools.partial(_kernel, block_q=block_q, block_k=block_k,
                          n_k_blocks=nk, causal=causal, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(offs, qlens, klens, q, k, v)
