"""Fused paged chunk-attention Pallas kernel: rectangular q over paged K/V.

Chunked prefill (serving/scheduler.py) attends each prompt chunk against
the prefix rows its sequence already wrote into the shared block pool.
The jnp oracle (``layers.attention_chunk_merge``) materializes a masked
gather of every row's FULL page-table extent — O(max_prefix) HBM traffic
per chunk, dequantized up front for int8 pools.  This kernel reads the
prefix straight out of the pool instead:

    k_pool / v_pool : (n_blocks, block_size, KVH, hd)   — one layer's pool
    page_table      : (B, max_blocks) int32             — block ids, -1 free
    pfx_lens        : (B,) int32                        — prefix rows (= the
                      chunk's position offset; pool row t = global pos t)
    q               : (B, C, H, hd)                     — the chunk queries

It is the PR-1 ``paged_decode_attention`` addressing pattern generalized
to multi-row q (the ``flash_prefill`` rectangle): the page table and the
per-row prefix/chunk lengths ride in via scalar prefetch
(``pltpu.PrefetchScalarGridSpec``), so the BlockSpec index_map
dereferences ``page_table[b, i]`` *before* the DMA is issued — the gather
IS the index_map, and no contiguous copy of the prefix ever exists in
HBM.  Dead tiles cost neither bytes nor FLOPs: KV tiles at or past
``ceil(pfx_len/block_size)`` and whole q tiles past a row's valid chunk
length clamp onto the last live tile in the index_map (Pallas recognizes
the revisit and elides the fetch) and their compute sits under
``@pl.when``.  Prefix keys all sit strictly below every live query
position, so the segment needs no causal diagonal — validity
(``pos < pfx_len``) already implies causality.

The kernel returns the *flash state* of the prefix segment — the
normalized output plus the running (max, denominator) per query — so
``layers.attention_chunk_merge`` can merge it with the chunk's own-segment
attention by its exact softmax-renormalization contract.  An empty prefix
leaves the state at (out=0, m=NEG_INF, l=0), which merges with weight
exactly zero: the merge then returns the chunk's own attention bit for
bit.  Q8_0 pools dequantize in-kernel via the per-(position,
kv-head) ``ks``/``vs`` scale gathers, same as the paged decode kernel.

Block layout.  Mosaic requires the last two dims of every block to be
multiples of (8, 128) or equal to the array's own dims.  A block of one
KV head, ``(1, bs, 1, hd)`` over the ``(.., KVH, hd)`` pool, breaks that
rule whenever KVH > 1, so each grid step fetches one pool block with ALL
its KV heads, ``(1, bs, KVH, hd)`` (scales ``(1, bs, KVH)``), and loops
over the heads inside the body.  The queries and the returned state are
laid out head-major, ``(B, KVH, C*HQ, ..)``, so each head's rows are one
contiguous ``(bq*HQ, hd)`` tile; the wrapper converts to and from the
merge layout.

``return_tile_counts=True`` adds a per-(batch, kv_head) int32 output
counting tiles whose body ran — the interpret-mode proof that dead tiles
(past the prefix extent or past the chunk length) are skipped.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(pt_ref, pfx_ref, qlen_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
            o_ref, m_ref, l_ref, *rest, block_q: int, block_size: int,
            n_blocks_grid: int, kv_int8: bool, count_tiles: bool):
    if count_tiles:
        cnt_ref, m_scr, l_scr, acc_scr = rest
    else:
        (m_scr, l_scr, acc_scr), cnt_ref = rest, None
    bb = pl.program_id(0)
    qi = pl.program_id(1)
    i = pl.program_id(2)                                   # logical block #
    kvh = k_ref.shape[2]
    pfx = pfx_ref[bb]
    qlen = qlen_ref[bb]

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    if count_tiles:
        @pl.when((qi == 0) & (i == 0))
        def _init_cnt():
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

    # a tile runs only if it holds live prefix keys AND its q tile holds
    # live chunk rows — both bounds are prefetched data, never compile keys
    run = (i * block_size < pfx) & (qi * block_q < qlen)

    @pl.when(run)
    def _tile():
        pos = i * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1)
        valid = pos < pfx                                  # (1, bs)
        for h in range(kvh):
            q = q_ref[0, h].astype(jnp.float32)            # (bq*hq, d)
            k = k_ref[0, :, h, :].astype(jnp.float32)      # (bs, d)
            v = v_ref[0, :, h, :].astype(jnp.float32)
            if kv_int8:
                k = k * ks_ref[0, :, h:h + 1]              # dequant per pos
                v = v * vs_ref[0, :, h:h + 1]

            s = jax.lax.dot_general(
                q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)        # (bq*hq, bs)
            s = jnp.where(valid, s, NEG_INF)

            m_prev = m_scr[h, :, :1]
            l_prev = l_scr[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            p = jnp.where(valid, p, 0.0)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p, v, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])
        if count_tiles:
            cnt_ref[...] += 1

    @pl.when(i == n_blocks_grid - 1)
    def _finish():
        for h in range(kvh):
            l = l_scr[h, :, :1]
            o_ref[0, h] = (acc_scr[h] / jnp.where(l > 0, l, 1.0)).astype(
                o_ref.dtype)
            m_ref[0, h] = m_scr[h, :, :1]
            l_ref[0, h] = l


def paged_prefill_attention_pallas(q: jax.Array, k_pool: jax.Array,
                                   v_pool: jax.Array, page_table: jax.Array,
                                   pfx_lens: jax.Array, q_lens=None,
                                   ks_pool=None, vs_pool=None, *,
                                   block_q: int = 128,
                                   return_tile_counts: bool = False,
                                   interpret: bool = False):
    """q: (B, C, H, D) pre-scaled by 1/sqrt(D); k/v_pool:
    (NB, BS, KVH, D) (int8 when ks/vs_pool (NB, BS, KVH) given);
    page_table: (B, MB) int32 block ids (-1 = unassigned); pfx_lens: (B,)
    int32 prefix lengths (each row attends pool positions < pfx_lens[b]);
    q_lens: (B,) int32 valid chunk rows (default C; q tiles fully past it
    are skipped and their state is (0, NEG_INF, 0) garbage).

    Returns the prefix segment's flash state in
    ``layers.attention_chunk_merge``'s ``pfx_state`` layout — out
    (B, C, H, D) f32, m (B, H, C, 1) f32, l (B, H, C, 1) f32 — plus
    (B, KVH) int32 live-tile counts when ``return_tile_counts``.
    """
    b, c, h, d = q.shape
    nb, bs, kvh, d_p = k_pool.shape
    if h % kvh or d_p != d:
        raise ValueError(f"pool heads/dim {(kvh, d_p)} do not fit q "
                         f"{(h, d)}")
    hq = h // kvh
    block_q = min(block_q, c)
    if c % block_q:
        raise ValueError(f"C={c} not a multiple of block_q={block_q}")
    nq = c // block_q
    rows = block_q * hq                                    # q rows per tile
    mb = page_table.shape[1]
    page_table = page_table.astype(jnp.int32)
    pfx_lens = pfx_lens.reshape(b).astype(jnp.int32)
    q_lens = (jnp.full((b,), c, jnp.int32) if q_lens is None
              else jnp.asarray(q_lens, jnp.int32).reshape(b))
    kv_int8 = ks_pool is not None
    if not kv_int8:
        ks_pool = jnp.ones((nb, bs, kvh), jnp.float32)
        vs_pool = jnp.ones((nb, bs, kvh), jnp.float32)
    # head-major rows: (B, KVH, C*HQ, D), row = (position, head in group)
    qh = q.reshape(b, c, kvh, hq, d).transpose(0, 2, 1, 3, 4).reshape(
        b, kvh, c * hq, d)

    def _blk(bb, i, pt_ref, pfx_ref):
        # clamp dead logical blocks onto the last live one (revisit -> no
        # DMA), and -1 entries onto pool block 0: the tile body is skipped
        # for them, the fetch just needs a legal address.
        last = jnp.maximum(pl.cdiv(pfx_ref[bb], bs) - 1, 0)
        return jnp.maximum(pt_ref[bb, jnp.minimum(i, last)], 0)

    def row_map(bb, qi, i, pt_ref, pfx_ref, qlen_ref):
        return (bb, 0, qi, 0)

    def pool_map(bb, qi, i, pt_ref, pfx_ref, qlen_ref):
        return (_blk(bb, i, pt_ref, pfx_ref), 0, 0, 0)

    def scale_map(bb, qi, i, pt_ref, pfx_ref, qlen_ref):
        return (_blk(bb, i, pt_ref, pfx_ref), 0, 0)

    out_shape = [jax.ShapeDtypeStruct((b, kvh, c * hq, d), jnp.float32),
                 jax.ShapeDtypeStruct((b, kvh, c * hq, 1), jnp.float32),
                 jax.ShapeDtypeStruct((b, kvh, c * hq, 1), jnp.float32)]
    out_specs = [pl.BlockSpec((1, kvh, rows, d), row_map),
                 pl.BlockSpec((1, kvh, rows, 1), row_map),
                 pl.BlockSpec((1, kvh, rows, 1), row_map)]
    if return_tile_counts:
        out_shape.append(jax.ShapeDtypeStruct((b, 1, kvh), jnp.int32))
        out_specs.append(pl.BlockSpec(
            (1, 1, kvh), lambda bb, qi, i, pt, pf, ql: (bb, 0, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nq, mb),
        in_specs=[
            pl.BlockSpec((1, kvh, rows, d), row_map),
            pl.BlockSpec((1, bs, kvh, d), pool_map),
            pl.BlockSpec((1, bs, kvh, d), pool_map),
            pl.BlockSpec((1, bs, kvh), scale_map),
            pl.BlockSpec((1, bs, kvh), scale_map),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((kvh, rows, 128), jnp.float32),     # running max
            pltpu.VMEM((kvh, rows, 128), jnp.float32),     # running sum
            pltpu.VMEM((kvh, rows, d), jnp.float32),       # acc
        ],
    )

    outs = pl.pallas_call(
        functools.partial(_kernel, block_q=block_q, block_size=bs,
                          n_blocks_grid=mb, kv_int8=kv_int8,
                          count_tiles=return_tile_counts),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(page_table, pfx_lens, q_lens, qh, k_pool, v_pool, ks_pool, vs_pool)
    out = outs[0].reshape(b, kvh, c, hq, d).transpose(0, 2, 1, 3, 4)
    m, l = (x.reshape(b, kvh, c, hq).transpose(0, 1, 3, 2).reshape(
        b, h, c, 1) for x in outs[1:3])
    state = (out.reshape(b, c, h, d), m, l)
    if return_tile_counts:
        return (*state, outs[3].reshape(b, kvh))
    return state
