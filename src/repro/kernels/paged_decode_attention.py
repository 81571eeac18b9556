"""Paged flash-decode attention Pallas kernel (vLLM-style page table).

The served decode step reads K/V straight out of the shared block pool of
``serving/paged_cache.py``:

    k_pool / v_pool : (n_blocks, block_size, KVH, hd)   — one layer's pool
    page_table      : (B, max_blocks) int32             — block ids, -1 free
    lens            : (B,) int32                        — live lengths

Both the page table and the lengths ride in via scalar prefetch
(``pltpu.PrefetchScalarGridSpec``), so the BlockSpec index_map
dereferences ``page_table[b, i]`` *before* the DMA is issued — the kernel
streams exactly the blocks a sequence owns, in order, and never
materializes a contiguous gathered copy in HBM (the gather IS the
index_map).  Tiles past ``ceil(len/block_size)`` clamp onto the last live
block, which Pallas recognizes as a revisit and elides the fetch, so
short sequences in a long-context pool cost only their own bytes; ``-1``
entries clamp onto pool block 0 and their tiles are skipped.

Block layout.  Mosaic requires the last two dims of every block to be
multiples of (8, 128) or equal to the array's own dims, so a one-head
block ``(1, bs, 1, hd)`` of the ``(.., KVH, hd)`` pool is refused whenever
KVH > 1.  Each grid step of the ``(B, max_blocks)`` grid therefore fetches
one pool block with ALL its KV heads, ``(1, bs, KVH, hd)`` (int8 scales
``(1, bs, KVH)``).  The tile math follows from the shapes:

* one query head per KV head (``HQ == 1``, multi-head attention) and a
  float pool: scores ``sum_d q[h, d] * k[s, h, d]`` and the output
  ``sum_s p[s, h] * v[s, h, d]`` are elementwise products with reductions
  in the block's own ``(bs, KVH, hd)`` layout — no per-head slices;
* grouped queries (``HQ > 1``) or an int8 pool: per KV head, a
  ``(HQ, hd) x (hd, bs)`` dot over that head's rows of the block, the
  scales applied to the head's ``(bs, 1)`` column.

Scores, the softmax state and the accumulator are f32 in VMEM, as
``layers.attention_decode`` computes them; a float pool takes no scale
operands.  Outputs match ``ref.ref_paged_decode_attention`` (a gather +
dense softmax oracle).

``return_tile_counts=True`` adds a per-(batch, kv_head) int32 output
counting the tiles whose body ran — the interpret-mode proof that tiles
past each row's length are skipped.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _tile_heads(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, *, pos0,
                length):
    """Fold one pool block into the state of every head at once (HQ == 1,
    float pool): q (KVH, hd), state (1, KVH, 1) and (KVH, hd)."""
    q = q_ref[0].astype(jnp.float32)                       # (kvh, d)
    k = k_ref[0].astype(jnp.float32)                       # (bs, kvh, d)
    s = jnp.sum(k * q[None], axis=-1, keepdims=True)       # (bs, kvh, 1)
    pos = pos0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    valid = pos < length
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                                    # (1, kvh, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)          # (bs, kvh, 1)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=0, keepdims=True)
    v = v_ref[0].astype(jnp.float32)
    acc_scr[...] = acc_scr[...] * alpha[0] + jnp.sum(p * v, axis=0)
    m_scr[...] = m_new


def _tile_groups(q_ref, k_ref, v_ref, ks_ref, vs_ref, m_scr, l_scr,
                 acc_scr, *, pos0, length):
    """Fold one pool block into each KV head's group of queries in turn:
    q (KVH, HQ, hd), state (KVH, HQ, 128) and (KVH, HQ, hd)."""
    bs, kvh = k_ref.shape[1], k_ref.shape[2]
    pos = pos0 + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    valid = pos < length                                   # (1, bs)
    for h in range(kvh):
        q = q_ref[0, h].astype(jnp.float32)                # (hq, d)
        k = k_ref[0, :, h, :].astype(jnp.float32)          # (bs, d)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        if ks_ref is not None:
            k = k * ks_ref[0, :, h:h + 1]                  # dequant per pos
            v = v * vs_ref[0, :, h:h + 1]
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # (hq, bs)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[h, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_new = alpha * l_scr[h, :, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
            p, v, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
        l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])


def _kernel(pt_ref, lens_ref, q_ref, k_ref, v_ref, *rest, block_size: int,
            n_blocks_grid: int, kv_int8: bool, count_tiles: bool):
    ks_ref, vs_ref = rest[:2] if kv_int8 else (None, None)
    rest = rest[2:] if kv_int8 else rest
    o_ref = rest[0]
    cnt_ref = rest[1] if count_tiles else None
    m_scr, l_scr, acc_scr = rest[-3:]
    bb = pl.program_id(0)
    i = pl.program_id(1)                                   # logical block #
    length = lens_ref[bb]
    heads = q_ref.ndim == 3                # (1, KVH, hd): one query a head

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        if count_tiles:
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

    @pl.when(i * block_size < length)
    def _tile():
        if heads:
            _tile_heads(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                        pos0=i * block_size, length=length)
        else:
            _tile_groups(q_ref, k_ref, v_ref, ks_ref, vs_ref, m_scr, l_scr,
                         acc_scr, pos0=i * block_size, length=length)
        if count_tiles:
            cnt_ref[...] += 1

    @pl.when(i == n_blocks_grid - 1)
    def _finish():
        l = l_scr[0] if heads else l_scr[:, :, :1]
        o_ref[0] = (acc_scr[...] / jnp.where(l > 0, l, 1.0)).astype(
            o_ref.dtype)


def paged_decode_attention_pallas(q: jax.Array, k_pool: jax.Array,
                                  v_pool: jax.Array, page_table: jax.Array,
                                  lens: jax.Array, ks_pool=None, vs_pool=None,
                                  *, return_tile_counts: bool = False,
                                  interpret: bool = False):
    """q: (B, KVH, HQ, D) pre-scaled by 1/sqrt(D);
    k/v_pool: (NB, BS, KVH, D) (int8 when ks/vs_pool (NB, BS, KVH) given);
    page_table: (B, MB) int32 block ids (-1 = unassigned); lens: (B,) int32.
    Returns (B, KVH, HQ, D) f32 — plus (B, KVH) int32 live-block counts when
    ``return_tile_counts``.
    """
    b, kvh, hq, d = q.shape
    nb, bs, kvh_p, d_p = k_pool.shape
    if (kvh_p, d_p) != (kvh, d):
        raise ValueError(f"pool heads/dim {(kvh_p, d_p)} != q {(kvh, d)}")
    mb = page_table.shape[1]
    page_table = page_table.astype(jnp.int32)
    lens = lens.reshape(b).astype(jnp.int32)
    kv_int8 = ks_pool is not None
    heads = hq == 1 and not kv_int8
    if heads:
        q = q.reshape(b, kvh, d)

    def _blk(bb, i, pt_ref, lens_ref):
        # clamp dead logical blocks onto the last live one (revisit -> no
        # DMA), and -1 entries (released slots) onto pool block 0: the tile
        # body is skipped for them, the fetch just needs a legal address.
        last = jnp.maximum(pl.cdiv(lens_ref[bb], bs) - 1, 0)
        return jnp.maximum(pt_ref[bb, jnp.minimum(i, last)], 0)

    def row_map(bb, i, pt_ref, lens_ref):
        return (bb,) + (0,) * (q.ndim - 1)

    def pool_map(bb, i, pt_ref, lens_ref):
        return (_blk(bb, i, pt_ref, lens_ref), 0, 0, 0)

    def scale_map(bb, i, pt_ref, lens_ref):
        return (_blk(bb, i, pt_ref, lens_ref), 0, 0)

    row_block = (1,) + q.shape[1:]
    in_specs = [pl.BlockSpec(row_block, row_map),
                pl.BlockSpec((1, bs, kvh, d), pool_map),
                pl.BlockSpec((1, bs, kvh, d), pool_map)]
    operands = [q, k_pool, v_pool]
    if kv_int8:
        in_specs += [pl.BlockSpec((1, bs, kvh), scale_map)] * 2
        operands += [ks_pool, vs_pool]
    out_shape = [jax.ShapeDtypeStruct(q.shape, jnp.float32)]
    out_specs = [pl.BlockSpec(row_block, row_map)]
    if return_tile_counts:
        out_shape.append(jax.ShapeDtypeStruct((b, 1, kvh), jnp.int32))
        out_specs.append(pl.BlockSpec(
            (1, 1, kvh), lambda bb, i, pt, lr: (bb, 0, 0)))
    if heads:
        state = [pltpu.VMEM((1, kvh, 1), jnp.float32),     # running max
                 pltpu.VMEM((1, kvh, 1), jnp.float32),     # running sum
                 pltpu.VMEM((kvh, d), jnp.float32)]        # acc
    else:
        state = [pltpu.VMEM((kvh, hq, 128), jnp.float32),
                 pltpu.VMEM((kvh, hq, 128), jnp.float32),
                 pltpu.VMEM((kvh, hq, d), jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=state,
    )

    outs = pl.pallas_call(
        functools.partial(_kernel, block_size=bs, n_blocks_grid=mb,
                          kv_int8=kv_int8, count_tiles=return_tile_counts),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(page_table, lens, *operands)
    out = outs[0].reshape(b, kvh, hq, d)
    if return_tile_counts:
        return out, outs[1].reshape(b, kvh)
    return out
