"""Paged flash-decode attention Pallas kernel (vLLM-style page table).

The dense decode kernel (decode_attention.py) assumes each sequence owns a
contiguous ``(max_seq, KVH, hd)`` reservation.  The paged variant reads K/V
straight out of the shared block pool of ``serving/paged_cache.py``:

    k_pool / v_pool : (n_blocks, block_size, KVH, hd)   — one layer's pool
    page_table      : (B, max_blocks) int32             — block ids, -1 free
    lens            : (B,) int32                        — live lengths

Both the page table and the lengths ride in via scalar prefetch
(``pltpu.PrefetchScalarGridSpec``), so the BlockSpec index_map dereferences
``page_table[b, i]`` *before* the DMA is issued — the kernel streams exactly
the blocks a sequence owns, in order, and never materializes a contiguous
gathered copy in HBM (the gather IS the index_map).  Tiles past
``ceil(len/block_size)`` clamp onto the last live block, which Pallas
recognizes as a revisit and elides the fetch — the same length-pruning
trick as the dense kernel, so short sequences in a long-context pool cost
only their own bytes.

Q8_0 pools are supported with per-(position, kv_head) f32 scales, same as
the dense cache.  Outputs match ``ref.ref_paged_decode_attention`` (a
gather + dense softmax oracle) bit-for-bit in f32.

GQA layout matches decode_attention.py: q[b, kvh, hq, d]; one grid step
serves the hq query heads sharing a KV block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import (_n_valid_blocks, finish_softmax,
                                            init_softmax_state,
                                            online_softmax_tile)


def _kernel(pt_ref, lens_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
            *rest, block_size: int, n_blocks_grid: int, kv_int8: bool,
            count_tiles: bool):
    if count_tiles:
        cnt_ref, m_scr, l_scr, acc_scr = rest
    else:
        (m_scr, l_scr, acc_scr), cnt_ref = rest, None
    bb = pl.program_id(0)
    i = pl.program_id(2)                                    # logical block #
    length = lens_ref[bb]

    @pl.when(i == 0)
    def _init():
        init_softmax_state(m_scr, l_scr, acc_scr)
        if count_tiles:
            cnt_ref[0, 0] = 0

    @pl.when(i * block_size < length)
    def _tile():
        # the tile math is the dense kernel's — only the addressing (the
        # page-table index_map below) differs
        online_softmax_tile(q_ref, k_ref, v_ref, ks_ref, vs_ref, m_scr,
                            l_scr, acc_scr, pos0=i * block_size,
                            length=length, block=block_size,
                            kv_int8=kv_int8)
        if count_tiles:
            cnt_ref[0, 0] += 1

    @pl.when(i == n_blocks_grid - 1)
    def _finish():
        finish_softmax(o_ref, l_scr, acc_scr)


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
    if not kv_int8:
        ks_pool = jnp.ones((nb, bs, kvh), jnp.float32)
        vs_pool = jnp.ones((nb, bs, kvh), jnp.float32)

    def _blk(bb, i, pt_ref, lens_ref):
        # clamp dead logical blocks onto the last live one (revisit -> no
        # DMA), and -1 entries (released slots) onto pool block 0: the tile
        # body is skipped for them, the fetch just needs a legal address.
        i_c = jnp.minimum(i, _n_valid_blocks(lens_ref[bb], bs) - 1)
        return jnp.maximum(pt_ref[bb, i_c], 0)

    def pool_map(bb, h, i, pt_ref, lens_ref):
        return (_blk(bb, i, pt_ref, lens_ref), 0, h, 0)

    def scale_map(bb, h, i, pt_ref, lens_ref):
        return (_blk(bb, i, pt_ref, lens_ref), 0, h)

    out_shape = [jax.ShapeDtypeStruct((b, kvh, hq, d), jnp.float32)]
    out_specs = [pl.BlockSpec((1, 1, hq, d),
                              lambda bb, h, i, pt, lr: (bb, h, 0, 0))]
    if return_tile_counts:
        out_shape.append(jax.ShapeDtypeStruct((b, kvh), jnp.int32))
        out_specs.append(pl.BlockSpec((1, 1), lambda bb, h, i, pt, lr: (bb, h)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh, mb),
        in_specs=[
            pl.BlockSpec((1, 1, hq, d), lambda bb, h, i, pt, lr: (bb, h, 0, 0)),
            pl.BlockSpec((1, bs, 1, d), pool_map),
            pl.BlockSpec((1, bs, 1, d), pool_map),
            pl.BlockSpec((1, bs, 1), scale_map),
            pl.BlockSpec((1, bs, 1), scale_map),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((hq, 128), jnp.float32),
            pltpu.VMEM((hq, 128), jnp.float32),
            pltpu.VMEM((hq, d), jnp.float32),
        ],
    )

    outs = pl.pallas_call(
        functools.partial(_kernel, block_size=bs, n_blocks_grid=mb,
                          kv_int8=kv_int8, count_tiles=return_tile_counts),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(page_table, lens, q, k_pool, v_pool, ks_pool, vs_pool)
    if return_tile_counts:
        return outs[0], outs[1]
    return outs[0]
