"""Length-aware flash-decode attention Pallas kernel.

The paper's FPGA computes decode attention head-by-head with exact softmax
(its ``forward_Pipeline_iterate/max/exp/sum/norm`` modules are an explicit
streaming softmax).  The TPU-native equivalent is flash-decoding: stream
the KV cache in (block_s, head_dim) tiles, maintain the online-softmax
running (max, sum, acc) in VMEM scratch, and never materialize the (S,)
score vector in HBM.

Two traffic optimizations on top of the plain streaming kernel — decode is
HBM-bandwidth-bound, so these are the whole ballgame:

* **Length pruning** (``prune=True``): per-batch lengths arrive via scalar
  prefetch (``pltpu.PrefetchScalarGridSpec``), so both the kernel body and
  the BlockSpec index_maps can see them *before* any DMA is issued.  KV
  tiles past ``ceil(len/block_s)`` are (a) never fetched — the index_map
  clamps their block index to the last valid tile, and Pallas skips the
  copy when consecutive grid steps map to the same block (revisiting) —
  and (b) never computed — the whole tile body sits under ``pl.when``.
  At 4k ``max_seq`` with ~200-token live sequences this removes ~95% of
  decode-attention HBM traffic.  Pruned and unpruned outputs are
  bit-exact: a fully-masked tile contributes ``p == 0`` and leaves the
  running (max, sum) untouched, which is precisely what skipping does.

* **Quantized KV** (beyond-paper): the cache may be Q8_0 per
  (position, kv_head) — int8 codes + one f32 scale — halving/quartering
  cache traffic.  Scores use f32 q x dequantized k, keeping softmax exact
  (the paper computes exact nonlinearities; we do not approximate).

GQA layout: queries arrive grouped per KV head, q[b, kvh, hq, d], so one
grid step serves all hq queries that share a KV tile.

``return_tile_counts=True`` adds a per-(batch, kv_head) int32 output
counting the tiles whose body actually ran — the interpret-mode proof that
pruning skips exactly ``n_s - ceil(len/block_s)`` tiles (see
tests/test_decode_paths.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _n_valid_blocks(length, block_s: int):
    """Number of KV tiles holding live positions; >=1 so index_maps always
    have a legal tile to (re)visit even for len==0 dead slots."""
    return jnp.maximum(pl.cdiv(length, block_s), 1)


# -- streaming-softmax tile primitives --------------------------------------


def init_softmax_state(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def online_softmax_tile(q_ref, k_ref, v_ref, ks_ref, vs_ref, m_scr, l_scr,
                        acc_scr, *, pos0, length, block: int, kv_int8: bool):
    """Fold one (block, d) KV tile starting at position ``pos0`` into the
    running (max, sum, acc); positions >= ``length`` are masked out."""
    q = q_ref[0, 0].astype(jnp.float32)                 # (hq, d)
    k = k_ref[0, :, 0, :].astype(jnp.float32)           # (bs, d)
    v = v_ref[0, :, 0, :].astype(jnp.float32)           # (bs, d)
    if kv_int8:
        k = k * ks_ref[0, :, 0][:, None]                # dequant per pos
        v = v * vs_ref[0, :, 0][:, None]

    pos = pos0 + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    valid = pos < length                                # (1, bs)

    scores = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)             # (hq, bs)
    scores = jnp.where(valid, scores, NEG_INF)

    m_prev = m_scr[:, :1]                               # (hq, 1)
    l_prev = l_scr[:, :1]
    m_cur = jnp.max(scores, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)                         # (hq, bs)
    p = jnp.where(valid, p, 0.0)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # (hq, d)

    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
    acc_scr[...] = acc


def finish_softmax(o_ref, l_scr, acc_scr):
    l = l_scr[:, :1]
    o_ref[0, 0] = (acc_scr[...] /
                   jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def _kernel(lens_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, *rest,
            block_s: int, n_s_blocks: int, kv_int8: bool, prune: bool,
            count_tiles: bool):
    if count_tiles:
        cnt_ref, m_scr, l_scr, acc_scr = rest
    else:
        (m_scr, l_scr, acc_scr), cnt_ref = rest, None
    bb = pl.program_id(0)
    s_idx = pl.program_id(2)
    length = lens_ref[bb]

    @pl.when(s_idx == 0)
    def _init():
        init_softmax_state(m_scr, l_scr, acc_scr)
        if count_tiles:
            cnt_ref[0, 0] = 0

    # tile holds at least one live position?  (always "yes" when pruning is
    # off — the unpruned kernel masks inside the tile instead)
    live = (s_idx * block_s < length) if prune else (s_idx >= 0)

    @pl.when(live)
    def _tile():
        online_softmax_tile(q_ref, k_ref, v_ref, ks_ref, vs_ref, m_scr,
                            l_scr, acc_scr, pos0=s_idx * block_s,
                            length=length, block=block_s, kv_int8=kv_int8)
        if count_tiles:
            cnt_ref[0, 0] += 1

    @pl.when(s_idx == n_s_blocks - 1)
    def _finish():
        finish_softmax(o_ref, l_scr, acc_scr)


def decode_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                            lens: jax.Array, k_scale=None, v_scale=None, *,
                            block_s: int = 512, prune: bool = True,
                            return_tile_counts: bool = False,
                            interpret: bool = False):
    """q: (B, KVH, HQ, D) pre-scaled by 1/sqrt(D); k/v: (B, S, KVH, D)
    (int8 when k_scale/v_scale (B, S, KVH) are given); lens: (B,) int32.
    Returns (B, KVH, HQ, D) f32 — plus (B, KVH) int32 live-tile counts when
    ``return_tile_counts``.
    """
    b, kvh, hq, d = q.shape
    s = k.shape[1]
    block_s = min(block_s, s)
    if s % block_s:
        raise ValueError(f"S={s} not a multiple of block_s={block_s}")
    n_s = s // block_s
    lens = lens.reshape(b).astype(jnp.int32)
    kv_int8 = k_scale is not None
    if not kv_int8:
        # dummy scale operands keep the kernel signature uniform
        k_scale = jnp.ones((b, s, kvh), jnp.float32)
        v_scale = jnp.ones((b, s, kvh), jnp.float32)

    def kv_map(bb, h, ss, lens_ref):
        if prune:
            # clamp dead tiles onto the last live tile: same block index as
            # the previous grid step -> Pallas elides the fetch entirely.
            ss = jnp.minimum(ss, _n_valid_blocks(lens_ref[bb], block_s) - 1)
        return (bb, ss, h, 0)

    def scale_map(bb, h, ss, lens_ref):
        if prune:
            ss = jnp.minimum(ss, _n_valid_blocks(lens_ref[bb], block_s) - 1)
        return (bb, ss, h)

    out_shape = [jax.ShapeDtypeStruct((b, kvh, hq, d), jnp.float32)]
    out_specs = [pl.BlockSpec((1, 1, hq, d), lambda bb, h, ss, lr: (bb, h, 0, 0))]
    if return_tile_counts:
        out_shape.append(jax.ShapeDtypeStruct((b, kvh), jnp.int32))
        out_specs.append(pl.BlockSpec((1, 1), lambda bb, h, ss, lr: (bb, h)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kvh, n_s),
        in_specs=[
            pl.BlockSpec((1, 1, hq, d), lambda bb, h, ss, lr: (bb, h, 0, 0)),
            pl.BlockSpec((1, block_s, 1, d), kv_map),
            pl.BlockSpec((1, block_s, 1, d), kv_map),
            pl.BlockSpec((1, block_s, 1), scale_map),
            pl.BlockSpec((1, block_s, 1), scale_map),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((hq, 128), jnp.float32),   # running max (dup lanes)
            pltpu.VMEM((hq, 128), jnp.float32),   # running sum
            pltpu.VMEM((hq, d), jnp.float32),     # acc
        ],
    )

    outs = pl.pallas_call(
        functools.partial(_kernel, block_s=block_s, n_s_blocks=n_s,
                          kv_int8=kv_int8, prune=prune,
                          count_tiles=return_tile_counts),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lens, q, k, v, k_scale, v_scale)
    if return_tile_counts:
        return outs[0], outs[1]
    return outs[0]
