"""Grouped-int8 matmul Pallas kernel — the paper's matmul engine on TPU.

The FPGA design streams 64 int8 weights per cycle over AXI4 into on-chip
buffers and MACs them against a resident activation vector, rescaling each
group by ``xs * ws``.  The TPU-native rendering:

  * "burst reads"  -> BlockSpec-driven HBM->VMEM tiles of the int8 weight
                      matrix; the Pallas grid double-buffers them (the
                      paper's `#pragma pipeline`).
  * "unrolling"    -> each grid step issues batched 128-lane int8 dots on
                      the MXU (depth = the quant group, 64) instead of the
                      FPGA's replicated MAC trees.
  * "partitioning" -> accumulator + per-group partials live in VMEM
                      scratch, sized by the block shapes below.

Exact semantics (matches ``repro.core.quantization.qmatmul_ref``):

    out[m, n] = sum_g  f32( dot_int32(xq[m, g, :], wq[n, g, :]) )
                       * xs[m, g] * ws[n, g]

int8 x int8 products accumulate in int32 inside each group of
``group_size`` (exact — no rounding), groups combine in f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(xq_ref, xs_ref, wq_ref, ws_ref, o_ref, *, group_size: int,
            n_k_blocks: int):
    """One (bm, bn) output tile; grid dim 2 walks the K blocks."""
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bm, bk = xq_ref.shape
    bn = wq_ref.shape[0]
    g_blk = bk // group_size

    xq = xq_ref[...].reshape(bm, g_blk, group_size)
    wq = wq_ref[...].reshape(bn, g_blk, group_size)
    # Batched int8 dot over the group axis: (g, bm, gs) x (g, bn, gs)
    # -> (g, bm, bn) int32.  Depth-64 contractions ride the MXU; int32
    # accumulation inside a group is exact.
    part = jax.lax.dot_general(
        xq.swapaxes(0, 1), wq.swapaxes(0, 1),
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.int32)                  # (g_blk, bm, bn)
    xs = xs_ref[...]                                       # (bm, g_blk)
    ws = ws_ref[...]                                       # (bn, g_blk)
    scaled = part.astype(jnp.float32) \
        * xs.T[:, :, None] * ws.T[:, None, :]              # (g_blk, bm, bn)
    o_ref[...] += jnp.sum(scaled, axis=0)


def q8_matmul_pallas(xq: jax.Array, xs: jax.Array, wq: jax.Array,
                     ws: jax.Array, *, group_size: int = 64,
                     block_m: int = 128, block_n: int = 256,
                     block_k: int = 512, interpret: bool = False
                     ) -> jax.Array:
    """out = (xq*xs) @ (wq*ws).T with integer-exact group accumulation.

    xq: (M, K) int8    xs: (M, K/gs) f32
    wq: (N, K) int8    ws: (N, K/gs) f32
    returns (M, N) f32.  M, N, K must divide the block shapes (the ops.py
    wrapper pads); block_k must be a multiple of group_size.
    """
    m, k = xq.shape
    n = wq.shape[0]
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    if block_k % group_size:
        raise ValueError(f"block_k {block_k} not a multiple of group {group_size}")
    if m % block_m or n % block_n or k % block_k:
        raise ValueError(f"unpadded dims ({m},{n},{k}) vs blocks "
                         f"({block_m},{block_n},{block_k})")
    gs_blk = block_k // group_size
    n_k_blocks = k // block_k
    grid = (m // block_m, n // block_n, n_k_blocks)

    return pl.pallas_call(
        functools.partial(_kernel, group_size=group_size,
                          n_k_blocks=n_k_blocks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_m, gs_blk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_n, block_k), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((block_n, gs_blk), lambda i, j, kk: (j, kk)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                             "arbitrary")),
        interpret=interpret,
    )(xq, xs, wq, ws)
