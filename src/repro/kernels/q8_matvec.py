"""Decode GEMV Pallas kernel — the paper's inner loop, TPU-native.

During single-token decode every matmul in the forward pass is a GEMV
(the paper's ``matmul_768_768`` .. ``matmul_768_32000`` modules).  The FPGA
keeps the activation vector on-chip and streams weight rows; we do exactly
that in VMEM terms: the quantized activation block (a few rows — decode
batch per chip) stays resident across the whole grid, while (bn, K) int8
weight tiles stream HBM->VMEM, one per grid step, double-buffered by the
Pallas pipeline.

Distinct from q8_matmul: no K grid dimension — the full contraction happens
inside one grid step, so per-output-tile partials never round-trip to HBM.
This is the right shape when ``M*K`` (activations) fits VMEM but ``N*K``
(weights) does not, i.e. decode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(xq_ref, xs_ref, wq_ref, ws_ref, o_ref, *, group_size: int):
    bm, k = xq_ref.shape
    bn = wq_ref.shape[0]
    g = k // group_size
    xq = xq_ref[...].reshape(bm, g, group_size)
    wq = wq_ref[...].reshape(bn, g, group_size)
    part = jax.lax.dot_general(
        xq.swapaxes(0, 1), wq.swapaxes(0, 1),
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.int32)                  # (g, bm, bn)
    xs = xs_ref[...]                                       # (bm, g)
    ws = ws_ref[...]                                       # (bn, g)
    scaled = part.astype(jnp.float32) * xs.T[:, :, None] * ws.T[:, None, :]
    o_ref[...] = jnp.sum(scaled, axis=0)


def q8_matvec_pallas(xq: jax.Array, xs: jax.Array, wq: jax.Array,
                     ws: jax.Array, *, group_size: int = 64,
                     block_n: int = 512, interpret: bool = False
                     ) -> jax.Array:
    """out = (xq*xs) @ (wq*ws).T, activations fully VMEM-resident.

    xq: (M, K) int8 with small M (decode batch)   xs: (M, K/gs) f32
    wq: (N, K) int8                               ws: (N, K/gs) f32
    N must divide block_n (ops.py pads).  VMEM check: block_n*K int8 +
    M*K int8 + partials (g, M, block_n) f32 must fit ~16 MiB; defaults
    cover K<=8192 at block_n=512.
    """
    m, k = xq.shape
    n = wq.shape[0]
    block_n = min(block_n, n)
    if n % block_n:
        raise ValueError(f"N={n} not a multiple of block_n={block_n}")
    if k % group_size:
        raise ValueError(f"K={k} not a multiple of group={group_size}")
    g = k // group_size
    grid = (n // block_n,)

    return pl.pallas_call(
        functools.partial(_kernel, group_size=group_size),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, k), lambda j: (0, 0)),     # resident acts
            pl.BlockSpec((m, g), lambda j: (0, 0)),
            pl.BlockSpec((block_n, k), lambda j: (j, 0)),  # streamed weights
            pl.BlockSpec((block_n, g), lambda j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((m, block_n), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(xq, xs, wq, ws)
