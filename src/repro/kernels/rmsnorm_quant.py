"""Fused RMSNorm + dynamic activation quantization Pallas kernel.

The paper's forward pass alternates ``rmsnorm -> quantize -> matmul``
(Appendix A.2 lists rmsnorm_768 and quantize_768 as separate pipelined
modules).  On TPU we fuse the two stages into one VMEM pass: normalize a
row block with fp32 gamma (the paper keeps RMSNorm params in fp32) and
emit Q8_0 codes + per-group scales directly, so the normalized fp32
activations never travel back to HBM.

    y        = x / sqrt(mean(x^2) + eps) * gamma
    q[g]     = round(127 * y[g] / max|y[g]|)   (int8)
    scale[g] = max|y[g]| / 127
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, gamma_ref, q_ref, s_ref, *, eps: float, group_size: int):
    x = x_ref[...].astype(jnp.float32)            # (bm, K)
    bm, k = x.shape
    g = k // group_size
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps) * gamma_ref[...].astype(jnp.float32)
    yg = y.reshape(bm, g, group_size)
    absmax = jnp.max(jnp.abs(yg), axis=-1, keepdims=True)
    inv = jnp.where(absmax > 0, 127.0 / absmax, 0.0)
    q = jnp.clip(jnp.round(yg * inv), -127, 127).astype(jnp.int8)
    q_ref[...] = q.reshape(bm, k)
    s_ref[...] = (absmax / 127.0).reshape(bm, g)


def rmsnorm_quant_pallas(x: jax.Array, gamma: jax.Array, *,
                         eps: float = 1e-5, group_size: int = 64,
                         block_m: int = 256, interpret: bool = False):
    """Returns (q int8 (M, K), scale f32 (M, K/gs)).

    Rows are independent, so the grid tiles M only; each step holds one
    (block_m, K) slab in VMEM — K<=16k rows of f32 fit comfortably.
    """
    m, k = x.shape
    if k % group_size:
        raise ValueError(f"K={k} not a multiple of group={group_size}")
    block_m = min(block_m, m)
    if m % block_m:
        raise ValueError(f"M={m} not a multiple of block_m={block_m}")
    g = k // group_size
    grid = (m // block_m,)

    return pl.pallas_call(
        functools.partial(_kernel, eps=eps, group_size=group_size),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, k), lambda i: (i, 0)),
            pl.BlockSpec((k,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, k), lambda i: (i, 0)),
            pl.BlockSpec((block_m, g), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k), jnp.int8),
            jax.ShapeDtypeStruct((m, g), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, gamma)
