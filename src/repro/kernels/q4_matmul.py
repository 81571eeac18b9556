"""Packed 4-bit (Q4_0) matvec Pallas kernel — the paper's §5.1 future work.

Same dataflow as q8_matvec, but weight tiles arrive as packed nibbles
(two codes per byte), halving HBM traffic again.  Unpacking happens in
VMEM with two arithmetic shifts — the TPU analogue of the FPGA widening
trick (more codes per burst word).

Packing convention (matches core.quantization._pack_nibbles):
byte b holds code[2i] in the low nibble, code[2i+1] in the high nibble,
both sign-extended int4 in [-7, 7].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _unpack(w_packed: jax.Array) -> jax.Array:
    """(N, K/2) int8 -> (N, K) int8, interleaved low/high nibbles."""
    lo = (w_packed << 4).astype(jnp.int8) >> 4
    hi = w_packed.astype(jnp.int8) >> 4
    n, kh = w_packed.shape
    return jnp.stack([lo, hi], axis=-1).reshape(n, kh * 2)


def _kernel(xq_ref, xs_ref, wq_ref, ws_ref, o_ref, *, group_size: int):
    bm, k = xq_ref.shape
    wq = _unpack(wq_ref[...])                              # (bn, K)
    bn = wq.shape[0]
    g = k // group_size
    xq = xq_ref[...].reshape(bm, g, group_size)
    wqg = wq.reshape(bn, g, group_size)
    part = jax.lax.dot_general(
        xq.swapaxes(0, 1), wqg.swapaxes(0, 1),
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.int32)                  # (g, bm, bn)
    xs = xs_ref[...]
    ws = ws_ref[...]
    scaled = part.astype(jnp.float32) * xs.T[:, :, None] * ws.T[:, None, :]
    o_ref[...] = jnp.sum(scaled, axis=0)


def q4_matvec_pallas(xq: jax.Array, xs: jax.Array, wq_packed: jax.Array,
                     ws: jax.Array, *, group_size: int = 64,
                     block_n: int = 512, interpret: bool = False
                     ) -> jax.Array:
    """out = (xq*xs) @ (unpack(wq)*ws).T.

    xq: (M, K) int8 activations (Q8_0 — acts stay 8-bit, only weights 4-bit)
    wq_packed: (N, K/2) int8, ws: (N, K/gs) f32.
    """
    m, k = xq.shape
    n = wq_packed.shape[0]
    if wq_packed.shape[1] * 2 != k:
        raise ValueError("packed K mismatch")
    block_n = min(block_n, n)
    if n % block_n or k % group_size:
        raise ValueError(f"bad dims N={n} bn={block_n} K={k} gs={group_size}")
    g = k // group_size
    grid = (n // block_n,)

    return pl.pallas_call(
        functools.partial(_kernel, group_size=group_size),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, k), lambda j: (0, 0)),
            pl.BlockSpec((m, g), lambda j: (0, 0)),
            pl.BlockSpec((block_n, k // 2), lambda j: (j, 0)),
            pl.BlockSpec((block_n, g), lambda j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((m, block_n), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(xq, xs, wq_packed, ws)
