"""Rotary position embedding Pallas kernel.

The paper singles RoPE out as a subroutine that is awkward on GPUs and
pipelines it on the FPGA (``forward_Pipeline_rotation1``).  On TPU it is a
pure VPU (8x128 vector unit) elementwise pass; the kernel exists so the
decode path can run it fused and VMEM-resident instead of as several XLA
ops.  Llama/neox convention: rotate halves.

    out = x * cos  +  rotate_half(x) * sin,   rotate_half(x) = [-x2, x1]

cos/sin are per-batch-row angle tables shared by every head: the grid
walks the batch dim and each step fetches one (1, D) angle row alongside
its (1, H, D) head block — the head broadcast happens on VMEM-resident
data inside the kernel.  (An earlier version ``jnp.repeat``-ed the tables
to (B*H, D) in HBM first: an H-fold duplication of pure angle bytes on the
decode hot path.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, cos_ref, sin_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)                  # (1, H, D)
    d = x.shape[-1]
    x1 = x[..., : d // 2]
    x2 = x[..., d // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    cos = cos_ref[...][:, None, :]                      # (1, 1, D): bcast H
    sin = sin_ref[...][:, None, :]
    o_ref[...] = (x * cos + rot * sin).astype(o_ref.dtype)


def rope_pallas(x: jax.Array, cos: jax.Array, sin: jax.Array, *,
                interpret: bool = False) -> jax.Array:
    """x: (B, H, D); cos/sin: (B, D) full-width (duplicated halves), one
    row per batch element — broadcast across H inside the kernel."""
    b, h, d = x.shape
    if cos.shape != (b, d):
        raise ValueError(f"cos/sin must be (B, D)=({b}, {d}), got {cos.shape}")
    xspec = pl.BlockSpec((1, h, d), lambda i: (i, 0, 0))
    aspec = pl.BlockSpec((1, d), lambda i: (i, 0))
    return pl.pallas_call(
        _kernel,
        grid=(b,),
        in_specs=[xspec, aspec, aspec],
        out_specs=xspec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, cos, sin)
