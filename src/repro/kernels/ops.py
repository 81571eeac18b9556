"""Jit'd public wrappers around the Pallas kernels.

Handles padding to block multiples, activation quantization, layout
massaging (the kernels want flat 2-D operands), and the interpret flag
(True on this CPU container; False when targeting real TPUs).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quantization import QuantizedTensor, quantize
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.paged_decode_attention import paged_decode_attention_pallas
from repro.kernels.paged_prefill_attention import paged_prefill_attention_pallas
from repro.kernels.flash_prefill import flash_prefill_pallas
from repro.kernels.q4_matmul import q4_matvec_pallas
from repro.kernels.q8_matmul import q8_matmul_pallas
from repro.kernels.q8_matvec import q8_matvec_pallas
from repro.kernels.rmsnorm_quant import rmsnorm_quant_pallas
from repro.kernels.rope import rope_pallas

# decode-vs-prefill dispatch threshold: below this many rows per shard the
# GEMV kernel (activations resident, no K grid) wins.
MATVEC_MAX_ROWS = 32


def _pad_axis(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@partial(jax.jit, static_argnames=("interpret", "block_m", "block_n",
                                   "block_k"))
def q8_matmul(x: jax.Array, w: QuantizedTensor, *, interpret: bool = False,
              block_m: int = 128, block_n: int = 256, block_k: int = 512
              ) -> jax.Array:
    """x (…, K) f32  @  wq (N, K).T  with paper-exact integer semantics.

    Quantizes activations Q8_0 on the fly, dispatches GEMV/GEMM on row
    count, pads every dim to block multiples and slices the result back.
    """
    if w.bits not in (4, 8):
        raise ValueError(f"bits={w.bits}")
    gs = w.group_size
    *lead, k = x.shape
    m = 1
    for d in lead:
        m *= d
    x2 = x.reshape(m, k)
    xq_t = quantize(x2, group_size=gs, bits=8)
    xq, xs = xq_t.q, xq_t.scale
    wq, ws = w.q, w.scale
    n = wq.shape[0]

    if w.bits == 4:
        bn = min(512, n) if n % 512 == 0 else _largest_block(n, 512)
        out = q4_matvec_pallas(xq, xs, wq, ws, group_size=gs, block_n=bn,
                               interpret=interpret)
    elif m <= MATVEC_MAX_ROWS:
        bn = _largest_block(n, 512)
        out = q8_matvec_pallas(xq, xs, wq, ws, group_size=gs, block_n=bn,
                               interpret=interpret)
    else:
        bm = _largest_block(m, block_m)
        bn_ = _largest_block(n, block_n)
        bk = _largest_block(k, block_k, mult=gs)
        out = q8_matmul_pallas(xq, xs, wq, ws, group_size=gs, block_m=bm,
                               block_n=bn_, block_k=bk, interpret=interpret)
    return out.reshape(*lead, n)


def _largest_block(dim: int, preferred: int, mult: int = 1) -> int:
    """Largest divisor of ``dim`` <= preferred that is a multiple of mult."""
    b = min(preferred, dim)
    while b > 1 and (dim % b or b % mult):
        b -= 1
    return max(b, 1)


@partial(jax.jit, static_argnames=("eps", "group_size", "interpret"))
def rmsnorm_quant(x: jax.Array, gamma: jax.Array, *, eps: float = 1e-5,
                  group_size: int = 64, interpret: bool = False):
    """Fused RMSNorm + Q8_0: (…, K) f32 -> ((…, K) i8, (…, K/gs) f32)."""
    *lead, k = x.shape
    m = 1
    for d in lead:
        m *= d
    x2 = x.reshape(m, k)
    bm = _largest_block(m, 256)
    q, s = rmsnorm_quant_pallas(x2, gamma, eps=eps, group_size=group_size,
                                block_m=bm, interpret=interpret)
    return q.reshape(*lead, k), s.reshape(*lead, k // group_size)


@partial(jax.jit, static_argnames=("interpret",))
def rope(x: jax.Array, cos: jax.Array, sin: jax.Array, *,
         interpret: bool = False) -> jax.Array:
    """x: (B, H, D); cos/sin: (B, D) (full-width, already duplicated halves).

    The angle tables stay (B, D) in HBM — the kernel broadcasts them
    across H via its index_map instead of ``jnp.repeat``-ing them to
    (B*H, D) first."""
    return rope_pallas(x, cos, sin, interpret=interpret)


@partial(jax.jit, static_argnames=("block_s", "prune", "return_tile_counts",
                                   "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lens: jax.Array, k_scale: Optional[jax.Array] = None,
                     v_scale: Optional[jax.Array] = None, *,
                     block_s: int = 512, prune: bool = True,
                     return_tile_counts: bool = False,
                     interpret: bool = False):
    """Single-token attention vs. a (possibly int8) KV cache.

    q: (B, H, D) already scaled by 1/sqrt(D); k/v: (B, S, KVH, D);
    lens: (B,) int32 valid lengths.  Returns (B, H, D) f32.

    ``prune=True`` (default) skips fetching/computing KV tiles past each
    row's length — bit-exact with the full scan.  ``return_tile_counts``
    additionally returns (B, KVH) int32 counts of tiles whose body ran.
    """
    b, h, d = q.shape
    kvh = k.shape[2]
    hq = h // kvh
    qg = q.reshape(b, kvh, hq, d)
    s = k.shape[1]
    bs = _largest_block(s, block_s)
    out = decode_attention_pallas(qg, k, v, lens.reshape(b),
                                  k_scale, v_scale, block_s=bs, prune=prune,
                                  return_tile_counts=return_tile_counts,
                                  interpret=interpret)
    if return_tile_counts:
        return out[0].reshape(b, h, d), out[1]
    return out.reshape(b, h, d)


@partial(jax.jit, static_argnames=("return_tile_counts", "interpret"))
def paged_decode_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                           page_table: jax.Array, lens: jax.Array,
                           ks_pool: Optional[jax.Array] = None,
                           vs_pool: Optional[jax.Array] = None, *,
                           return_tile_counts: bool = False,
                           interpret: bool = False):
    """Single-token attention reading K/V through a vLLM-style page table.

    q: (B, H, D) already scaled by 1/sqrt(D); k/v_pool: (NB, BS, KVH, D)
    (int8 when ks/vs_pool (NB, BS, KVH) are given); page_table: (B, MB)
    int32; lens: (B,) int32.  Returns (B, H, D) f32.
    """
    b, h, d = q.shape
    kvh = k_pool.shape[2]
    hq = h // kvh
    qg = q.reshape(b, kvh, hq, d)
    out = paged_decode_attention_pallas(
        qg, k_pool, v_pool, page_table, lens, ks_pool, vs_pool,
        return_tile_counts=return_tile_counts, interpret=interpret)
    if return_tile_counts:
        return out[0].reshape(b, h, d), out[1]
    return out.reshape(b, h, d)


@partial(jax.jit, static_argnames=("block_q", "return_tile_counts",
                                   "interpret"))
def paged_prefill_attention(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, page_table: jax.Array,
                            pfx_lens: jax.Array, q_lens=None,
                            ks_pool: Optional[jax.Array] = None,
                            vs_pool: Optional[jax.Array] = None, *,
                            block_q: int = 128,
                            return_tile_counts: bool = False,
                            interpret: bool = False):
    """Rectangular-q attention over the paged prefix of a prefill chunk.

    q: (B, C, H, D) already scaled by 1/sqrt(D); k/v_pool:
    (NB, BS, KVH, D) (int8 when ks/vs_pool (NB, BS, KVH) are given);
    page_table: (B, MB) int32; pfx_lens/q_lens: (B,) int32 traced data
    (prefix rows each chunk row attends / valid chunk rows).  Returns the
    prefix segment's flash state in `layers.attention_chunk_merge`'s
    ``pfx_state`` layout — out (B, C, H, D) f32, m (B, H, C, 1) f32,
    l (B, H, C, 1) f32 — plus (B, KVH) live-tile counts when
    ``return_tile_counts``.  An empty prefix row is exactly
    (out=0, m=-1e30, l=0), which the merge weights at exactly zero.
    """
    c = q.shape[1]
    # several q tiles: each one's rows must be a multiple of Mosaic's
    # 8-row sublane tiling
    bq = c if c <= block_q else _largest_block(c, block_q, mult=8)
    return paged_prefill_attention_pallas(
        q, k_pool, v_pool, page_table, pfx_lens, q_lens, ks_pool, vs_pool,
        block_q=bq, return_tile_counts=return_tile_counts,
        interpret=interpret)


@partial(jax.jit, static_argnames=("causal", "interpret",
                                   "block_q", "block_k"))
def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, q_offset=0,
                  q_lens=None, k_lens=None,
                  block_q: int = 128, block_k: int = 128,
                  interpret: bool = False) -> jax.Array:
    """Full-sequence attention: q (B,Sq,H,D); k/v (B,Sk,KVH,D) ->
    (B,Sq,H,D).

    GQA KV heads are repeated to H (XLA keeps it a gather) and the head
    axis folds into the grid's batch dim; blocks pad via the wrapper.
    ``Sk > Sq`` with a ``q_offset`` is the chunked-prefill form: query
    row i of batch row b sits at global position ``q_offset[b] + i`` and
    attends the prefix keys plus its own chunk causally.  ``q_offset``
    (int or (B,)) and the optional per-row valid extents ``q_lens`` /
    ``k_lens`` (B,) are *traced data* carried into the kernel by scalar
    prefetch — NOT static arguments — so serving traffic with churning
    chunk lengths and offsets shares one compiled executable per padded
    shape (the shape-stability contract of
    models/transformer.prefill_chunk_batch)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    kvh = k.shape[2]
    hq = h // kvh
    kr = jnp.repeat(k, hq, axis=2)
    vr = jnp.repeat(v, hq, axis=2)
    qf = jnp.moveaxis(q, 2, 1).reshape(b * h, sq, d)
    kf = jnp.moveaxis(kr, 2, 1).reshape(b * h, sk, d)
    vf = jnp.moveaxis(vr, 2, 1).reshape(b * h, sk, d)
    bq = _largest_block(sq, block_q)
    bk = _largest_block(sk, block_k)

    def per_bh(x, fill):
        """Per-batch scalar/array -> per-(batch*head) rows (b-major)."""
        if x is None:
            return jnp.full((b * h,), fill, jnp.int32)
        x = jnp.broadcast_to(jnp.asarray(x, jnp.int32), (b,))
        return jnp.repeat(x, h)

    out = flash_prefill_pallas(
        qf, kf, vf, causal=causal, q_offset=per_bh(q_offset, 0),
        q_lens=per_bh(q_lens, sq), k_lens=per_bh(k_lens, sk),
        block_q=bq, block_k=bk, interpret=interpret)
    return jnp.moveaxis(out.reshape(b, h, sq, d), 1, 2)
