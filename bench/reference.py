"""Plain float32 reference of the served models, and the check it decides.

A Llama-architecture decoder (RMSNorm, rotate-half RoPE, grouped-query
attention, SwiGLU, no biases) written with ``jax.numpy`` alone, following
the published description; it imports nothing of the program and reads
its sizes from the configuration file.  It draws its weights layer by
layer from the run's seed with ``bench/weights.py`` (the int8 codes and
scales the served tree was made from, dequantized to float32), runs all
matrix products at ``highest`` precision, and returns the logits before
each served token.

``control=True`` is the comparison's control: the same pass with every
matrix product's operands rounded to float8 (e4m3, one scale per tensor
for weights and per row for activations), the nearest precision below
the bfloat16 that the configurations state.

The check (:func:`served_gap`): teacher-forced on a request's prompt and
its served tokens, the widest gap by which a served token's reference
logit lies below the reference's best logit at that position.  Greedy
serving in exact arithmetic reads 0.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

_Q_BLOCK = 256
_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0


def _round_f8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``
    (None: one scale for the tensor)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.where(amax > 0, amax / _F8_MAX, 1.0)
    return (x / s).astype(_F8).astype(jnp.float32) * s


def _mm(eq: str, x, w, control: bool):
    if control:
        x, w = _round_f8(x, -1), _round_f8(w, None)
    return jnp.einsum(eq, x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x (B, T, heads, hd); rotate-half layout (as HF Llama)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[..., None].astype(jnp.float32) * inv          # (B, T, hd/2)
    ang = jnp.concatenate([ang, ang], axis=-1)[:, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(ang)


def _attention(q, k, v, control):
    """Causal GQA attention, q (B, T, H, hd), k/v (B, T, KVH, hd), in
    query blocks so that the score block stays small."""
    b, t, h, hd = q.shape
    kvh = k.shape[2]
    k = jnp.repeat(k, h // kvh, axis=2)
    v = jnp.repeat(v, h // kvh, axis=2)
    q = q / jnp.sqrt(jnp.float32(hd))
    kpos = jnp.arange(t)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, _Q_BLOCK, axis=1)
        s = _mm("bqhd,bkhd->bhqk", qb, k, control)
        qpos = start + jnp.arange(_Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("bhqk,bkhd->bqhd", p, v, control)

    outs = jax.lax.map(block, jnp.arange(0, t, _Q_BLOCK))
    return jnp.moveaxis(outs, 0, 1).reshape(b, t, h, hd)


def _layer(x, w: Dict[str, jax.Array], pos, m, control):
    h = _rms(x, w["blocks/norm1/gamma"], m["eps"])
    q = _mm("btd,hkd->bthk", h, w["blocks/attn/wq"], control)
    k = _mm("btd,hkd->bthk", h, w["blocks/attn/wk"], control)
    v = _mm("btd,hkd->bthk", h, w["blocks/attn/wv"], control)
    q, k = _rope(q, pos, m["theta"]), _rope(k, pos, m["theta"])
    a = _attention(q, k, v, control)
    x = x + _mm("bthk,dhk->btd", a, w["blocks/attn/wo"], control)
    h = _rms(x, w["blocks/norm2/gamma"], m["eps"])
    g = _mm("btd,fd->btf", h, w["blocks/mlp/w1"], control)
    u = _mm("btd,fd->btf", h, w["blocks/mlp/w3"], control)
    return x + _mm("btf,df->btd", jax.nn.silu(g) * u, w["blocks/mlp/w2"],
                   control)


def logits_before(conf: Dict, seed: int,
                  seqs: Sequence[Tuple[np.ndarray, List[int]]],
                  control: bool = False) -> List[np.ndarray]:
    """For each ``(prompt, served)``: the reference logits before each
    served token, ``(len(served), vocab)`` float32, teacher-forced on
    ``prompt + served[:-1]``.  ``conf`` is the configuration file."""
    m = weights.dims(conf)
    plan = weights.plan(conf)
    key = weights.seed_key(seed)
    lens = [len(p) + len(s) - 1 for p, s in seqs]
    t = -(-max(lens) // _Q_BLOCK) * _Q_BLOCK
    toks = np.zeros((len(seqs), t), np.int32)
    for i, (p, s) in enumerate(seqs):
        full = np.concatenate([np.asarray(p), np.asarray(s[:-1], np.int32)])
        toks[i, :len(full)] = full
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), toks.shape)
    draw = jax.jit(functools.partial(weights.layer_weights, plan))
    top = draw(key)
    x = jnp.take(top["embed"], jnp.asarray(toks), axis=0)
    step = jax.jit(functools.partial(_layer, m=m, control=control))
    for layer in range(m["layers"]):
        x = step(x, draw(key, jnp.uint32(layer)), pos)
    head = top["embed"] if m["tied"] else top["lm_head"]
    x = _rms(x, top["final_norm/gamma"], m["eps"])
    out = []
    for i, (p, s) in enumerate(seqs):
        rows = x[i, len(p) - 1:len(p) - 1 + len(s)]
        out.append(np.asarray(_mm("td,vd->tv", rows, head, control)))
    return out


def served_gap(ref_rows: Sequence[np.ndarray],
               served: Sequence[Sequence[int]]) -> float:
    """Widest gap, over every served token, between the reference's best
    logit and its logit for the served token."""
    gap = 0.0
    for rows, toks in zip(ref_rows, served):
        picked = rows[np.arange(len(toks)), np.asarray(toks)]
        gap = max(gap, float(np.max(rows.max(axis=1) - picked)))
    return gap


def control_gap(ref_rows: Sequence[np.ndarray],
                ctl_rows: Sequence[np.ndarray]) -> float:
    """The control's reading: at each position, the reference's gap for
    the token the control puts first; the widest over all positions."""
    return served_gap(ref_rows, [r.argmax(axis=1) for r in ctl_rows])
