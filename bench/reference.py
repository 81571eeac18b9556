"""Plain float32 reference of the served models, and the check it decides.

Each architecture's layer, final norm and head are in its module,
``bench/archs/<model_type>.py``, written with ``jax.numpy`` alone and the
helpers here (:func:`mm`, :func:`rms`, :func:`rope`, :func:`attention`),
following the published description; none imports anything of the
program, and each reads its sizes from the configuration file.  The
reference draws its weights layer by layer from the run's seed with
``bench/weights.py`` (the int8 codes and scales the served tree was made
from, dequantized to float32), runs all matrix products at ``highest``
precision, and returns the logits before each served token.

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
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec, weights

_Q_BLOCK = 256
_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0


def _round_f8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``
    (None: one scale for the tensor)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.where(amax > 0, amax / _F8_MAX, 1.0)
    return (x / s).astype(_F8).astype(jnp.float32) * s


def mm(eq: str, x, w, control: bool):
    if control:
        x, w = _round_f8(x, -1), _round_f8(w, None)
    return jnp.einsum(eq, x, w, precision=jax.lax.Precision.HIGHEST)


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    """x (B, T, heads, hd); rotate-half layout (as HF Llama)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[..., None].astype(jnp.float32) * inv          # (B, T, hd/2)
    ang = jnp.concatenate([ang, ang], axis=-1)[:, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(ang)


def attention(q, k, v, control):
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
        s = mm("bqhd,bkhd->bhqk", qb, k, control)
        qpos = start + jnp.arange(_Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("bhqk,bkhd->bqhd", p, v, control)

    outs = jax.lax.map(block, jnp.arange(0, t, _Q_BLOCK))
    return jnp.moveaxis(outs, 0, 1).reshape(b, t, h, hd)


def logits_before(conf: Dict, seed: int,
                  seqs: Sequence[Tuple[np.ndarray, List[int]]],
                  control: bool = False,
                  root: Path = spec.ROOT) -> List[np.ndarray]:
    """For each ``(prompt, served)``: the reference logits before each
    served token, ``(len(served), vocab)`` float32, teacher-forced on
    ``prompt + served[:-1]``.  ``conf`` is the configuration file, whose
    architecture module under ``root`` gives the layers."""
    arch = spec.arch(conf, root)
    m = arch.dims(conf)
    plan = weights.plan(conf, root)
    key = weights.seed_key(seed)
    draw = jax.jit(functools.partial(weights.layer_weights, plan))
    top = draw(key)
    # one request a pass, its length padded to a power of two of at
    # least a query block, and the head over blocks of rows: a few shapes
    # serve every run, so the persistent cache holds their programs
    xs = []
    for p, s in seqs:
        full = np.concatenate([np.asarray(p), np.asarray(s[:-1], np.int32)])
        t = max(_Q_BLOCK, 1 << (len(full) - 1).bit_length())
        toks = np.zeros((1, t), np.int32)
        toks[0, :len(full)] = full
        xs.append(arch.embed(top, jnp.asarray(toks), m))
    step = jax.jit(functools.partial(arch.layer, m=m, control=control))
    for layer in range(m["layers"]):
        w = draw(key, jnp.uint32(layer))
        xs = [step(x, w, jnp.arange(x.shape[1], dtype=jnp.int32)[None])
              for x in xs]
    head = jax.jit(functools.partial(arch.lm_head, m=m, control=control))
    out = []
    for x, (p, s) in zip(xs, seqs):
        rows = np.asarray(arch.final_norm(x, top, m)[0])[
            len(p) - 1:len(p) - 1 + len(s)]
        blocks = []
        for i in range(0, len(rows), _Q_BLOCK):
            block = np.zeros((_Q_BLOCK, rows.shape[1]), np.float32)
            block[:len(rows[i:i + _Q_BLOCK])] = rows[i:i + _Q_BLOCK]
            blocks.append(np.asarray(head(jnp.asarray(block), top)))
        out.append(np.concatenate(blocks)[:len(rows)])
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
