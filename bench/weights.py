"""Random Q8_0 weights of a Llama-architecture model, drawn from a seed.

The shapes come from a configuration file alone (its published
``config.json`` keys), so that the plain reference (``bench/reference.py``)
needs nothing of the program.  Every leaf is drawn from a key of its own,
and each layer of a stacked ``blocks/*`` leaf from a key of its own, so the
served tree (``bench/model.py``) and the reference draw the same numbers:
the server the whole tree in one jitted call, the reference one layer at
a time.

A quantized leaf is int8 codes uniform in [-127, 127] and one float32
scale per group of :data:`GROUP` along its last axis (the contraction),
set so that the dequantized weight has the standard deviation it would
have at initialisation.  Norm gains are float32, 1 +- 0.1.
"""

from __future__ import annotations

import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

GROUP = 64
# Standard deviation of a code drawn uniformly from [-127, 127].
_CODE_STD = float(np.sqrt((255 ** 2 - 1) / 12))
# Scales of one leaf vary by group within [0.5, 1.5] of the leaf's base.
_SCALE_SPREAD = 0.5

# (leaf path, kind, shape, stacked, shape of the dequantized leaf)
PlanEntry = Tuple[str, str, Tuple[int, ...], bool, Tuple[int, ...]]


def dims(conf: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes a forward pass needs, from the published keys."""
    heads = conf["num_attention_heads"]
    return {"layers": conf["num_hidden_layers"], "d": conf["hidden_size"],
            "heads": heads, "kv_heads": conf["num_key_value_heads"],
            "hd": conf.get("head_dim") or conf["hidden_size"] // heads,
            "ff": conf["intermediate_size"], "vocab": conf["vocab_size"],
            "theta": float(conf["rope_theta"]),
            "eps": float(conf["rms_norm_eps"]),
            "tied": bool(conf["tie_word_embeddings"])}


def leaf_shapes(conf: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], bool]]:
    """``{leaf path: (shape, quantized)}`` of the model; ``blocks/*``
    leaves carry the layer axis first.  Projections keep the contraction
    last: ``wq`` is (heads, hd, d), ``wo`` is (d, heads, hd)."""
    m = dims(conf)
    n, d, hd, ff = m["layers"], m["d"], m["hd"], m["ff"]
    out = {
        "embed": ((m["vocab"], d), True),
        "final_norm/gamma": ((d,), False),
        "blocks/norm1/gamma": ((n, d), False),
        "blocks/norm2/gamma": ((n, d), False),
        "blocks/attn/wq": ((n, m["heads"], hd, d), True),
        "blocks/attn/wk": ((n, m["kv_heads"], hd, d), True),
        "blocks/attn/wv": ((n, m["kv_heads"], hd, d), True),
        "blocks/attn/wo": ((n, d, m["heads"], hd), True),
        "blocks/mlp/w1": ((n, ff, d), True),
        "blocks/mlp/w3": ((n, ff, d), True),
        "blocks/mlp/w2": ((n, d, ff), True),
    }
    if not m["tied"]:
        out["lm_head"] = ((m["vocab"], d), True)
    return out


def group_size(dim: int) -> int:
    """The largest divisor of ``dim`` that is at most :data:`GROUP`."""
    g = min(GROUP, dim)
    while dim % g:
        g -= 1
    return g


def plan(conf: Dict[str, Any]) -> List[PlanEntry]:
    """One entry per array: codes and scales of a quantized leaf, or a
    float leaf."""
    out: List[PlanEntry] = []
    for path, (shape, quantized) in sorted(leaf_shapes(conf).items()):
        stacked = path.startswith("blocks/")
        if not quantized:
            out.append((path, "float", shape, stacked, shape))
            continue
        groups = shape[-1] // group_size(shape[-1])
        out.append((path, "q", shape, stacked, shape))
        out.append((path, "scale", shape[:-1] + (groups,), stacked, shape))
    return out


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed, including ones past 32 bits."""
    seed = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(0)
    for word in (seed & 0xFFFFFFFF, seed >> 32):
        key = jax.random.fold_in(key, np.uint32(word))
    return key


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()))


def _weight_std(path: str, shape) -> float:
    """Standard deviation the weight would have at initialisation: 0.02
    for the embedding table, 1/sqrt(fan-in) for a projection."""
    if path.startswith("embed"):
        return 0.02
    fan_in = int(np.prod(shape[1:])) if path.endswith("wo") else shape[-1]
    return 1.0 / float(np.sqrt(fan_in))


def _gen_leaf(key, path: str, kind: str, shape, orig_shape):
    """One unstacked array: ``kind`` is 'q' (codes), 'scale' or 'float'."""
    if kind == "q":
        return jax.random.randint(jax.random.fold_in(key, 0), shape, -127,
                                  128, dtype=jnp.int8)
    if kind == "scale":
        base = _weight_std(path, orig_shape) / _CODE_STD
        u = jax.random.uniform(jax.random.fold_in(key, 1), shape, jnp.float32)
        return base * (1.0 - _SCALE_SPREAD + 2 * _SCALE_SPREAD * u)
    return 1.0 + 0.2 * (jax.random.uniform(key, shape, jnp.float32) - 0.5)


def draw(key, entry: PlanEntry):
    """One array of the plan, all layers of a stacked leaf included."""
    path, kind, shape, stacked, orig = entry
    lkey = _leaf_key(key, path)
    if not stacked:
        return _gen_leaf(lkey, path, kind, shape, orig)
    keys = jax.vmap(lambda i: jax.random.fold_in(lkey, i))(
        jnp.arange(shape[0], dtype=jnp.uint32))
    return jax.vmap(lambda k: _gen_leaf(k, path, kind, shape[1:],
                                        orig[1:]))(keys)


def layer_weights(entries: List[PlanEntry], key, layer: Optional[Any] = None,
                  dtype=jnp.float32) -> Dict[str, jax.Array]:
    """Dequantized weights of one layer (``layer`` given, may be traced:
    the ``blocks/*`` leaves of that layer) or of the unstacked leaves
    (``layer=None``), as ``{leaf path: array}`` in ``dtype``.  ``key`` is
    :func:`seed_key` of the run's seed; the numbers are those
    :func:`draw` gives for the whole leaf."""
    groups: Dict[str, Dict[str, jax.Array]] = {}
    for path, kind, shape, stacked, orig in entries:
        if stacked != (layer is not None):
            continue
        lkey = _leaf_key(key, path)
        if stacked:
            lkey = jax.random.fold_in(lkey, jnp.asarray(layer, jnp.uint32))
            shape, orig = shape[1:], orig[1:]
        groups.setdefault(path, {})[kind] = _gen_leaf(lkey, path, kind,
                                                      shape, orig)
    out = {}
    for path, g in groups.items():
        if "float" in g:
            out[path] = g["float"].astype(dtype)
            continue
        q, s = g["q"], g["scale"]
        w = (q.reshape(*q.shape[:-1], s.shape[-1], -1).astype(jnp.float32)
             * s[..., None]).reshape(q.shape)
        out[path] = w.astype(dtype)
    return out
