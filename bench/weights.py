"""Random Q8_0 weights of a configuration's model, drawn from a seed.

The leaves and their shapes come from the configuration file alone,
through its architecture module (``bench/archs/<model_type>.py``), so
that the plain reference (``bench/reference.py``) needs nothing of the
program.  Every leaf is drawn from a key of its own, and each layer of a
stacked leaf from a key of its own, so the served tree
(``bench/model.py``) and the reference draw the same numbers: the server
the whole tree in one jitted call, the reference one layer at a time.

A quantized leaf is int8 codes uniform in [-127, 127] and one float32
scale per group of :data:`GROUP` along its last axis (the contraction),
set so that the dequantized weight has the standard deviation it would
have at initialisation: 1/sqrt(fan-in) for a projection, 0.02 for a
table read by lookup.  Norm gains are float32, 1 +- 0.1.
"""

from __future__ import annotations

import zlib
from pathlib import Path
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec

GROUP = 64
# Standard deviation of a code drawn uniformly from [-127, 127].
_CODE_STD = float(np.sqrt((255 ** 2 - 1) / 12))
# Scales of one leaf vary by group within [0.5, 1.5] of the leaf's base.
_SCALE_SPREAD = 0.5
# Standard deviation of a table read by lookup (an embedding).
_TABLE_STD = 0.02


class Leaf(NamedTuple):
    """One leaf of an architecture's served tree.  ``shape`` keeps the
    contraction last; a ``stacked`` leaf carries the layer axis first.
    ``fan_in`` is the number of inputs each output of a projection sums
    over, None for a table read by lookup and for a float leaf."""

    shape: Tuple[int, ...]
    quantized: bool
    fan_in: Optional[int]
    stacked: bool


# (leaf path, kind, shape, stacked, fan-in)
PlanEntry = Tuple[str, str, Tuple[int, ...], bool, Optional[int]]


def group_size(dim: int) -> int:
    """The largest divisor of ``dim`` that is at most :data:`GROUP`."""
    g = min(GROUP, dim)
    while dim % g:
        g -= 1
    return g


def plan(conf: Dict[str, Any], root: Path = spec.ROOT) -> List[PlanEntry]:
    """One entry per array of the architecture's leaves: codes and
    scales of a quantized leaf, or a float leaf."""
    out: List[PlanEntry] = []
    leaves = spec.arch(conf, root).leaf_shapes(conf)
    for path, (shape, quantized, fan_in, stacked) in sorted(leaves.items()):
        if not quantized:
            out.append((path, "float", shape, stacked, fan_in))
            continue
        groups = shape[-1] // group_size(shape[-1])
        out.append((path, "q", shape, stacked, fan_in))
        out.append((path, "scale", shape[:-1] + (groups,), stacked, fan_in))
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


def _weight_std(fan_in: Optional[int]) -> float:
    """Standard deviation the weight would have at initialisation."""
    if fan_in is None:
        return _TABLE_STD
    return 1.0 / float(np.sqrt(fan_in))


def _gen_leaf(key, kind: str, shape, fan_in: Optional[int]):
    """One unstacked array: ``kind`` is 'q' (codes), 'scale' or 'float'."""
    if kind == "q":
        return jax.random.randint(jax.random.fold_in(key, 0), shape, -127,
                                  128, dtype=jnp.int8)
    if kind == "scale":
        base = _weight_std(fan_in) / _CODE_STD
        u = jax.random.uniform(jax.random.fold_in(key, 1), shape, jnp.float32)
        return base * (1.0 - _SCALE_SPREAD + 2 * _SCALE_SPREAD * u)
    return 1.0 + 0.2 * (jax.random.uniform(key, shape, jnp.float32) - 0.5)


def draw(key, entry: PlanEntry):
    """One array of the plan, all layers of a stacked leaf included."""
    path, kind, shape, stacked, fan_in = entry
    lkey = _leaf_key(key, path)
    if not stacked:
        return _gen_leaf(lkey, kind, shape, fan_in)
    keys = jax.vmap(lambda i: jax.random.fold_in(lkey, i))(
        jnp.arange(shape[0], dtype=jnp.uint32))
    return jax.vmap(lambda k: _gen_leaf(k, kind, shape[1:], fan_in))(keys)


def layer_weights(entries: List[PlanEntry], key, layer: Optional[Any] = None,
                  dtype=jnp.float32) -> Dict[str, jax.Array]:
    """Dequantized weights of one layer (``layer`` given, may be traced:
    its layer of each stacked leaf) or of the unstacked leaves
    (``layer=None``), as ``{leaf path: array}`` in ``dtype``.  ``key`` is
    :func:`seed_key` of the run's seed; the numbers are those
    :func:`draw` gives for the whole leaf."""
    groups: Dict[str, Dict[str, jax.Array]] = {}
    for path, kind, shape, stacked, fan_in in entries:
        if stacked != (layer is not None):
            continue
        lkey = _leaf_key(key, path)
        if stacked:
            lkey = jax.random.fold_in(lkey, jnp.asarray(layer, jnp.uint32))
            shape = shape[1:]
        groups.setdefault(path, {})[kind] = _gen_leaf(lkey, kind, shape,
                                                      fan_in)
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
