"""A configuration file, turned into the program's model and its weights.

The configuration files under ``bench/configs/`` hold the published
``config.json`` keys of each model, plus the serving geometry and the
stated precision.  :func:`model_config` maps them onto the program's
``ModelConfig`` through the architecture module that ``model_type``
names (``bench/archs/<model_type>.py``); :func:`build_weights` makes the
served Q8_0 tree on the device from the seed in one jitted call, with
the numbers ``bench/weights.py`` draws (the same the reference draws).
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict

import jax

from bench import spec, weights


def load_config(path) -> Dict[str, Any]:
    return json.loads(Path(path).read_text())


def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file: the
    architecture's, in the stated precision."""
    prec = conf["precision"]
    if prec["weights"] != "q8_0":
        raise ValueError(f"unsupported weight format {prec['weights']!r}")
    return spec.arch(conf).program_config(conf).with_(
        compute_dtype=prec["compute"], kv_cache_dtype=prec["kv_cache"])


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _served_tree(model):
    """Shape tree of ``quantize(init)`` without the fused decode copies,
    flattened with the quantized leaves whole."""
    from repro.core.policy import QuantPolicy
    from repro.core.quantization import QuantizedTensor
    tree = jax.eval_shape(
        lambda k: model.quantize(model.init(k),
                                 QuantPolicy(bits=8, min_size=512),
                                 fuse_decode=False),
        jax.random.PRNGKey(0))
    return jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, QuantizedTensor))


def check_layout(model, conf: Dict[str, Any]) -> None:
    """Raise unless the program's served tree holds exactly the arrays of
    ``weights.plan(conf)``, with the same shapes."""
    from repro.core.quantization import QuantizedTensor
    want = {}
    for path, leaf in _served_tree(model)[0]:
        ps = _path_str(path)
        if isinstance(leaf, QuantizedTensor):
            want[(ps, "q")] = tuple(leaf.q.shape)
            want[(ps, "scale")] = tuple(leaf.scale.shape)
        else:
            want[(ps, "float")] = tuple(leaf.shape)
    got = {(ps, kind): tuple(shape)
           for ps, kind, shape, _, _ in weights.plan(conf)}
    if want != got:
        raise ValueError(f"the program's weight layout differs from "
                         f"{spec.arch_path(conf['model_type'])}: program "
                         f"{sorted(want.items())}"
                         f", benchmark {sorted(got.items())}")


def build_weights(model, conf: Dict[str, Any], seed: int):
    """The served Q8_0 parameter tree, made on the default device from
    ``seed`` in one jitted call (codes int8, scales f32, fused decode
    copies included).  Its structure and dtypes are those of
    ``model.quantize(model.init(key), QuantPolicy(bits=8, min_size=512))``."""
    from repro.core.quantization import QuantizedTensor
    from repro.models import transformer
    check_layout(model, conf)
    flat, treedef = _served_tree(model)
    entries = weights.plan(conf)
    cfg = model.cfg

    def make(key):
        arrays = {(e[0], e[1]): weights.draw(key, e) for e in entries}
        leaves = []
        for path, leaf in flat:
            ps = _path_str(path)
            if isinstance(leaf, QuantizedTensor):
                leaves.append(QuantizedTensor(
                    q=arrays[(ps, "q")], scale=arrays[(ps, "scale")],
                    group_size=leaf.group_size, bits=leaf.bits,
                    orig_dim=leaf.orig_dim))
            else:
                leaves.append(arrays[(ps, "float")])
        params = jax.tree_util.tree_unflatten(treedef, leaves)
        return transformer.fuse_decode_weights(params, cfg)

    return jax.jit(make)(weights.seed_key(seed))
