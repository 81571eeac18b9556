"""The Q8_0 weights: the benchmark's layout from the configuration file
is the program's served tree, and the reference's per-layer draw gives
the served numbers."""

import jax
import numpy as np
import pytest

from bench import model as bm
from bench import spec, weights
from bench_tiny import reduced_conf
from repro.core.policy import QuantPolicy
from repro.core.quantization import QuantizedTensor, dequantize
from repro.models import build_model

CONFIGS = sorted(p.name for p in (spec.ROOT / "bench" / "configs").glob("*.json"))


def _conf(name):
    return reduced_conf(bm.load_config(spec.ROOT / "bench" / "configs" / name))


@pytest.mark.parametrize("name", CONFIGS)
def test_layout_is_the_programs_at_published_widths(name):
    conf = bm.load_config(spec.ROOT / "bench" / "configs" / name)
    bm.check_layout(build_model(bm.model_config(conf)), conf)


@pytest.mark.parametrize("name", CONFIGS)
def test_tree_is_the_quantized_init(name):
    conf = _conf(name)
    model = build_model(bm.model_config(conf))
    built = jax.eval_shape(lambda: bm.build_weights(model, conf, 2 ** 33 + 1))
    want = jax.eval_shape(
        lambda k: model.quantize(model.init(k),
                                 QuantPolicy(bits=8, min_size=512)),
        jax.random.PRNGKey(0))
    assert (jax.tree_util.tree_structure(built)
            == jax.tree_util.tree_structure(want))
    for a, b in zip(jax.tree_util.tree_leaves(built),
                    jax.tree_util.tree_leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_a_layout_the_program_does_not_serve_is_refused():
    conf = _conf(CONFIGS[0])
    model = build_model(bm.model_config(conf))
    with pytest.raises(ValueError):
        bm.check_layout(model, dict(conf, intermediate_size=512))


def _leaf(tree, path):
    for part in path.split("/"):
        tree = tree[part]
    return np.asarray(dequantize(tree) if isinstance(tree, QuantizedTensor)
                      else tree)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_draws_the_served_weights(name):
    """Every planned leaf of the served tree, dequantized, is what the
    reference draws for it (the last layer of a stacked leaf); each of the
    program's fused decode copies holds the rows of the drawn leaves its
    architecture names, in that order."""
    conf = _conf(name)
    model = build_model(bm.model_config(conf))
    seed = 2 ** 40 + 3
    params = bm.build_weights(model, conf, seed)
    plan = weights.plan(conf)
    key = weights.seed_key(seed)
    layer = model.cfg.n_layers - 1
    drawn = {**weights.layer_weights(plan, key),
             **weights.layer_weights(plan, key, layer)}
    stacked = {e[0] for e in plan if e[3]}
    for path in drawn:
        served = _leaf(params, path)
        served = served[layer] if path in stacked else served
        assert np.array_equal(np.asarray(drawn[path]), served), path
    flat = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, QuantizedTensor))[0]
    fused = spec.arch(conf).fused_copies(conf)
    assert sorted(bm._path_str(p) for p, _ in flat
                  if bm._path_str(p) not in drawn) == sorted(fused)
    for path, parts in fused.items():
        served = _leaf(params, path)
        served = served[layer] if served.ndim > 2 else served
        width = served.shape[-1]
        rows = np.concatenate([np.asarray(drawn[p]).reshape(-1, width)
                               for p in parts])
        assert np.array_equal(served.reshape(-1, width), rows), path
