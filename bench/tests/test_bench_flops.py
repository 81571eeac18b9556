"""The benchmark's FLOP functions against the program's jaxpr FLOP walker
(``launch/flops.py``) on reduced configurations, and the prefix read's
operations and bytes from shapes."""

import jax
import jax.numpy as jnp
import pytest

from bench import flops
from bench import model as bm
from bench import spec
from repro.configs.base import reduced
from repro.launch.flops import count_flops
from repro.models import build_model

CONFIGS = sorted(p.name for p in (spec.ROOT / "bench" / "configs").glob("*.json"))


def _model(name):
    conf = bm.load_config(spec.ROOT / "bench" / "configs" / name)
    cfg = reduced(bm.model_config(conf)).with_(compute_dtype="float32")
    return build_model(cfg)


@pytest.mark.parametrize("name", CONFIGS)
def test_prefill_flops_match_the_walker(name):
    """One-shot prefill of T tokens computes every (query, key) pair of
    the square and the logits of the last position."""
    model = _model(name)
    cfg, t = model.cfg, 64
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    walker = count_flops(
        lambda p, x: model.prefill(p, {"tokens": x})[0], params,
        jax.ShapeDtypeStruct((1, t), jnp.int32))
    assert walker == flops.forward_flops(cfg, t, t * t, 1)


@pytest.mark.parametrize("name", CONFIGS)
def test_decode_flops_match_the_walker(name):
    """A dense-cache decode step attends the cache's whole span S."""
    model = _model(name)
    cfg, b, s = model.cfg, 4, 96
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(b, s))
    walker = count_flops(lambda p, c, x: model.decode_step(p, c, x)[0],
                         params, cache, jax.ShapeDtypeStruct((b,), jnp.int32))
    assert walker == flops.decode_flops(cfg, [s] * b)


def test_chunk_and_prefix_read_counts():
    cfg = _model(CONFIGS[0]).cfg
    # a 3-token row at position 5 sees 6 + 7 + 8 keys
    assert flops.chunk_flops(cfg, [(5, 3)]) == flops.forward_flops(
        cfg, 3, 21, 1)
    f, b = flops.prefix_attn_cost(cfg, [(0, 8), (128, 16)], kv_bytes=2)
    h, kvh, hd, layers = cfg.n_heads, cfg.n_kv_heads, cfg.hd(), cfg.n_layers
    assert f == layers * 4 * h * hd * 16 * 128
    assert b == layers * (2 * 128 * kvh * hd * 2 + 16 * h * hd * 2
                          + 16 * h * (hd + 2) * 4)


def test_peaks_table():
    assert flops.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("cpu")
