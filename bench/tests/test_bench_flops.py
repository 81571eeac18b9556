"""The benchmark's FLOP functions, over each architecture's token work,
against the program's jaxpr FLOP walker (``launch/flops.py``) on reduced
configurations, and the prefix read's operations and bytes from shapes."""

import jax
import jax.numpy as jnp
import pytest

from bench import flops
from bench import model as bm
from bench import spec
from bench_tiny import reduced_conf
from repro.configs.base import reduced
from repro.launch.flops import count_flops
from repro.models import build_model

CONFIGS = sorted(p.name for p in (spec.ROOT / "bench" / "configs").glob("*.json"))


def _model(name):
    """The program's reduced model and the same sizes' token work."""
    conf = bm.load_config(spec.ROOT / "bench" / "configs" / name)
    cfg = reduced(bm.model_config(conf)).with_(compute_dtype="float32")
    small = reduced_conf(conf)
    return build_model(cfg), spec.arch(small).token_work(small)


@pytest.mark.parametrize("name", CONFIGS)
def test_prefill_flops_match_the_walker(name):
    """One-shot prefill of T tokens computes every (query, key) pair of
    the square and the logits of the last position."""
    model, work = _model(name)
    t = 64
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    walker = count_flops(
        lambda p, x: model.prefill(p, {"tokens": x})[0], params,
        jax.ShapeDtypeStruct((1, t), jnp.int32))
    assert walker == flops.forward_flops(work, t, lambda window: t * t, 1)


@pytest.mark.parametrize("name", CONFIGS)
def test_decode_flops_match_the_walker(name):
    """A dense-cache decode step attends the cache's whole span S."""
    model, work = _model(name)
    b, s = 4, 96
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(b, s))
    walker = count_flops(lambda p, c, x: model.decode_step(p, c, x)[0],
                         params, cache, jax.ShapeDtypeStruct((b,), jnp.int32))
    assert walker == flops.decode_flops(work, [s] * b)


def test_chunk_and_prefix_read_counts():
    model, work = _model(CONFIGS[0])
    cfg = model.cfg
    # a 3-token row at position 5 sees 6 + 7 + 8 keys
    assert flops.chunk_flops(work, [(5, 3)]) == flops.forward_flops(
        work, 3, lambda window: 21, 1)
    f, b = flops.prefix_attn_cost(work, [(0, 8), (128, 16)], kv_bytes=2)
    h, kvh, hd, layers = cfg.n_heads, cfg.n_kv_heads, cfg.hd(), cfg.n_layers
    assert f == layers * 4 * h * hd * 16 * 128
    assert b == layers * (2 * 128 * kvh * hd * 2 + 16 * h * hd * 2
                          + 16 * h * (hd + 2) * 4)


def test_peaks_table():
    assert flops.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("cpu")


def test_windowed_attention_sees_at_most_its_window():
    """A layer with a window of 4 keys: a query at position p sees
    min(p + 1, 4) keys, and a chunk reads at most 3 cached ones."""
    attn = {"layers": 2, "heads": 4, "kv_heads": 2, "head_dim": 8}
    full = {"params": 1000, "head": 500,
            "attention": [dict(attn, window=None)]}
    windowed = dict(full, attention=[dict(attn, window=4)])
    per_pair = 4.0 * 2 * 4 * 8
    # positions 5, 6, 7 see 4 keys each, not 6 + 7 + 8
    assert (flops.chunk_flops(full, [(5, 3)])
            - flops.chunk_flops(windowed, [(5, 3)])) == per_pair * (21 - 12)
    assert (flops.decode_flops(full, [2, 10])
            - flops.decode_flops(windowed, [2, 10])) == per_pair * (12 - 6)
    f_full, b_full = flops.prefix_attn_cost(full, [(10, 2)], kv_bytes=2)
    f_win, b_win = flops.prefix_attn_cost(windowed, [(10, 2)], kv_bytes=2)
    # the chunk's two queries see 3 and 2 of the 10 cached keys
    assert f_full == 2 * 4 * 4 * 8 * 2 * 10
    assert f_win == 2 * 4 * 4 * 8 * (3 + 2)
    assert b_full - b_win == 2 * 2 * (10 - 3) * 2 * 8 * 2
