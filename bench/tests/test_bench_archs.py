"""The seam between the benchmark and an architecture: a configuration's
``model_type`` names ``bench/archs/<model_type>.py``.  The Llama module
gives the numbers that the Llama code gave before it moved there
(``data/tiny_pins.json``), an unknown architecture is refused by the path
it lacks, and a new architecture is one new module."""

import hashlib
import json
import textwrap

import numpy as np
import pytest

from bench import flops, reference, spec, weights
from bench import model as bm
from bench_tiny import DATA

PINS = json.loads((DATA / "tiny_pins.json").read_text())
CONF = bm.load_config(DATA / "tiny.json")


def test_planned_arrays_are_pinned():
    key = weights.seed_key(PINS["seed"])
    plan = weights.plan(CONF)
    assert sorted(f"{e[0]}:{e[1]}" for e in plan) == sorted(PINS["arrays"])
    for entry in plan:
        pin = PINS["arrays"][f"{entry[0]}:{entry[1]}"]
        a = np.asarray(weights.draw(key, entry))
        assert [list(a.shape), str(a.dtype)] == [pin["shape"], pin["dtype"]]
        if a.dtype == np.int8:
            assert hashlib.sha256(a.tobytes()).hexdigest() == pin["sha256"]
        else:
            a = a.astype(np.float64)
            assert a.sum() == pytest.approx(pin["sum"], rel=1e-6)
            assert (a * a).sum() == pytest.approx(pin["sumsq"], rel=1e-6)


def test_reference_logits_are_pinned():
    seq, pin = PINS["sequence"], PINS["logits"]
    rows = reference.logits_before(
        CONF, PINS["seed"], [(np.asarray(seq["prompt"], np.int32),
                              seq["served"])])[0]
    assert rows.argmax(axis=1).tolist() == pin["argmax"]
    scale = float(np.abs(rows).max())
    np.testing.assert_allclose(rows.max(axis=1), pin["max"], rtol=1e-5)
    np.testing.assert_allclose(np.linalg.norm(rows.astype(np.float64),
                                              axis=1), pin["norm"], rtol=1e-5)
    np.testing.assert_allclose(rows[:, pin["cols"]], pin["at_cols"],
                               rtol=1e-5, atol=1e-5 * scale)


def test_flops_and_bytes_are_pinned():
    work = spec.arch(CONF).token_work(CONF)
    pin = PINS["flops"]
    assert flops.forward_flops(work, 37, lambda window: 1000, 3) \
        == pin["forward_flops"]
    assert flops.chunk_flops(work, [(0, 32), (5, 3), (100, 17)]) \
        == pin["chunk_flops"]
    assert flops.decode_flops(work, [1, 17, 300]) == pin["decode_flops"]
    assert list(flops.prefix_attn_cost(
        work, [(0, 8), (128, 16), (40, 32)], kv_bytes=2)) \
        == pin["prefix_attn_cost"]


def test_smollm2_plan_and_flops_are_pinned():
    """The published SmolLM2-1.7B configuration: the same leaves, shapes
    and initial standard deviations, and the same FLOPs and bytes."""
    conf = bm.load_config(spec.ROOT / "bench" / "configs"
                          / "smollm2-1.7b.json")
    pin = PINS["smollm2"]
    plan = [[path, kind, list(shape), stacked,
             None if kind == "float" else weights._weight_std(fan_in)]
            for path, kind, shape, stacked, fan_in in weights.plan(conf)]
    assert plan == pin["plan"]
    work = spec.arch(conf).token_work(conf)
    rows = [(0, 64), (1500, 64), (6000, 13)]
    assert flops.forward_flops(work, 37, lambda window: 1000, 3) \
        == pin["forward_flops"]
    assert flops.chunk_flops(work, rows) == pin["chunk_flops"]
    assert flops.decode_flops(work, [1, 2049, 6144, 8192]) \
        == pin["decode_flops"]
    assert list(flops.prefix_attn_cost(work, rows, kv_bytes=2)) \
        == pin["prefix_attn_cost"]


def test_unknown_model_type_names_the_missing_module():
    conf = dict(CONF, model_type="no_such_arch")
    for call in (lambda: weights.plan(conf), lambda: bm.model_config(conf),
                 lambda: reference.logits_before(conf, 1, [([1, 2], [3])])):
        with pytest.raises(FileNotFoundError,
                           match=r"bench/archs/no_such_arch\.py"):
            call()


# A toy architecture, not one of the benchmark's: RMSNorm and a GELU MLP
# in each layer, no attention, so each position's logits depend on its
# own token alone.
TOY = '''
import jax
import jax.numpy as jnp

from bench.reference import mm, rms
from bench.weights import Leaf


def dims(conf):
    return {"layers": conf["layers"], "d": conf["width"],
            "ff": conf["inner"], "vocab": conf["vocab"]}


def leaf_shapes(conf):
    m = dims(conf)
    n, d, ff = m["layers"], m["d"], m["ff"]
    return {"table": Leaf((m["vocab"], d), True, None, False),
            "out/gain": Leaf((d,), False, None, False),
            "layers/gain": Leaf((n, d), False, None, True),
            "layers/up": Leaf((n, ff, d), True, d, True),
            "layers/down": Leaf((n, d, ff), True, ff, True)}


def token_work(conf):
    m = dims(conf)
    return {"params": 2 * m["layers"] * m["d"] * m["ff"],
            "head": m["vocab"] * m["d"], "attention": []}


def embed(top, tokens, m):
    return jnp.take(top["table"], tokens, axis=0)


def layer(x, w, pos, m, control):
    h = mm("btd,fd->btf", rms(x, w["layers/gain"], 1e-6), w["layers/up"],
           control)
    return x + mm("btf,df->btd", jax.nn.gelu(h), w["layers/down"], control)


def final_norm(x, top, m):
    return rms(x, top["out/gain"], 1e-6)


def lm_head(rows, top, m, control):
    return mm("td,vd->tv", rows, top["table"], control)
'''


def _gelu(x):
    import math
    return 0.5 * x * (1 + np.tanh(math.sqrt(2 / math.pi)
                                  * (x + 0.044715 * x ** 3)))


def _rms(x, g):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + 1e-6) * g


def test_a_new_architecture_is_one_new_module(tmp_path):
    """Written into a root of its own, with no file under ``bench/``
    touched, the toy module is planned, drawn and run by the reference,
    whose logits are the toy's equations over the drawn weights."""
    archs = tmp_path / "bench" / "archs"
    archs.mkdir(parents=True)
    (archs / "toy.py").write_text(textwrap.dedent(TOY))
    assert not spec.arch_path("toy").exists()
    conf = {"model_type": "toy", "layers": 3, "width": 64, "inner": 192,
            "vocab": 300}
    plan = weights.plan(conf, tmp_path)
    assert {(e[0], e[1]) for e in plan} == {
        ("table", "q"), ("table", "scale"), ("out/gain", "float"),
        ("layers/gain", "float"), ("layers/up", "q"), ("layers/up", "scale"),
        ("layers/down", "q"), ("layers/down", "scale")}
    seed = 2 ** 36 + 11
    key = weights.seed_key(seed)
    up = next(e for e in plan if e[:2] == ("layers/up", "q"))
    assert np.asarray(weights.draw(key, up)).shape == (3, 192, 64)
    prompt, served = np.asarray([5, 17, 299, 42], np.int32), [7, 0, 123]
    got = reference.logits_before(conf, seed, [(prompt, served)],
                                  root=tmp_path)[0]
    top = {k: np.asarray(v, np.float64)
           for k, v in weights.layer_weights(plan, key).items()}
    x = top["table"][np.concatenate([prompt, served[:-1]])[-len(served):]]
    for i in range(3):
        w = {k: np.asarray(v, np.float64)
             for k, v in weights.layer_weights(plan, key, i).items()}
        h = _rms(x, w["layers/gain"]) @ w["layers/up"].T
        x = x + _gelu(h) @ w["layers/down"].T
    want = _rms(x, top["out/gain"]) @ top["table"].T
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())
    # no attention: a token's work is its weights and the head
    work = spec.arch(conf, tmp_path).token_work(conf)
    assert flops.decode_flops(work, [5, 900]) == (
        2.0 * 2 * 3 * 64 * 192 * 2 + 2.0 * 300 * 64 * 2)
