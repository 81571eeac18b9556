"""Compile the served path for a described TPU v5e, with no chip attached.

The TPU compiler is installed with JAX, and it compiles for a topology
that is described rather than attached.  What it refuses here it would
refuse on the chip: a Pallas block that breaks Mosaic's tiling rule, a
kernel that needs more VMEM than it may use, a program that does not
fit HBM.  Interpret mode checks none of that.  Nothing runs, so these
tests say nothing about results or times.

Shapes are llama2-110m at its published widths (12 layers, d_model 768,
12 heads of 64, vocabulary 32000) under the ``Engine`` defaults: 8 slots,
``max_seq`` 1024, page size 64, chunks of 512 tokens, Q8_0 weights.  The
decode step is compiled again at SmolLM2-1.7B's widths and serving
geometry, the benchmark's cell (24 layers, d_model 2048, 32 heads and 32
KV heads of 64, d_ff 8192, vocabulary 49152; 8 slots of 128 pages of 64,
368 pages).

Only one process may load libtpu at a time, so the topology is described
inside a module fixture (never at import), and every compile happens in
this process.  The persistent compilation cache is off around them: an
entry compiled for a described chip cannot be read back without one.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

SLOTS, CHUNK, PAGE, MAX_SEQ = 8, 512, 64, 1024
MAX_BLOCKS = MAX_SEQ // PAGE
N_PAGES = SLOTS * MAX_BLOCKS


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _placed(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _served(kv):
    """(cfg, model, served param shapes, pool shapes) — abstract only."""
    from repro.configs import get_config
    from repro.core.policy import QuantPolicy
    from repro.models import build_model
    cfg = get_config("llama2-110m")
    if kv:
        cfg = cfg.with_(kv_cache_dtype=kv)
    m = build_model(cfg)
    params = jax.eval_shape(
        lambda k: m.quantize(m.init(k), QuantPolicy(bits=8, min_size=512)),
        jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: m.init_paged_cache(
        SLOTS, block_size=PAGE, n_blocks=N_PAGES,
        max_blocks_per_seq=MAX_BLOCKS))
    return cfg, m, params, cache


def _chunk_args(sharding):
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=sharding)
    return (i32(SLOTS, CHUNK), i32(SLOTS, CHUNK), i32(SLOTS, CHUNK),
            i32(SLOTS, MAX_BLOCKS), i32(SLOTS), i32(SLOTS), i32(SLOTS))


@pytest.mark.parametrize("kv", [None, "int8"])
def test_prefill_kernel_compiles_at_served_widths(one_chip, kv):
    """The fused paged prefill kernel over an f32 or an int8 pool."""
    from repro.kernels import ops
    h, d = 12, 64
    pool_dt = jnp.int8 if kv else jnp.float32
    args = [jax.ShapeDtypeStruct((SLOTS, CHUNK, h, d), jnp.float32),
            jax.ShapeDtypeStruct((N_PAGES, PAGE, h, d), pool_dt),
            jax.ShapeDtypeStruct((N_PAGES, PAGE, h, d), pool_dt),
            jax.ShapeDtypeStruct((SLOTS, MAX_BLOCKS), jnp.int32),
            jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
            jax.ShapeDtypeStruct((SLOTS,), jnp.int32)]
    if kv:
        args += [jax.ShapeDtypeStruct((N_PAGES, PAGE, h), jnp.float32)] * 2
    compiled = jax.jit(ops.paged_prefill_attention).lower(
        *_placed(args, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv", [None, "int8"])
def test_served_chunk_step_compiles_with_kernel(one_chip, kv):
    """The Engine's jitted chunked-prefill step, as it runs on a TPU:
    the prefix read is the Pallas kernel, not the jnp gather."""
    from repro.models import transformer
    cfg, _, params, cache = _served(kv)
    step = transformer._prefill_chunk_fn(cfg, "kernel")
    compiled = step.lower(_placed(params, one_chip),
                          _placed(cache, one_chip),
                          *_chunk_args(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_served_decode_step_compiles(one_chip, monkeypatch):
    """The Engine's jitted decode step over the paged pool, as it runs on
    a TPU: its attention is the Pallas paged decode kernel."""
    monkeypatch.setenv("REPRO_FUSED_PREFILL", "kernel")
    _, m, params, cache = _served(None)
    tok = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(m.decode_step, donate_argnums=(1,)).lower(
        _placed(params, one_chip), _placed(cache, one_chip), tok).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _smollm2(kv):
    """SmolLM2-1.7B's decode step in the benchmark's geometry:
    (step(mode, mesh), served param shapes, pool shapes)."""
    from repro.configs.base import ModelConfig
    from repro.core.policy import QuantPolicy
    from repro.models import build_model, transformer
    cfg = ModelConfig(
        arch_id="smollm2-1.7b", family="dense", n_layers=24, d_model=2048,
        n_heads=32, n_kv_heads=32, d_ff=8192, vocab_size=49152,
        rope_theta=130000, eps=1e-5, tie_embeddings=True,
        kv_cache_dtype=kv)
    m = build_model(cfg)
    params = jax.eval_shape(
        lambda k: m.quantize(m.init(k), QuantPolicy(bits=8, min_size=512)),
        jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: m.init_paged_cache(
        8, block_size=64, n_blocks=368, max_blocks_per_seq=128))

    def step(mode, mesh=None):
        def decode_step(params, cache, tokens):
            return transformer._decode_step_paged(params, cfg, cache, tokens,
                                                  None, mesh=mesh, mode=mode)
        return jax.jit(decode_step, donate_argnums=(1,))
    return cfg, step, params, cache


@pytest.mark.parametrize("kv", ["bfloat16", "int8"])
def test_smollm2_decode_step_kernel_drops_the_gather(one_chip, kv):
    """The benchmark's decode step with the kernel compiles, and its
    temporaries shrink against the jnp gather's by at least one layer's
    gathered f32 K+V span (8 rows x 8192 positions x 32 heads x 64 x 4 B x
    2): the kernel reads pages in place and widens them in VMEM."""
    _, step, params, cache = _smollm2(kv)
    args = (_placed(params, one_chip), _placed(cache, one_chip),
            jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip))
    kernel = step("kernel").lower(*args).compile()
    assert "tpu_custom_call" in kernel.as_text()
    oracle = step("oracle").lower(*args).compile()
    assert "tpu_custom_call" not in oracle.as_text()
    span = 8 * 8192 * 32 * 64 * 4 * 2
    assert (kernel.memory_analysis().temp_size_in_bytes
            <= oracle.memory_analysis().temp_size_in_bytes - span)


def test_sharded_chunk_step_compiles_on_four_chips(topo):
    """The chunk step under a 4-chip serving mesh: the KV pool sharded
    on its KV-heads axis, gathered whole before the Pallas call, which
    then runs replicated under the partitioner."""
    import numpy as np
    from jax.sharding import AxisType, Mesh

    from repro.distribution import sharding as shardlib
    from repro.models import transformer
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(1, 4),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    rep = NamedSharding(mesh, PartitionSpec())
    cfg, _, params, cache = _served(None)
    pspecs = shardlib.param_specs(cfg, params, mesh, mode="serve")
    cspecs = shardlib.cache_specs(cfg, cache, mesh)
    step = transformer._prefill_chunk_fn(cfg, "kernel", mesh=mesh)
    compiled = step.lower(
        jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            params, shardlib.to_shardings(pspecs, mesh)),
        jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            cache, shardlib.to_shardings(cspecs, mesh)),
        *_chunk_args(rep)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_decode_step_compiles_on_four_chips(topo):
    """The benchmark's decode step under a 4-chip serving mesh: the pool
    sharded on its KV-heads axis and the kernel under ``shard_map`` with
    that spec, so each chip reads only its 8 heads' pages."""
    import numpy as np
    from jax.sharding import AxisType, Mesh

    from repro.distribution import sharding as shardlib
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(1, 4),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    rep = NamedSharding(mesh, PartitionSpec())
    cfg, step, params, cache = _smollm2("bfloat16")
    pspecs = shardlib.param_specs(cfg, params, mesh, mode="serve")
    cspecs = shardlib.cache_specs(cfg, cache, mesh)
    assert cspecs["attn"]["k"] == PartitionSpec(None, None, None, "model")
    compiled = step("kernel", mesh).lower(
        jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            params, shardlib.to_shardings(pspecs, mesh)),
        jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            cache, shardlib.to_shardings(cspecs, mesh)),
        jax.ShapeDtypeStruct((8,), jnp.int32, sharding=rep)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel reads its shard, 8 of the 32 KV heads of each page, and
    # no chip gathers the whole pool
    assert "bf16[368,64,8,64]" in text
    assert not any("all-gather" in line and "[368,64,32,64]" in line
                   for line in text.splitlines())
