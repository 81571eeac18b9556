"""Tensor-parallel sharded serving: the multi-device bit-exactness tier.

The sharded engine's contract is *bitwise stream identity*: an
``Engine(mesh=...)`` over any mesh size must emit exactly the token
streams of the unsharded engine, greedy and sampled, f32 and int8 KV,
through every serving feature (prefix-cache warm hits, fork/COW parallel
sampling, preemption-resume).  The scheme that makes this possible is
storage-sharded / compute-replicated (see
``transformer._serve_mesh_helpers``): the paged pool shards its KV-heads
dim, weights are stored sharded but gathered whole at use, and the only
collectives are all-gathers — pure data movement, never arithmetic — so
no floating-point reduction is ever reassociated across devices.

Mesh sizes above the local device count self-skip; the CI multi-device
lane re-runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the
launch/dryrun.py idiom) where sizes 1/2/4 all execute for real.

The ``sanitize`` / ``paged_cache_specs`` unit tests at the bottom pin
the degrade-never-raise contract: paged-pool dims that don't divide the
model axis (odd KV-head counts, tiny block sizes) fall back to
replication mid-admission instead of raising.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, reduced
from repro.distribution import sharding as sh
from repro.launch.mesh import make_serve_mesh
from repro.models import build_model
from repro.serving.engine import Engine

MESH_SIZES = (1, 2, 4)
PROMPT_SIZES = (5, 9, 17, 12)


def _mesh_or_skip(n):
    if jax.device_count() < n:
        pytest.skip(f"needs {n} devices (CI multi-device lane)")
    return make_serve_mesh(n)


@pytest.fixture(scope="module", params=["f32", "int8"])
def model_params(request):
    """f32: float params + float KV pool.  int8: quantized params with
    fused decode weights + int8 KV pool with f32 scale pools — the
    layout where the pool's per-(position, kv-head) scale buffers shard
    alongside the codes."""
    cfg = reduced(get_config("llama2-110m")).with_(compute_dtype="float32")
    if request.param == "int8":
        cfg = cfg.with_(kv_cache_dtype="int8")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    if request.param == "int8":
        params = m.quantize(params)
    return m, params


def _prompts(seed=0, sizes=PROMPT_SIZES):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 300, size=n).astype(np.int32) for n in sizes]


def _serve(model, params, mesh, *, greedy=True, n_samples=1, n_pages=48,
           max_new=8, prompts=None, repeats=1):
    eng = Engine(model, params, max_slots=4, max_seq=64, page_size=4,
                 n_pages=n_pages, prefill_chunk_tokens=8, mesh=mesh)
    uids, done = [], {}
    for rep in range(repeats):
        for i, pr in enumerate(prompts or _prompts()):
            uids.append(eng.submit(
                pr, max_new_tokens=max_new,
                temperature=0.0 if greedy else 0.9,
                top_p=1.0 if greedy else 0.95,
                seed=7 + i, n_samples=n_samples))
        # drain between repeats so warm resubmissions actually hit the
        # prefix index (registration happens at prefill completion)
        done.update({r.uid: r for r in eng.run()})
    streams = []
    for u in uids:
        r = done[u]
        assert r.error is None, r.error
        streams.append(tuple(tuple(o) for o in r.outputs))
    return streams, eng


class TestBitIdenticalStreams:
    @pytest.mark.parametrize("msize", MESH_SIZES)
    @pytest.mark.parametrize("greedy", (True, False),
                             ids=("greedy", "sampled"))
    def test_streams_match_unsharded(self, model_params, msize, greedy):
        mesh = _mesh_or_skip(msize)
        model, params = model_params
        ref, _ = _serve(model, params, None, greedy=greedy)
        got, eng = _serve(model, params, mesh, greedy=greedy)
        assert got == ref
        # zero leaks: every lease back, whole pool reclaimable
        assert all(rc == 0 for rc in eng.pager.refcount)
        assert eng.pager.n_free() == eng.pager.cfg.n_blocks
        assert eng.pager.audit().clean

    @pytest.mark.parametrize("msize", MESH_SIZES)
    def test_fused_prefill_streams_match_unsharded(self, model_params,
                                                   msize, monkeypatch):
        """The TPU attention path: the Pallas prefill kernel (interpret
        mode) runs per device under the mesh, on the gathered pool, the
        paged decode kernel on each device's own KV heads, and greedy
        streams stay those of the unsharded engine.  Chunks of 8 over
        16/12-token prompts give the prefill kernel non-empty
        prefixes."""
        mesh = _mesh_or_skip(msize)
        monkeypatch.setenv("REPRO_FUSED_PREFILL", "interpret")
        model, params = model_params
        prompts = _prompts(sizes=(16, 12))
        ref, _ = _serve(model, params, None, prompts=prompts)
        got, _ = _serve(model, params, mesh, prompts=prompts)
        assert got == ref

    @pytest.mark.parametrize("msize", MESH_SIZES)
    def test_decode_kernel_sharded_bit_identical(self, model_params,
                                                 msize):
        """The decode step's attention in the Pallas kernel (interpret
        mode) over one layer's pool: under the mesh it runs under
        ``shard_map`` with the pool's KV-heads spec, each device on its
        own heads' pages, and its output is bit for bit the unsharded
        kernel's."""
        from repro.models import transformer as T
        mesh = _mesh_or_skip(msize)
        model, _ = model_params
        cfg = model.cfg
        pool = jax.tree_util.tree_map(lambda a: a[0], model.init_paged_cache(
            4, block_size=4, n_blocks=16, max_blocks_per_seq=4)["attn"])
        key = jax.random.PRNGKey(3)
        lc = {}
        for i, (name, buf) in enumerate(sorted(pool.items())):
            x = jax.random.normal(jax.random.fold_in(key, i), buf.shape)
            lc[name] = ((x * 40).astype(buf.dtype) if buf.dtype == np.int8
                        else jnp.abs(x) * 0.02 if name in ("ks", "vs")
                        else x.astype(buf.dtype))
        pt = np.full((4, 4), -1, np.int32)
        pt[0, :3] = [5, 2, 9]
        pt[1, :2] = [0, 7]
        pt[3, :1] = [11]
        pt, lens = jnp.asarray(pt), jnp.asarray([11, 5, 0, 2], np.int32)
        q = jax.random.normal(jax.random.fold_in(key, 9),
                              (4, cfg.n_heads, cfg.hd()))

        def attend(mesh):
            return jax.jit(T._paged_decode_attend(cfg, "interpret", mesh))

        want = attend(None)(q, lc, pt, lens)
        heads = jax.sharding.NamedSharding(
            mesh, P(None, None, sh.pool_model_axis(cfg, mesh)))
        placed = jax.device_put(lc, heads)      # (NB, BS, KVH[, hd])
        got = attend(mesh)(q, placed, pt, lens)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("msize", MESH_SIZES)
    def test_prefix_cache_warm_hit_sharded(self, model_params, msize):
        """A warm resubmission of the same prompt must hit the prefix
        index under a mesh (registration hashes host-side tokens, never
        device bytes) and still stream bit-identically."""
        mesh = _mesh_or_skip(msize)
        model, params = model_params
        prompts = _prompts(sizes=(16, 12))
        ref, reng = _serve(model, params, None, prompts=prompts,
                           repeats=2)
        got, eng = _serve(model, params, mesh, prompts=prompts,
                          repeats=2)
        assert got == ref
        assert eng.metrics["prefix_hits"] > 0
        assert eng.metrics["prefix_hits"] == reng.metrics["prefix_hits"]
        assert (eng.metrics["prefix_cached_tokens"]
                == reng.metrics["prefix_cached_tokens"])

    @pytest.mark.parametrize("msize", MESH_SIZES)
    def test_fork_cow_parallel_sampling_sharded(self, model_params,
                                                msize):
        """n_samples fanout over fork/COW: the device half of COW is a
        donated copy on the *sharded* pool — sibling streams must match
        the unsharded engine's exactly."""
        mesh = _mesh_or_skip(msize)
        model, params = model_params
        prompts = _prompts(sizes=(7, 11))
        ref, reng = _serve(model, params, None, greedy=False,
                           n_samples=3, n_pages=64, prompts=prompts)
        got, eng = _serve(model, params, mesh, greedy=False,
                          n_samples=3, n_pages=64, prompts=prompts)
        assert got == ref
        assert eng.metrics["fanouts"] > 0
        assert eng.metrics["cow_copies"] == reng.metrics["cow_copies"]

    @pytest.mark.parametrize("msize", MESH_SIZES)
    def test_preemption_resume_sharded(self, model_params, msize):
        """A pool far below demand forces preemption + recompute-on-
        resume; the resumed KV is rebuilt through the sharded prefill
        path and the streams must still match unsharded serving."""
        mesh = _mesh_or_skip(msize)
        model, params = model_params
        prompts = _prompts(sizes=(9, 13, 11, 8))
        ref, reng = _serve(model, params, None, n_pages=12,
                           max_new=6, prompts=prompts)
        got, eng = _serve(model, params, mesh, n_pages=12,
                          max_new=6, prompts=prompts)
        assert got == ref
        assert eng.metrics["preemptions"] > 0, \
            "pool sizing no longer forces preemption; test is vacuous"
        assert eng.metrics["preemptions"] == reng.metrics["preemptions"]


class TestCompileBoundSharded:
    @pytest.mark.parametrize("msize", MESH_SIZES)
    def test_one_executable_per_mesh(self, model_params, msize):
        """Traffic mixing chunk lengths, offsets and decode composition
        stays at ONE chunk executable per (pool key, mesh shape).  The
        probe counts jit entries for this (cfg, mesh) pair across ALL
        pool keys served so far in the process, so the assertion is a
        delta: this pool key costs at most one entry, and a second
        engine on the same pool key costs zero."""
        mesh = _mesh_or_skip(msize)
        model, params = model_params
        probe = Engine(model, params, max_slots=4, max_seq=64,
                       page_size=4, n_pages=48, prefill_chunk_tokens=8,
                       mesh=mesh)
        c0 = probe.prefill_compile_count()
        _, eng = _serve(model, params, mesh)
        grew = eng.prefill_compile_count() - c0
        assert grew <= 1, f"{grew} chunk executables for one pool key"
        # fresh engine, same pool key, different traffic: fully warm
        _, eng2 = _serve(model, params, mesh, greedy=False,
                         prompts=_prompts(seed=5, sizes=(3, 21, 8)))
        assert eng2.prefill_compile_count() == c0 + grew, \
            "same (pool key, mesh shape) must not compile again"


class _FakeMesh:
    """Duck-typed mesh for spec-rule unit tests (axis sizes only)."""
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


class TestSanitizeDegrades:
    """sanitize / paged_cache_specs must degrade, never raise — they run
    mid-admission where an exception would fail a request."""

    MESH2 = _FakeMesh({"data": 1, "model": 2})
    MESH4 = _FakeMesh({"data": 1, "model": 4})

    def test_nondivisible_dim_degrades(self):
        assert sh.sanitize(P(None, "model"), (8, 3), self.MESH2) \
            == P(None, None)

    def test_overlong_spec_truncates(self):
        assert sh.sanitize(P("model", None, None), (8,), self.MESH2) \
            == P("model")
        assert sh.sanitize(P("model", None, None), (3,), self.MESH2) \
            == P(None)

    def test_unknown_axis_degrades(self):
        assert sh.sanitize(P("tp", None), (8, 8), self.MESH2) \
            == P(None, None)

    def test_pool_model_axis_odd_heads(self):
        cfg = get_config("llama2-110m").with_(n_heads=6, n_kv_heads=3)
        assert sh.pool_model_axis(cfg, self.MESH2) is None
        assert sh.pool_model_axis(cfg, self.MESH4) is None
        cfg4 = get_config("llama2-110m").with_(n_kv_heads=4)
        assert sh.pool_model_axis(cfg4, self.MESH2) == "model"
        assert sh.pool_model_axis(cfg4, self.MESH4) == "model"

    def test_pool_model_axis_size1_mesh_replicates(self):
        cfg = get_config("llama2-110m")
        assert sh.pool_model_axis(
            cfg, _FakeMesh({"data": 1, "model": 1})) is None

    def test_paged_pool_odd_heads_replicate(self):
        """KVH=3 on a model-2 axis: every pool buffer degrades to
        replication — including tiny block_s — without raising."""
        cfg = get_config("llama2-110m").with_(n_heads=6, n_kv_heads=3)
        i32 = jax.ShapeDtypeStruct((4, 12), np.int32)
        cache = {
            "lens": jax.ShapeDtypeStruct((4,), np.int32),
            "page_table": i32,
            "attn": {
                # tiny block_s=2, odd KVH=3
                "k": jax.ShapeDtypeStruct((2, 48, 2, 3, 32), np.float32),
                "v": jax.ShapeDtypeStruct((2, 48, 2, 3, 32), np.float32),
                "ks": jax.ShapeDtypeStruct((2, 48, 2, 3), np.float32),
                "vs": jax.ShapeDtypeStruct((2, 48, 2, 3), np.float32),
            },
        }
        specs = sh.paged_cache_specs(cfg, cache, self.MESH2)
        assert specs["attn"]["k"] == P()
        assert specs["attn"]["ks"] == P()
        assert specs["lens"] == P()
        assert specs["page_table"] == P()

    def test_paged_pool_divisible_heads_shard(self):
        """KVH=4 on model-2/model-4: the pool's KV-heads dim shards,
        scale pools follow, control state stays replicated, and specs
        are canonical (no trailing Nones — the donated-cache jit-key
        contract)."""
        cfg = get_config("llama2-110m").with_(n_kv_heads=4)
        cache = {
            "lens": jax.ShapeDtypeStruct((4,), np.int32),
            "page_table": jax.ShapeDtypeStruct((4, 12), np.int32),
            "attn": {
                "k": jax.ShapeDtypeStruct((2, 48, 4, 4, 32), np.float32),
                "v": jax.ShapeDtypeStruct((2, 48, 4, 4, 32), np.float32),
                "ks": jax.ShapeDtypeStruct((2, 48, 4, 4), np.float32),
                "vs": jax.ShapeDtypeStruct((2, 48, 4, 4), np.float32),
            },
        }
        for mesh in (self.MESH2, self.MESH4):
            specs = sh.paged_cache_specs(cfg, cache, mesh)
            assert specs["attn"]["k"] == P(None, None, None, "model")
            assert specs["attn"]["ks"] == P(None, None, None, "model")
            assert specs["lens"] == P()

    def test_cache_specs_dispatches_paged(self):
        """cache_specs routes a page_table-carrying cache to the paged
        layout (KVH axis) instead of the dense decode layout."""
        cfg = get_config("llama2-110m").with_(n_kv_heads=4)
        cache = {
            "lens": jax.ShapeDtypeStruct((4,), np.int32),
            "page_table": jax.ShapeDtypeStruct((4, 12), np.int32),
            "attn": {
                "k": jax.ShapeDtypeStruct((2, 48, 4, 4, 32), np.float32),
                "v": jax.ShapeDtypeStruct((2, 48, 4, 4, 32), np.float32),
            },
        }
        specs = sh.cache_specs(cfg, cache, self.MESH2)
        assert specs["attn"]["k"] == P(None, None, None, "model")


class TestShardedEngineGuards:
    def test_mesh_requires_paged_cache(self, model_params):
        model, params = model_params
        mesh = _mesh_or_skip(1)
        with pytest.raises(ValueError, match="paged"):
            Engine(model, params, cache_kind="dense", mesh=mesh)

    def test_serve_mesh_validates_size(self):
        with pytest.raises(ValueError):
            make_serve_mesh(0)
        with pytest.raises(ValueError):
            make_serve_mesh(jax.device_count() + 1)
