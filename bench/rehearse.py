#!/usr/bin/env python3
"""Compile a configuration's served steps for a described TPU v5e.

Run by hand, with no chip attached, before a chip call:

    JAX_PLATFORMS=cpu python3 bench/rehearse.py bench/configs/smollm2-1.7b.json

It compiles the engine's chunk step (with the Pallas prefix kernel) and
its decode step at the configuration's published widths and serving
geometry, for one chip of a described ``v5e:2x2``, and prints each
program's ``memory_analysis`` and the bytes the KV pool allocates, logical
and as laid out in HBM.  Nothing runs, so it says nothing about results
or times.  ``--n-pages`` overrides the pool size in the file.
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _placed(tree, sharding):
    import jax
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _mem(tag, compiled) -> None:
    m = compiled.memory_analysis()
    print(f"{tag}: arguments {m.argument_size_in_bytes} B, outputs "
          f"{m.output_size_in_bytes} B, aliased {m.alias_size_in_bytes} B, "
          f"temporaries {m.temp_size_in_bytes} B, generated code "
          f"{m.generated_code_size_in_bytes} B")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--n-pages", type=int, default=0)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import model as bm
    from repro.models import build_model, transformer

    jax.config.update("jax_enable_compilation_cache", False)
    conf = bm.load_config(args.config)
    srv = conf["serving"]
    cfg = bm.model_config(conf)
    model = build_model(cfg)
    slots, chunk, page = srv["slots"], srv["prefill_chunk_tokens"], \
        srv["page_size"]
    mb = -(-srv["max_seq"] // page)
    n_pages = args.n_pages or srv["n_pages"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    params = _placed(jax.eval_shape(lambda: bm.build_weights(model, conf, 0)),
                     one)
    cache = _placed(jax.eval_shape(lambda: model.init_paged_cache(
        slots, block_size=page, n_blocks=n_pages, max_blocks_per_seq=mb)),
        one)
    weight_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                       for a in jax.tree_util.tree_leaves(params))
    print(f"config {args.config}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.n_heads} heads / {cfg.n_kv_heads} KV heads "
          f"of {cfg.hd()}, d_ff {cfg.d_ff}, vocabulary {cfg.vocab_size}")
    print(f"geometry: {slots} slots, max_seq {srv['max_seq']}, pages of "
          f"{page}, {n_pages} pages ({n_pages * page} tokens), chunks of "
          f"{chunk}")
    print(f"served weights (fused copies included): {weight_bytes} B")

    pool = cache["attn"]["k"]
    logical = 2 * int(np.prod(pool.shape)) * pool.dtype.itemsize
    zeros = jax.jit(lambda: jnp.zeros(pool.shape, pool.dtype),
                    out_shardings=one).lower().compile()
    alloc = 2 * zeros.memory_analysis().output_size_in_bytes
    print(f"KV pool (k and v): logical {logical} B, allocated {alloc} B "
          f"({alloc / logical:.2f}x), {alloc // (n_pages * page)} B per "
          f"token allocated")

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    step = transformer._prefill_chunk_fn(cfg, "kernel")
    compiled = step.lower(params, cache, i32(slots, chunk), i32(slots, chunk),
                          i32(slots, chunk), i32(slots, mb), i32(slots),
                          i32(slots), i32(slots)).compile()
    if "tpu_custom_call" not in compiled.as_text():
        sys.exit("the chunk step compiled without the Pallas kernel")
    _mem("chunk step", compiled)
    decode = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        params, cache, i32(slots)).compile()
    _mem("decode step", decode)


if __name__ == "__main__":
    main()
