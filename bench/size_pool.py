#!/usr/bin/env python3
"""Size a configuration's KV pool from the chip's own memory readings.

Run by hand on the chip, once, when a configuration's geometry is set:

    python3 bench/size_pool.py --workload <cell> --slots 8,16

For each number of slots, in one process: the served weights and an
engine with a small pool, the compiled chunk and decode steps'
``memory_analysis`` on this chip, and ``memory_stats()`` after the
weights, after the pool and after one warmed request.  From these it
prints the largest pool that leaves ``MARGIN`` bytes free beside what
the warmed request added to the peak, and the largest beside the larger
step's compiled temporaries.  It then builds an engine with the first of
these pools and serves one request through it; where that runs out of
memory, it tries the second.  The configuration file then fixes the
number of pages, so that every run serves the same pool.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

MARGIN = 1e9          # bytes left free on the chip
SMALL_PAGES = 64      # the pool of the first engine, which reads the steps


def _stats():
    import jax
    s = jax.devices()[0].memory_stats() or {}
    return {k: int(s.get(k, 0)) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


def _engine(model, params, srv, slots, n_pages):
    from repro.serving.engine import Engine
    import jax
    eng = Engine(model, params, max_slots=slots, max_seq=srv["max_seq"],
                 eos_id=-1, page_size=srv["page_size"], n_pages=n_pages,
                 prefill_chunk_tokens=srv["prefill_chunk_tokens"])
    jax.block_until_ready(eng.cache)
    return eng


def _serve_one(eng, n_prompt, vocab):
    import numpy as np
    prompt = (np.arange(n_prompt, dtype=np.int32) * 7919) % (vocab - 1) + 1
    eng.submit(prompt, max_new_tokens=4, temperature=0.0)
    eng.run()


def _temps(model, eng, srv, slots):
    """Temporaries of the chunk and decode steps as compiled here."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer
    chunk, mb = srv["prefill_chunk_tokens"], -(-srv["max_seq"]
                                               // srv["page_size"])
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    step = transformer._prefill_chunk_fn(model.cfg, "kernel")
    c = step.lower(eng.params, eng.cache, i32(slots, chunk),
                   i32(slots, chunk), i32(slots, chunk), i32(slots, mb),
                   i32(slots), i32(slots), i32(slots)).compile()
    d = eng._decode.lower(eng.params, eng.cache, i32(slots)).compile()
    out = {}
    for name, comp in (("chunk", c), ("decode", d)):
        m = comp.memory_analysis()
        out[name] = {"arguments": int(m.argument_size_in_bytes),
                     "outputs": int(m.output_size_in_bytes),
                     "aliased": int(m.alias_size_in_bytes),
                     "temporaries": int(m.temp_size_in_bytes)}
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--slots", required=True)
    args = ap.parse_args()
    import jax

    from bench import model as bm
    from bench import spec
    from repro.launch.compile_cache import configure_compile_cache
    from repro.models import build_model
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.resolve(args.workload)
    conf, srv = cell.config, cell.config["serving"]
    model = build_model(bm.model_config(conf))
    vocab = model.cfg.vocab_size
    base = _stats()
    params = bm.build_weights(model, conf, 1)
    jax.block_until_ready(params)
    after_weights = _stats()
    weights = after_weights["bytes_in_use"] - base["bytes_in_use"]
    for slots in [int(s) for s in args.slots.split(",")]:
        small = SMALL_PAGES
        eng = _engine(model, params, srv, slots, small)
        with_pool = _stats()
        logical = sum(int(a.nbytes) for a in
                      jax.tree_util.tree_leaves(eng.cache["attn"])) / small
        page = (with_pool["bytes_in_use"]
                - after_weights["bytes_in_use"]) / small or logical
        temps = _temps(model, eng, srv, slots)
        _serve_one(eng, 3 * srv["prefill_chunk_tokens"] + 5, vocab)
        warmed = _stats()
        eng = None
        gc.collect()
        limit = warmed["bytes_limit"]
        # what one warmed request added to the peak; 0 where an earlier
        # peak (the weights' build) hides it
        seen = warmed["peak_bytes_in_use"] > with_pool["peak_bytes_in_use"]
        step = (warmed["peak_bytes_in_use"] - with_pool["bytes_in_use"]
                if seen else 0)
        temp = max(t["temporaries"] for t in temps.values())
        free = limit - with_pool["bytes_in_use"] - MARGIN
        by_stats = small + int((free - step) // page)
        by_compile = small + int((free - temp) // page)
        print(json.dumps({
            "slots": slots, "bytes_limit": limit, "weights_bytes": weights,
            "in_use_after_weights": after_weights["bytes_in_use"],
            "page_bytes_allocated": page, "page_bytes_logical": logical,
            "small_pool": {"pages": small, **with_pool},
            "after_warm_request": warmed, "step_peak_over_in_use": step,
            "step_peak_hidden": not seen,
            "compiled": temps, "pages_by_memory_stats": by_stats,
            "pages_by_compiled_temporaries": by_compile}), flush=True)
        for pages in dict.fromkeys((by_stats, by_compile)):
            if pages <= 0:
                continue
            try:
                eng = _engine(model, params, srv, slots, pages)
                _serve_one(eng, 3 * srv["prefill_chunk_tokens"] + 5, vocab)
                print(json.dumps({"slots": slots, "pages": pages,
                                  "fits": True, **_stats()}), flush=True)
                break
            except Exception as e:  # noqa: BLE001 - report what the chip says
                print(json.dumps({"slots": slots, "pages": pages,
                                  "fits": False, "error": str(e)[:400]}),
                      flush=True)
            finally:
                eng = None
                gc.collect()

if __name__ == "__main__":
    main()
