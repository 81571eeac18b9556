"""A tiny cell for the benchmark's CPU tests: the data files under
``bench/tests/data`` in place of a configuration and a mix."""

import contextlib
import itertools
import json
import time
import types
from pathlib import Path

DATA = Path(__file__).resolve().parent / "data"


def reduced_conf(conf):
    """A configuration file cut to the program's ``reduced()`` sizes, for
    the CPU: two layers of width 128, heads of 32, vocabulary 512."""
    heads, kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    return dict(conf, num_hidden_layers=2, hidden_size=128,
                num_attention_heads=4,
                num_key_value_heads=min(kv, 2) if kv < heads else 4,
                head_dim=32, intermediate_size=256, vocab_size=512)


def cell(traffic: str, per_layer=(), end_to_end=(), config=None):
    """A cell of the tiny configuration (or ``config``) and the mix
    ``traffic``, from ``bench/tests/data`` or else ``bench/traffic``."""
    from bench import spec
    path = DATA / f"{traffic}.json"
    if not path.exists():
        path = spec.traffic_path(traffic)
    return spec.Cell(
        name=f"tiny.{traffic}", chips=1, config_name="tiny",
        config=config or json.loads((DATA / "tiny.json").read_text()),
        traffic_name=traffic, traffic=json.loads(path.read_text()),
        end_to_end=list(end_to_end), per_layer=list(per_layer))


def run(traffic: str, seed: int = 2 ** 33 + 7, seconds: float = 2.0,
        trace: bool = False, trace_dir=None, config=None, tick=None):
    """One run of the tiny cell on the CPU (no look for a chip).  With
    ``tick``, the harness's clock moves ``tick`` seconds each time it is
    read, so that the window holds the same steps on any machine."""
    from unittest import mock

    from bench import harness
    clock = mock.patch.object(harness, "time", types.SimpleNamespace(
        time=time.time, perf_counter=itertools.count(0.0, tick).__next__,
        sleep=time.sleep)) if tick else contextlib.nullcontext()
    with clock:
        return harness.run_cell(cell(traffic, config=config), seed, seconds,
                                trace, time.time(), require_tpu=False,
                                trace_dir=trace_dir)


def _stale(decode, slots):
    """The decode step returns its KV pool unchanged."""
    import jax
    import jax.numpy as jnp

    def stale(params, cache, tokens):
        kept = jax.tree_util.tree_map(jnp.copy, cache)
        logits, new = decode(params, cache, tokens)
        kept["lens"] = new["lens"]
        return logits, kept
    return stale


def _half(decode, slots):
    """The decode step computes only the first half of its rows; the
    others take the first row's logits."""
    import jax.numpy as jnp

    def half(params, cache, tokens):
        logits, new = decode(params, cache, tokens)
        keep = jnp.arange(slots)[:, None] < slots // 2
        return jnp.where(keep, logits, logits[:1]), new
    return half


FAULTS = {"stale_state": _stale, "half_batch": _half, "altered_token": None}


def broken_run(monkeypatch, fault: str, traffic="tiny_sessions_closed",
               seed=77, seconds=3.0, config=None, tick=None):
    """One run with the timed path broken by ``fault`` (a key of
    :data:`FAULTS`; ``altered_token`` alters the first row's sampled
    token where it is made); returns the record and whether it came out
    correct."""
    import jax.numpy as jnp

    from bench.run import is_correct
    from repro.serving import engine as eng_mod
    make_decode = FAULTS[fault]
    real_init = eng_mod.Engine.__init__

    def init(self, *a, **kw):
        real_init(self, *a, **kw)
        if make_decode is not None:
            self._decode = make_decode(self._decode, self.max_slots)

    monkeypatch.setattr(eng_mod.Engine, "__init__", init)
    if fault == "altered_token":
        real_sample = eng_mod.sample_logits_per_row

        def sample(*a, **kw):
            tok = real_sample(*a, **kw)
            return jnp.where(jnp.arange(tok.shape[0]) == 0,
                             (tok + 1) % 512, tok)
        monkeypatch.setattr(eng_mod, "sample_logits_per_row", sample)
    rec = run(traffic, seed=seed, seconds=seconds, config=config, tick=tick)
    return rec, is_correct(rec)
