"""A tiny cell for the benchmark's CPU tests: the data files under
``bench/tests/data`` in place of a configuration and a mix."""

import json
import time
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


def cell(traffic: str, per_layer=(), end_to_end=()):
    from bench import spec
    return spec.Cell(
        name=f"tiny.{traffic}", chips=1, config_name="tiny",
        config=json.loads((DATA / "tiny.json").read_text()),
        traffic_name=traffic,
        traffic=json.loads((DATA / f"{traffic}.json").read_text()),
        end_to_end=list(end_to_end), per_layer=list(per_layer))


def run(traffic: str, seed: int = 2 ** 33 + 7, seconds: float = 2.0,
        trace: bool = False, trace_dir=None):
    """One run of the tiny cell on the CPU (no look for a chip)."""
    from bench import harness
    return harness.run_cell(cell(traffic), seed, seconds, trace,
                            time.time(), require_tpu=False,
                            trace_dir=trace_dir)
