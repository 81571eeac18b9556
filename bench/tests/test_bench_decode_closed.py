"""The ``decode_closed`` mix on Yi-1.5-6B cut to the tiny sizes on the
CPU, with grouped queries (4 heads over 2 KV heads): 16 clients on 16
slots, no prefix shared.  A sound run is correct, the float8 control
fails its limit, and a run whose timed path is broken is not correct.

The harness's clock ticks per read (``bench_tiny.run``), so that each run
holds the same 99 decode steps however busy the machine is."""

import pytest

import bench_tiny
from bench import harness, reference, spec
from bench import model as bm
from bench.run import is_correct

CONF = bench_tiny.reduced_conf(
    bm.load_config(spec.ROOT / "bench" / "configs" / "yi-1.5-6b.json"))
RUN = {"traffic": "decode_closed", "seed": 2 ** 34 + 21, "seconds": 5.0,
       "config": CONF, "tick": 0.025}


@pytest.fixture(scope="module")
def clean():
    """One sound run, with the requests its check compared."""
    seen = {}
    real = harness.check

    def spy(conf, seed, seqs):
        seen.update(seqs=seqs)
        return real(conf, seed, seqs)

    harness.check = spy
    try:
        rec = bench_tiny.run(**RUN)
    finally:
        harness.check = real
    return rec, seen["seqs"]


def test_decode_closed_run_is_correct(clean):
    rec, _ = clean
    assert CONF["num_attention_heads"] == 2 * CONF["num_key_value_heads"]
    assert rec["failed"] == 0 and rec["compiles_in_window"] == 0
    assert rec["counters"]["prefix_hits"] == 0
    assert rec["counters"]["decode_steps"] > 0
    assert rec["check"]["requests"] >= 1 and is_correct(rec)


def test_float8_control_fails_the_limit_on_decode_closed(clean):
    rec, seqs = clean
    ref = reference.logits_before(CONF, rec["seed"], seqs)
    ctl = reference.logits_before(CONF, rec["seed"], seqs, control=True)
    assert reference.control_gap(ref, ctl) > rec["check"]["limit"]


@pytest.mark.parametrize("fault", sorted(bench_tiny.FAULTS))
def test_broken_decode_closed_run_is_not_correct(monkeypatch, fault):
    rec, ok = bench_tiny.broken_run(monkeypatch, fault, **RUN)
    assert not ok and rec["check"]["logit_gap"] > rec["check"]["limit"]
