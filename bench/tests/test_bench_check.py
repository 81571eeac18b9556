"""The comparison that decides ``correct``, on the tiny cell on the CPU:
the reference agrees with the program, its float8 control fails the
limit, and a run whose timed path is broken comes out not correct."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from bench import harness, reference
from bench import model as bm
from repro.models import build_model

CONF = bm.load_config(bench_tiny.DATA / "tiny.json")


@pytest.fixture(scope="module")
def clean():
    """One sound run of the tiny sessions cell, with the requests its
    check compared."""
    seen = {}
    real = harness.check

    def spy(conf, seed, seqs):
        seen.update(seqs=seqs)
        return real(conf, seed, seqs)

    harness.check = spy
    try:
        rec = bench_tiny.run("tiny_sessions_closed", seconds=3.0)
    finally:
        harness.check = real
    return rec, seen["seqs"]


def test_reference_matches_program_logits():
    model = build_model(bm.model_config(CONF))
    seed = 2 ** 35 + 9
    params = bm.build_weights(model, CONF, seed)
    prompt = np.arange(1, 40, dtype=np.int32) * 7 % 500
    got, _ = model.prefill(params, {"tokens": jnp.asarray(prompt)[None]})
    ref = reference.logits_before(CONF, seed, [(prompt[:-1],
                                                [int(prompt[-1]), 0])])[0]
    scale = np.abs(ref[1]).max()
    assert np.abs(np.asarray(got[0]) - ref[1]).max() <= 2e-2 * scale


def test_sound_run_is_correct(clean):
    rec, seqs = clean
    chk = rec["check"]
    assert rec["failed"] == 0 and chk["requests"] >= 3
    assert chk["served_tokens"] >= 40
    assert chk["logit_gap"] <= chk["limit"]


def test_setup_phases_are_recorded_in_order(clean):
    rec, _ = clean
    names = [n for n, _ in rec["setup_phases"]]
    assert names == ["start", "model", "weights", "engine", "row_slices",
                     "setup_turns", "first_turns"]
    times = [t for _, t in rec["setup_phases"]]
    assert times == sorted(times) and times[-1] <= rec["setup_s"]


def test_float8_control_fails_the_limit(clean):
    rec, seqs = clean
    ref = reference.logits_before(CONF, rec["seed"], seqs)
    ctl = reference.logits_before(CONF, rec["seed"], seqs, control=True)
    assert reference.control_gap(ref, ctl) > rec["check"]["limit"]


def test_state_left_unchanged_is_not_correct(monkeypatch):
    """The decode step returns its KV pool unchanged."""
    rec, ok = bench_tiny.broken_run(monkeypatch, "stale_state")
    assert not ok and rec["check"]["logit_gap"] > rec["check"]["limit"]


def test_half_the_batch_left_out_is_not_correct(monkeypatch):
    """The decode step computes only the first half of its rows; the
    others take the first row's logits."""
    rec, ok = bench_tiny.broken_run(monkeypatch, "half_batch")
    assert not ok


def test_altered_token_is_not_correct(monkeypatch):
    """Sampling alters the token of the first row where it is made."""
    rec, ok = bench_tiny.broken_run(monkeypatch, "altered_token")
    assert not ok and rec["check"]["logit_gap"] > rec["check"]["limit"]


def test_closed_sessions_run_is_correct():
    """Chat sessions in a closed loop: more sessions than slots, turns
    admitted from the prefix cache, and a sound check."""
    rec = bench_tiny.run("tiny_sessions_closed", seed=2 ** 34 + 3,
                         seconds=3.0)
    from bench.run import is_correct
    assert rec["failed"] == 0 and rec["attempted"] > 0
    assert rec["counters"]["prefix_hits"] > 0
    assert rec["admitted_cached_tokens"] > 0
    assert rec["check"]["requests"] >= 3 and is_correct(rec)

