"""The reduction from a profiler trace to per-layer numbers: on events
made by hand with known answers, and on a small trace of the tiny cell
recorded on the CPU (``data/tiny_sessions_closed.xplane.pb.gz``)."""

import gzip
import shutil

import pytest

from bench import trace
from bench_tiny import DATA

ROLES = {"chunk_step": "jit_run", "decode_step": "jit__lambda",
         "prefill_attn_kernel": "paged_prefill_attention"}


def test_reduce_events_by_hand():
    ev = {
        "spans": [(0.0, 10.0, "bench.window"),
                  (1.0, 4.0, "bench.step_async"),
                  (2.0, 3.0, "bench.submit"),
                  (6.0, 9.0, "bench.finish_step")],
        "ops": {"/device:TPU:0": [
            (0.5, 1.5, "fusion.1", "jit_run"),
            (1.0, 2.0, "paged_prefill_attention.3", "jit_run"),
            # a loop over layers holds the operations of its body; these
            # carry no module and take the execution that covers them
            (5.0, 7.0, "while.4", ""),
            (5.0, 6.0, "fusion.2", ""),
            (6.0, 7.0, "fusion.2", ""),
            (9.5, 11.0, "fusion.2", "jit__lambda")]},
        "modules": {"/device:TPU:0": [(0.5, 2.0, "jit_run"),
                                      (5.0, 7.0, "jit__lambda"),
                                      (9.5, 11.0, "jit__lambda")]},
    }
    out = trace.reduce_events(ev, ROLES, inflight_s=8.0)
    assert out["window_s"] == 10.0
    assert out["busy_s"] == pytest.approx(1.5 + 2.0 + 0.5)
    assert out["kernel_s"] == pytest.approx(1.0)
    assert out["programs"]["chunk_step"] == {"calls": 1, "seconds": 1.5}
    assert out["programs"]["decode_step"]["calls"] == 2
    assert out["programs"]["decode_step"]["seconds"] == pytest.approx(2.5)
    gaps = dict(out["idle_gaps"])
    # idle: [0,0.5] other, [2,3] submit, [3,4] step_async, [4,5] other,
    # [7,9] finish_step, [9,9.5] other
    assert gaps == pytest.approx({"host:other": 2.0, "bench.submit": 1.0,
                                  "bench.step_async": 1.0,
                                  "bench.finish_step": 2.0})
    ops = dict(out["device_ops"])
    assert ops == pytest.approx({"jit__lambda/fusion.2": 2.5,
                                 "jit_run/fusion.1": 1.0,
                                 "jit_run/paged_prefill_attention.3": 1.0})


def test_op_names_from_tpu_event_names():
    assert trace.op_name("%fusion.171 = f32[16,1,22016]{2,0,1:T(8,128)} "
                         "fusion(f32[22016,4096]{0,1:T(8,128)} %b)") \
        == "fusion.171"
    assert trace.op_name("dot.3") == "dot.3"


def test_reduce_recorded_cpu_trace(tmp_path):
    path = tmp_path / "tiny.xplane.pb"
    with gzip.open(DATA / "tiny_sessions_closed.xplane.pb.gz") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    ev = trace.read(str(path))
    names = {n for _, _, n in ev["spans"]}
    assert {"bench.window", "bench.step_async", "bench.finish_step",
            "bench.server_step", "bench.stamp"} <= names
    out = trace.reduce_events(ev, ROLES, inflight_s=0.6)
    assert 0 < out["busy_s"] < out["window_s"]
    assert sum(v for _, v in out["idle_gaps"]) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-6)
    for role in ("chunk_step", "decode_step"):
        prog = out["programs"][role]
        assert prog["calls"] >= 1 and 0 < prog["seconds"] < out["busy_s"]
    assert out["kernel_s"] == 0           # the CPU takes the jnp prefix read
    times = [v for _, v in out["device_ops"]]
    assert times == sorted(times, reverse=True) and len(times) == 10
    assert all(k.startswith(("bench.", "host:")) for k, _ in out["idle_gaps"])


def test_module_names_match_the_program():
    import jax
    assert trace.module_name(jax.jit(lambda x: x)) == "jit__lambda"

    def run(x):
        return x
    assert trace.module_name(jax.jit(run)) == "jit_run"
