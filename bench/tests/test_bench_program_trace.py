"""The reduction of the program's own spans and scopes
(``bench/program_trace.py``): on events made by hand with answers worked
by hand, on a CPU trace of the tiny cell recorded with them
(``data/tiny_sessions_closed_spans.*``), on a tiny engine traced in the
test, and through one traced run of the tiny cell.  Also pins what
``bench/trace.py`` reads from the trace recorded before the program had
spans (``data/tiny_sessions_closed.xplane.pb.gz``)."""

import gzip
import json
import shutil
import time

import pytest

import bench_tiny
from bench import program_trace as pt
from bench import trace
from bench_tiny import DATA

OLD_ROLES = {"chunk_step": "jit_run", "decode_step": "jit__lambda",
             "prefill_attn_kernel": "paged_prefill_attention"}
ROLES = {"chunk_step": "jit_chunk_step", "decode_step": "jit_decode_step",
         "prefill_attn_kernel": "paged_prefill_attention"}
ENGINE_SPANS = {"engine.step", "engine.schedule", "engine.page_table",
                "engine.chunk", "engine.chunk_sync", "engine.first_token",
                "engine.compile_probe", "engine.decode_dispatch",
                "engine.sample", "engine.finish", "engine.sync",
                "engine.register", "engine.step_tail"}
READINGS = ("host_idle_ms", "schedule_ms", "decode_attn_ms",
            "decode_matmul_ms")

# a fusion whose fused computation holds a qkv multiply under an mlp
# root, a convert XLA left with no metadata, and a scan copy whose path
# names no scope
HLO_BY_HAND = """HloModule jit_decode_step, is_scheduled=true

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %multiply.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(decode_step)/while/body/closed_call/qkv/mul"}
  ROOT %add.2 = f32[8]{0} add(%multiply.1, %param_0), metadata={op_name="jit(decode_step)/while/body/closed_call/mlp/add" stack_frame_id=5}
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(decode_step)/while/body/closed_call/mlp/add" stack_frame_id=5}
  %gather.2 = f32[8]{0} gather(%p, %p), metadata={op_name="jit(decode_step)/while/body/closed_call/kv_gather/gather"}
  %convert.3 = f32[8]{0} convert(%gather.2)
  %copy.4 = f32[8]{0} copy(%p), metadata={op_name="jit(decode_step)/while/body/dynamic_slice"}
  ROOT %fusion.5 = f32[8]{0} fusion(%convert.3, %fusion.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(decode_step)/while/body/closed_call/attend/dot_general"}
}
"""


def test_scope_map_by_hand():
    assert pt.scope_map(HLO_BY_HAND) == {
        "param_0": "unscoped", "multiply.1": "qkv", "add.2": "mlp",
        "p": "unscoped", "fusion.1": "mlp",        # the root's scope
        "gather.2": "kv_gather",
        "convert.3": "kv_gather",                  # from its operand
        "copy.4": "unscoped", "fusion.5": "attend"}


SPANS_BY_HAND = [(-2.0, -1.0, "engine.step", 6),
                 (1.0, 5.5, "engine.step", 7),
                 (1.0, 2.0, "engine.schedule", None),
                 (3.0, 5.0, "engine.decode_dispatch", None),
                 (4.0, 5.0, "engine.sample", None),
                 (7.0, 9.0, "engine.finish", None),
                 (7.0, 8.5, "engine.sync", None)]


def _events_by_hand():
    dev = "/device:TPU:0"
    return {
        "spans": [(0.0, 10.0, "bench.window"),
                  (1.0, 6.0, "bench.step_async"),
                  (7.0, 9.0, "bench.finish_step")],
        "ops": {dev: [
            # an execution that starts before the window: in no program
            # and no scope, though its time counts as busy
            (-1.0, 0.5, "fusion.5", ""),
            (2.0, 2.5, "fusion.1", ""),
            (2.5, 3.0, "fusion.5", ""),
            (5.5, 6.0, "gather.2", ""),
            (6.0, 6.5, "convert.3", ""),
            (6.5, 7.0, "copy.4", ""),
            (9.5, 11.0, "fusion.9", "")]},
        "modules": {dev: [(-1.0, 0.5, "jit_decode_step"),
                          (2.0, 3.0, "jit_decode_step"),
                          (5.5, 7.0, "jit_decode_step"),
                          (9.5, 11.0, "jit_chunk_step")]},
    }


def test_reduce_program_by_hand():
    ev = _events_by_hand()
    out = pt.reduce_program(
        ev, SPANS_BY_HAND, ROLES,
        {"jit_decode_step": pt.scope_map(HLO_BY_HAND)})
    assert out["engine_steps"] == 1
    assert out["host_spans"] == {
        "engine.step": {"calls": 1, "seconds": 4.5},
        "engine.schedule": {"calls": 1, "seconds": 1.0},
        "engine.decode_dispatch": {"calls": 1, "seconds": 2.0},
        "engine.sample": {"calls": 1, "seconds": 1.0},
        "engine.finish": {"calls": 1, "seconds": 2.0},
        "engine.sync": {"calls": 1, "seconds": 1.5}}
    # busy [0,0.5] [2,3] [5.5,7] [9.5,10], so idle: [0.5,1] outside,
    # [1,2] schedule, [3,4] dispatch, [4,5] sample, [5,5.5] step,
    # [7,8.5] sync, [8.5,9] finish, [9,9.5] outside
    assert out["host_idle"] == pytest.approx({
        "outside_engine": 1.0, "engine.schedule": 1.0,
        "engine.decode_dispatch": 1.0, "engine.sample": 1.0,
        "engine.step": 0.5, "engine.sync": 1.5, "engine.finish": 0.5})
    assert out["scopes"]["decode_step"] == pytest.approx(
        {"mlp": 0.5, "attend": 0.5, "kv_gather": 1.0, "unscoped": 0.5})
    assert out["scopes"]["chunk_step"] == pytest.approx({"unscoped": 0.5})
    # the scopes share out the programs' own seconds, and the idle time
    # is the window's
    base = trace.reduce_events(ev, ROLES, inflight_s=10.0)
    for role in ("decode_step", "chunk_step"):
        assert sum(out["scopes"][role].values()) == pytest.approx(
            base["programs"][role]["seconds"])
    assert sum(out["host_idle"].values()) == pytest.approx(
        base["window_s"] - base["busy_s"])
    # two decode calls start in the window
    assert pt.readings(out, base["programs"]) == pytest.approx({
        "host_idle_ms": 1e3 * 5.5, "schedule_ms": 1e3 * 1.0,
        "decode_attn_ms": 1e3 * 1.5 / 2, "decode_matmul_ms": 1e3 * 0.5 / 2})


def test_no_readings_without_spans_or_scopes():
    """A program with no spans and no scopes (as before them) gives no
    reading, and no error."""
    ev = _events_by_hand()
    out = pt.reduce_program(ev, [], ROLES, {})
    assert out["engine_steps"] == 0 and out["host_spans"] == {}
    assert set(out["host_idle"]) == {"outside_engine"}
    base = trace.reduce_events(ev, ROLES, inflight_s=10.0)
    assert pt.readings(out, base["programs"]) == dict.fromkeys(READINGS)


def _unzip(name, tmp_path):
    path = tmp_path / name.replace(".gz", "")
    with gzip.open(DATA / name) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


def test_recorded_trace_reads_as_before(tmp_path):
    """``bench/trace.py`` reduces the trace recorded before the program
    had spans to the numbers it read then, in every key."""
    path = _unzip("tiny_sessions_closed.xplane.pb.gz", tmp_path)
    out = trace.reduce_events(trace.read(path), OLD_ROLES, inflight_s=0.6)
    approx = lambda x: pytest.approx(x, rel=1e-12, abs=1e-15)
    assert out["window_s"] == approx(1.0033517760000001)
    assert out["busy_s"] == approx(0.212433682000003)
    assert out["inflight_s"] == 0.6
    assert out["kernel_s"] == 0.0
    assert out["programs"] == {
        "chunk_step": {"calls": 12.0, "seconds": approx(0.1134682790000001)},
        "decode_step": {"calls": 22.0,
                        "seconds": approx(0.07729239399999994)}}
    assert [k for k, _ in out["device_ops"]] == [
        "jit_run/dot.50", "jit_sort/sort.5", "jit_run/dot.54",
        "jit__lambda/transpose_copy_fusion",
        "jit__lambda/transpose_copy_fusion.1",
        "jit__lambda/convert_bitcast_fusion.5",
        "jit__lambda/copy_multiply_fusion.1",
        "jit__lambda/dynamic-update-slice_convert_fusion",
        "jit__lambda/dot.18",
        "jit__lambda/dynamic-update-slice_convert_fusion.1"]
    assert [v for _, v in out["device_ops"]] == [approx(v) for v in (
        0.019451096999999952, 0.011480956999999691, 0.007496681999999934,
        0.006524676999999854, 0.005765793000000061, 0.005471167000000131,
        0.005367420999999671, 0.004026934999999995, 0.003966783000000043,
        0.003934480000000129)]
    assert out["idle_gaps"] == [
        ["bench.step_async", approx(0.7695599219999972)],
        ["bench.finish_step", approx(0.00955929500000001)],
        ["bench.submit", approx(0.006602010999999908)],
        ["host:other", approx(0.003384892000000198)],
        ["bench.server_step", approx(0.0015361199999998525)],
        ["bench.stamp", approx(0.00027585399999990184)]]
    assert pt.read_spans(path) == []


def test_reduce_recorded_trace_with_program_spans(tmp_path):
    """A CPU trace of the tiny cell recorded with the program's spans:
    the window's ``.xplane.pb`` and the step programs' HLO, as
    :func:`bench.program_trace.run_traced` had them."""
    path = _unzip("tiny_sessions_closed_spans.xplane.pb.gz", tmp_path)
    with gzip.open(DATA / "tiny_sessions_closed_spans.hlo.json.gz",
                   "rt") as f:
        meta = json.load(f)
    assert meta["roles"] == ROLES
    ev = trace.read(path)
    base = trace.reduce_events(ev, ROLES, meta["inflight_s"])
    out = pt.reduce_program(ev, pt.read_spans(path), ROLES,
                            {m: pt.scope_map(t)
                             for m, t in meta["hlo"].items()})
    assert set(out["host_spans"]) == ENGINE_SPANS
    assert out["engine_steps"] == base["programs"]["decode_step"]["calls"]
    assert sum(out["host_idle"].values()) == pytest.approx(
        base["window_s"] - base["busy_s"], rel=1e-6)
    assert out["host_idle"]["outside_engine"] < 0.1 * sum(
        out["host_idle"].values())
    for role in ("decode_step", "chunk_step"):
        sc, prog = out["scopes"][role], base["programs"][role]
        assert set(pt.SCOPES) <= set(sc), role
        # CPU threads leave gaps inside an execution: the operations
        # cover most of it and never more
        assert 0.5 * prog["seconds"] < sum(sc.values()) <= prog["seconds"]
    assert all(v > 0 for v in pt.readings(out, base["programs"]).values())


@pytest.fixture(scope="module")
def traced_engine(tmp_path_factory):
    """A tiny engine that ran one synchronous step (a prompt's last
    chunk and a decode) and one pipelined step (decodes) under the
    profiler."""
    import numpy as np

    from bench import model as bm
    from repro.models import build_model
    from repro.serving.engine import Engine

    conf = json.loads((DATA / "tiny.json").read_text())
    model = build_model(bm.model_config(conf))
    engine = Engine(model, bm.build_weights(model, conf, 5), max_slots=2,
                    max_seq=128, eos_id=-1,
                    page_size=conf["serving"]["page_size"], n_pages=16,
                    prefill_chunk_tokens=32)
    # the traced chunk step completes the first prompt's third block
    for n in (50, 20):
        engine.submit(np.arange(n, dtype=np.int32) % 512, max_new_tokens=4,
                      temperature=0.0)
    engine.step()                       # compiles outside the trace
    trace.start(str(tmp_path_factory.mktemp("trace")))
    engine.step()
    _, pending = engine.step_async()
    engine.finish_step(pending)
    return engine, pt.read_spans(trace.stop())


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_engine_spans_nest_as_documented(traced_engine):
    engine, spans = traced_engine
    assert {n for _, _, n, _ in spans} == ENGINE_SPANS
    by = lambda name: [sp for sp in spans if sp[2] == name]
    steps = by("engine.step")
    assert [sp[3] for sp in steps] == [p["step"]
                                       for p in engine.plan_log[-2:]]
    parent = {"engine.schedule": "engine.step",
              "engine.page_table": "engine.step",
              "engine.chunk": "engine.step",
              "engine.compile_probe": "engine.step",
              "engine.decode_dispatch": "engine.step",
              "engine.chunk_sync": "engine.chunk",
              "engine.first_token": "engine.chunk",
              "engine.sample": "engine.decode_dispatch"}
    for child, outer in parent.items():
        for sp in by(child):
            assert any(_inside(sp, o) for o in by(outer)), (child, sp)
    # the pipelined step's deferred half runs after its engine.step
    (fin,) = by("engine.finish")
    assert fin[0] >= steps[-1][1]
    for child in ("engine.sync", "engine.step_tail"):
        assert any(_inside(sp, fin) for sp in by(child)), child
    for sp in by("engine.register"):
        assert any(_inside(sp, o) for o in by("engine.chunk")
                   + by("engine.finish")), sp


def test_step_programs_carry_every_scope(traced_engine):
    engine, _ = traced_engine
    hlo = pt.step_hlo(engine)
    assert set(hlo) == {"jit_decode_step", "jit_chunk_step"}
    assert trace.program_roles(engine)["decode_step"] == "jit_decode_step"
    for module, text in hlo.items():
        found = set(pt.scope_map(text).values())
        assert set(pt.SCOPES) <= found, (module, found)


def test_run_traced_on_the_tiny_cell(tmp_path):
    rec = pt.run_traced(bench_tiny.cell("tiny_sessions_closed"),
                        2 ** 33 + 11, 1.0, time.time(), require_tpu=False,
                        trace_dir=str(tmp_path / "trace"))
    assert rec["compiles_in_window"] == 0
    prog, programs = rec["program"], rec["trace"]["programs"]
    assert prog["engine_steps"] == programs["decode_step"]["calls"] > 0
    assert set(rec["hlo"]) == {"jit_decode_step", "jit_chunk_step"}
    assert all(v > 0 for v in pt.readings(prog, programs).values())
    # the benchmark's own functions are themselves again after the run
    assert trace.reduce.__module__ == "bench.trace"
    assert trace.program_roles.__module__ == "bench.trace"
