"""The reduction from the program's scopes and spans to per-layer
metrics (``bench.scopes``), on an HLO text and a trace built by hand
(every number below is worked out from them) and on a recorded excerpt
of a TPU v5e trace that holds them, committed as a fixture."""
from __future__ import annotations

import json
import os

import pytest

import bench_tiny  # noqa: F401  (puts the repo root on sys.path)
from bench import devices, scopes, tracing
from bench.spec import Spec

MS = 1e6  # ns
SCOPED_FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                              "v5e_scoped_trace_excerpt.json")

HLO = """HloModule jit_window, entry_computation_layout={()->f32[8]{0}}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %exponential.1 = f32[8]{0} exponential(f32[8]{0} %param_0), metadata={op_type="exp" op_name="jit(window)/while/body/closed_call/jvp(model)/exp" source_file="m.py" source_line=3}
  ROOT %multiply.2 = f32[8]{0} multiply(%exponential.1, %exponential.1), metadata={op_type="mul" op_name="jit(window)/while/body/closed_call/transpose(jvp(model))/mul"}
}

%fused_computation.2 (param_0.1: bf16[8]) -> f32[8] {
  %param_0.1 = bf16[8]{0} parameter(0)
  %convert.3 = f32[8]{0} convert(%param_0.1), metadata={op_name="jit(window)/while/body/closed_call/gf.update/convert_element_type"}
  ROOT %copy.4 = f32[8]{0} copy(%convert.3)
}

%fused_computation.3 (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  %negate.5 = f32[8]{0} negate(%param_0.2), metadata={op_name="jit(window)/while/body/closed_call/gf.pack/neg"}
  %abs.6 = f32[8]{0} abs(%negate.5), metadata={op_name="jit(window)/while/body/closed_call/gf.master_pack/abs"}
  ROOT %copy.7 = f32[8]{0} copy(%abs.6)
}

%body.8 (p: (f32[8])) -> (f32[8]) {
  %p = (f32[8]{0}) parameter(0)
  %get-tuple-element.9 = f32[8]{0} get-tuple-element(%p), index=0
  %dot.10 = f32[8]{0} dot(%get-tuple-element.9, %get-tuple-element.9), lhs_contracting_dims={}, metadata={op_type="dot_general" op_name="jit(window)/while/body/closed_call/jvp(model)/while/body/closed_call/dot_general"}
  %dot.11 = f32[8]{0} dot(%dot.10, %dot.10), metadata={op_name="jit(window)/while/body/closed_call/transpose(jvp(model))/while/body/dot_general"}
  %fusion.12 = f32[8]{0} fusion(%dot.11), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(window)/while/body/closed_call/transpose(jvp(model))/mul"}
  %fusion.13 = f32[8]{0} fusion(%fusion.12), kind=kLoop, calls=%fused_computation.2
  %fusion.14 = f32[8]{0} fusion(%fusion.13), kind=kLoop, calls=%fused_computation.3
  %top_k.15 = f32[8]{0} custom-call(%fusion.14), custom_call_target="TopK", metadata={op_name="jit(window)/while/body/closed_call/gf.reduce/gf.csc_select/top_k"}
  %all-reduce-start.16 = f32[8]{0} all-reduce-start(%top_k.15), to_apply=%add, metadata={op_name="jit(window)/while/body/closed_call/gf.reduce/psum"}
  %pool_pack.17 = bf16[8]{0} custom-call(%dot.11), custom_call_target="tpu_custom_call", backend_config={"custom_call_config": {"body": "x"}}, metadata={op_name="jit(window)/while/body/closed_call/gf.pack/pallas_call"}
  %dynamic-slice.18 = f32[4]{0} dynamic-slice(%dot.10, %c), metadata={op_name="jit(window)/while/body/dynamic_slice"}
  ROOT %copy.19 = f32[8]{0} copy(%dot.11)
}

ENTRY %main.20 () -> f32[8] {
  %while.21 = (f32[8]{0}) while(%t), condition=%cond, body=%body.8, metadata={op_name="jit(window)/while"}
}
"""


def test_scope_of_takes_the_innermost_and_marks_the_backward():
    assert scopes.scope_of("jit(w)/while/body/closed_call/jvp(model)/"
                           "while/body/dot_general") == ("model", False)
    assert scopes.scope_of("jit(w)/closed_call/transpose(jvp(model))/"
                           "jit(silu)/mul") == ("model", True)
    assert scopes.scope_of("jit(w)/gf.reduce/gf.csc_select/top_k") == \
        ("gf.csc_select", False)
    assert scopes.scope_of("jit(w)/gf.reduce/gf.reduce/psum") == \
        ("gf.reduce", False)
    assert scopes.scope_of("jit(w)/while/body/dynamic_slice") is None
    assert scopes.scope_of("jit(w)/models/jit(model_x)/add") is None


def test_hlo_scopes():
    got = scopes.hlo_scopes(HLO)
    assert got["dot.10"] == ("model", False)
    assert got["dot.11"] == ("model", True)
    # a fusion belongs to its root's scope: the backward multiply, not
    # the forward exp beside it in the fused computation
    assert got["fusion.12"] == ("model", True)
    # no metadata of its own nor on its root: the one scope inside
    assert got["fusion.13"] == ("gf.update", False)
    # ... and none where the instructions inside disagree
    assert "fusion.14" not in got
    assert got["top_k.15"] == ("gf.csc_select", False)
    assert got["all-reduce-start.16"] == ("gf.reduce", False)
    assert got["pool_pack.17"] == ("gf.pack", False)
    for unscoped in ("dynamic-slice.18", "copy.19", "while.21",
                     "get-tuple-element.9", "main.20", "body.8"):
        assert unscoped not in got, unscoped
    # instructions inside fused computations keep their own scopes
    assert got["exponential.1"] == ("model", False)
    assert got["negate.5"] == ("gf.pack", False)


def hand_trace():
    """Window 0..100 ms, two devices, one step.
    TPU:0: a loop over everything (0-100); fwd dot 0-30; bwd dot 30-60
    and the bwd fusion 50-70 (overlapping it by 10); pack 70-80; an
    async all-reduce 75-95 in gf.reduce; an unscoped copy 95-100; the
    update fusion from 90, cut by the window's end at 100 of its 110.
    TPU:1: fwd dot 0-40, bwd dot 40-80, pack 80-90, idle after.
    Host: the window; the benchmark's batches 0-10 ms around the loop's
    0.1-9.9, which holds a 6 ms queue wait 2-8 and the placing 8-9.9;
    the prefetch thread's fetch 0.5-4.5."""
    return {
        "host": [["bench.window", 0.0, 100 * MS],
                 ["bench.batches", 0.0, 10 * MS],
                 ["repro.loop.batches", 0.1 * MS, 9.8 * MS],
                 ["repro.data.get", 2 * MS, 6 * MS],
                 ["repro.loop.place", 8 * MS, 1.9 * MS],
                 ["repro.data.fetch", 0.5 * MS, 4 * MS]],
        "devices": {
            "TPU:0": [["while.21", 0.0, 100 * MS],
                      ["dot.10", 0.0, 30 * MS],
                      ["dot.11", 30 * MS, 30 * MS],
                      ["fusion.12", 50 * MS, 20 * MS],
                      ["pool_pack.17", 70 * MS, 10 * MS],
                      ["all-reduce-start.16", 75 * MS, 20 * MS],
                      ["copy.19", 95 * MS, 5 * MS],
                      ["fusion.13", 90 * MS, 20 * MS]],
            "TPU:1": [["dot.10", 0.0, 40 * MS],
                      ["dot.11", 40 * MS, 40 * MS],
                      ["pool_pack.17", 80 * MS, 10 * MS]]},
        "ops": {"while.21": "while", "dot.10": "dot", "dot.11": "dot",
                "fusion.12": "fusion", "fusion.13": "fusion",
                "pool_pack.17": tracing.MOSAIC,
                "all-reduce-start.16": "all-reduce-start",
                "copy.19": "copy"},
        "scopes": {k: list(v) for k, v in scopes.hlo_scopes(HLO).items()}}


def ctx(trace, steps=1):
    return {"trace": trace, "steps": steps}


def read(metric, c):
    return Spec().reader(metric)(c)


def test_scope_seconds():
    got = scopes.scope_seconds(hand_trace(), 1)
    # model fwd: 30 ms on TPU:0, 40 on TPU:1 -> 35 ms averaged
    assert got[("model", False)] == pytest.approx(0.035)
    # model bwd: the union of 30-60 and 50-70 is 40 ms (not 50) on
    # TPU:0, 40 on TPU:1
    assert got[("model", True)] == pytest.approx(0.040)
    assert got[("gf.pack", False)] == pytest.approx(0.010)
    # the reduce on TPU:0 only; the update cut to 90-100 by the window
    assert got[("gf.reduce", False)] == pytest.approx(0.010)
    assert got[("gf.update", False)] == pytest.approx(0.005)
    assert set(got) == {("model", False), ("model", True),
                        ("gf.pack", False), ("gf.reduce", False),
                        ("gf.update", False)}
    # per step: the same trace over two steps halves every number
    assert scopes.scope_seconds(hand_trace(), 2)[("gf.pack", False)] == \
        pytest.approx(0.005)


def test_scope_readers():
    c = ctx(hand_trace(), steps=2)
    assert read("model_fwd_ms", c) == pytest.approx(17.5)
    assert read("model_bwd_ms", c) == pytest.approx(20.0)
    assert read("gf_pack_ms", c) == pytest.approx(5.0)
    assert read("gf_update_ms", c) == pytest.approx(2.5)
    # a 6 ms wait over two steps
    assert read("input_queue_wait_ms", c) == pytest.approx(3.0)


@pytest.mark.parametrize("metric", [
    "model_fwd_ms", "model_bwd_ms", "gf_pack_ms", "gf_master_pack_ms",
    "gf_update_ms", "input_queue_wait_ms"])
def test_readers_give_nothing_where_the_program_has_no_scopes(metric):
    """The trace of a program without scopes or spans, as the benchmark
    compacts it today: no ``scopes`` map, no ``repro.`` span."""
    t = hand_trace()
    del t["scopes"]
    t["host"] = [h for h in t["host"] if h[0].startswith("bench.")]
    assert read(metric, ctx(t)) is None


def test_readers_give_nothing_for_a_scope_absent_from_the_trace():
    # the master pack's instruction is in no event of the hand trace
    assert read("gf_master_pack_ms", ctx(hand_trace())) is None
    t = hand_trace()
    t["devices"] = {d: [e for e in ev if e[0] != "fusion.13"]
                    for d, ev in t["devices"].items()}
    assert read("gf_update_ms", ctx(t)) is None


def test_scoped_share_and_unscoped_ops():
    t = hand_trace()
    # TPU:0 busy 0-100 (the loop left out); scoped all but the copy's
    # 95-100, which the update covers from 90: 100%. TPU:1 busy 0-90, all
    # scoped: 100%.
    assert scopes.scoped_share(t) == pytest.approx(1.0)
    t["devices"]["TPU:0"] = [e for e in t["devices"]["TPU:0"]
                             if e[0] != "fusion.13"]
    # TPU:0: the copy's 5 ms of 100 busy are outside every scope
    assert scopes.scoped_share(t) == pytest.approx((0.95 + 1.0) / 2)
    assert scopes.unscoped_ops(t) == [["copy.19", pytest.approx(0.0025)]]


def test_breakdown_names_a_gap_by_the_program_span_it_falls_in():
    """With the program's spans kept, the innermost span of the training
    loop that holds a device gap names it: the queue wait, not the
    benchmark's ``batches``, nor the prefetch thread's fetch that runs
    beside the wait (as on the chip, where the fetch is the shorter)."""
    host = hand_trace()["host"]
    t = {"host": host + [["repro.data.fetch", 4.5 * MS, 1 * MS]],
         "devices": {"TPU:0": [["dot.10", 10 * MS, 90 * MS]]},
         "ops": {"dot.10": "dot"}}
    brk = scopes.breakdown(t)
    assert brk["idle_gaps"] == [["data.get", pytest.approx(0.010)]]
    # the plain breakdown takes the innermost span of any thread
    assert tracing.breakdown(t)["idle_gaps"][0][0] == "data.fetch"
    # ... and, as the benchmark compacts traces today, the outside span
    t["host"] = [h for h in host if h[0].startswith("bench.")]
    assert scopes.breakdown(t)["idle_gaps"][0][0] == "batches"
    for at, label in ((9 * MS, "loop.place"), (0.2 * MS, "loop.batches")):
        t = {"host": host, "devices": {"TPU:0": [["dot.10", 1 * MS, 1]]},
             "ops": {"dot.10": "dot"}}
        assert tracing.host_label(t, at) == label


def recorded():
    with open(SCOPED_FIXTURE) as f:
        fix = json.load(f)
    c = {"trace": fix["trace"], "steps": fix["steps"],
         "kernel_bytes": fix["kernel_bytes"],
         "tokens_per_s_per_chip": fix["tokens_per_s_per_chip"],
         "flops_per_token": fix["flops_per_token"],
         "peaks": devices.peaks_for("tpu", "TPU v5 lite"), "chips": 1}
    return fix, c


def test_recorded_v5e_trace_with_the_programs_scopes():
    """An excerpt of a trace recorded on the chip with the program's
    spans kept and its scopes mapped (qwen3-0.6b-4L: the window's input
    gap and its first step, 679 ms): every reader reads what it read when
    recorded, and the scopes agree with the kernels' own events."""
    fix, c = recorded()
    for name, want in fix["expect"].items():
        assert read(name, c) == pytest.approx(want, rel=1e-9), name
    events = fix["trace"]["devices"]["TPU:0"]
    kernel = {n: d / 1e6 for n, s, d in events
              if n in ("pool_pack.22", "pool_pack.23")}
    # each pack scope is its kernel and a little around it
    for metric, name in (("gf_master_pack_ms", "pool_pack.22"),
                         ("gf_pack_ms", "pool_pack.23")):
        assert kernel[name] <= read(metric, c) < kernel[name] * 1.01
    # the step's phases hold its busy time, less the scan's plumbing
    busy_ms = 1e3 * tracing.busy_s(fix["trace"])
    phases = sum(read(m, c) for m in (
        "model_fwd_ms", "model_bwd_ms", "gf_pack_ms", "gf_master_pack_ms",
        "gf_update_ms"))
    assert 0.95 * busy_ms < phases < busy_ms
    assert scopes.scoped_share(fix["trace"]) > 0.95
    # the loop waited on its prefetch queue for most of its input time
    assert 0.9 * read("input_wait_ms", c) < read("input_queue_wait_ms", c) \
        < read("input_wait_ms", c)
    gap, secs = scopes.breakdown(fix["trace"])["idle_gaps"][0]
    assert gap == "data.get" and secs > 0.03


@pytest.mark.parametrize("metric", [
    "input_wait_ms", "mfu", "pool_kernel_ms", "pool_kernel_roofline",
    "pool_pack_roofline", "pool_unpack_update_roofline", "device_idle_pct"])
def test_accepted_readers_ignore_the_programs_scopes_and_spans(metric):
    """The accepted readers read the same on the recorded trace with the
    program's spans and scopes as on it compacted as the benchmark does
    today (``bench.`` spans alone, no scope map)."""
    fix, c = recorded()
    plain = dict(fix["trace"], host=[h for h in fix["trace"]["host"]
                                     if h[0].startswith("bench.")])
    del plain["scopes"]
    assert read(metric, dict(c, trace=plain)) == read(metric, c)
