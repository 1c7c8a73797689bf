"""The reduction from a profiler trace to the per-layer metrics, on a
trace built by hand (every number below is worked out from it) and on a
recorded excerpt of a TPU v5e trace committed as a fixture."""
from __future__ import annotations

import json
import os

import pytest

import bench_tiny  # noqa: F401  (puts the repo root on sys.path)
from bench import devices, tracing
from bench.spec import Spec

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "v5e_trace_excerpt.json")
V5E = devices.peaks_for("tpu", "TPU v5 lite")
MS = 1e6  # ns


def hand_trace():
    """Window 0..100 ms. Device A: fusion 0-40, kernel 40-50, all-reduce
    45-60 (5 ms under the kernel), fusion 70-90. Device B: fusion 0-50,
    all-reduce 50-70, fusion 70-100. Host: batches 0-10 ms, dispatch
    10-12, window 0-100."""
    return {
        "host": [["bench.window", 0.0, 100 * MS],
                 ["bench.batches", 0.0, 10 * MS],
                 ["bench.dispatch", 10 * MS, 2 * MS]],
        "devices": {
            "TPU:0": [["fusion.1", 0.0, 40 * MS],
                      ["pool_pack.3", 40 * MS, 10 * MS],
                      ["all-reduce.7", 45 * MS, 15 * MS],
                      ["fusion.2", 70 * MS, 20 * MS]],
            "TPU:1": [["fusion.1", 0.0, 50 * MS],
                      ["all-reduce.7", 50 * MS, 20 * MS],
                      ["fusion.2", 70 * MS, 30 * MS]]},
        "ops": {"fusion.1": "fusion", "fusion.2": "fusion",
                "pool_pack.3": tracing.MOSAIC,
                "all-reduce.7": "all-reduce"}}


def ctx(trace, **kw):
    base = {"trace": trace, "steps": 2, "tokens_per_s_per_chip": 1000.0,
            "flops_per_token": 1e9, "peaks": V5E, "chips": 2,
            "kernel_bytes": {"pool_pack": 819e9 * 4e-3}}
    base.update(kw)
    return base


def read(metric, c):
    return Spec().reader(metric)(c)


def test_interval_arithmetic():
    assert tracing.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert tracing.subtract([(0, 10)], [(2, 3), (5, 12)]) == \
        [(0, 2), (3, 5)]
    assert tracing.clip([(0, 5), (8, 20)], 2, 10) == [(2, 5), (8, 10)]


def test_hand_trace_metrics():
    t = hand_trace()
    c = ctx(t)
    # busy: A 0-60 and 70-90 = 80 ms; B 100 ms; mean 90 ms of 100.
    assert tracing.busy_s(t) == pytest.approx(0.090)
    assert read("device_idle_pct", c) == pytest.approx(10.0)
    # the kernel: one 10 ms call on A (at least one call per step), none
    # on B -> 5 ms per step averaged over the chips
    assert read("pool_kernel_ms", c) == pytest.approx(5.0)
    # needs 4 ms of bytes per step at 819 GB/s over 5 ms -> 80%
    assert read("pool_kernel_roofline", c) == pytest.approx(80.0)
    # batches 10 ms over 2 steps
    assert read("input_wait_ms", c) == pytest.approx(5.0)
    # 1e9 FLOP/token * 1000 tokens/s over 197e12
    assert read("mfu", c) == pytest.approx(100 * 1e12 / 197e12)


def test_dropped_events_leave_kernel_time_per_call_unbiased():
    """Four steps of one 2 ms kernel call each; the profiler dropped one:
    the trace is incomplete, and the time per step stays 2 ms."""
    calls = [["pool_pack.1", i * 10 * MS, 2 * MS] for i in (0, 1, 3)]
    t = {"host": [["bench.window", 0.0, 40 * MS]],
         "devices": {"TPU:0": calls + [["fusion.1", 30 * MS, MS]]},
         "ops": {"pool_pack.1": tracing.MOSAIC, "fusion.1": "fusion"}}
    assert not tracing.complete(t, 4)
    assert tracing.mosaic_seconds(t, 4) == {"pool_pack": pytest.approx(
        0.002)}
    whole = dict(t, devices={"TPU:0": calls + [["pool_pack.1", 20 * MS,
                                                2 * MS],
                                               ["fusion.1", 30 * MS, MS]]})
    assert tracing.complete(whole, 4)


def test_breakdown_names_gaps_by_host_span():
    brk = tracing.breakdown(hand_trace())
    assert brk["device_ops"][0] == ["fusion.1", pytest.approx(0.045)]
    # device A idles 60-70 and 90-100, inside the window call only
    assert brk["idle_gaps"] == [["loss_read", pytest.approx(0.010)],
                                ["loss_read", pytest.approx(0.010)]]


def test_readers_give_nothing_where_nothing_is():
    t = hand_trace()
    assert read("pool_kernel_roofline",
                ctx(t, kernel_bytes={"csc_compact": 1.0})) is None
    none = dict(t, ops={k: "fusion" for k in t["ops"]})
    assert read("pool_kernel_ms", ctx(none)) is None


def test_hlo_opcodes():
    text = (
        '  %pool_pack.23 = bf16[134515008]{0:T(1024)} custom-call(%a, %b),'
        ' custom_call_target="tpu_custom_call", operand_layout_constraints'
        '\n  %all-reduce-start.4 = (f32[8]{0}, f32[8]{0}) '
        'all-reduce-start(%x), replica_groups={}\n'
        '  ROOT %fusion.9 = f32[4,4]{1,0} fusion(%p), kind=kLoop\n'
        '  %pool_unpack_update.67 = (f32[9953280]{0:T(1024)}, f32[9953280]'
        '{0:T(1024)S(1)}) custom-call(%bitcast.390, %slice.471), '
        'custom_call_target="tpu_custom_call"\n'
        '  %all-reduce.14 = (bf16[31629888]{0:T(1024)(128)(2,1)}, bf16[99]'
        '{0:T(1024)(128)(2,1)}) all-reduce(%a, %b), channel_id=1\n')
    assert tracing.hlo_opcodes(text) == {
        "pool_pack.23": tracing.MOSAIC,
        "all-reduce-start.4": "all-reduce-start", "fusion.9": "fusion",
        "pool_unpack_update.67": tracing.MOSAIC,
        "all-reduce.14": "all-reduce"}


def test_recorded_v5e_trace():
    """An excerpt of a trace recorded on the chip (132.9 ms of the last
    step of a traced smollm-135m window: the gradient pack and the six
    bucket updates): the reduction reads what it read when recorded, and
    the kernel time equals the sum of the kernels' own events."""
    with open(FIXTURE) as f:
        fix = json.load(f)
    trace = fix["trace"]
    c = ctx(trace, steps=fix["steps"], kernel_bytes=fix["kernel_bytes"],
            tokens_per_s_per_chip=fix["tokens_per_s_per_chip"],
            flops_per_token=fix["flops_per_token"], chips=1)
    for name, want in fix["expect"].items():
        assert read(name, c) == pytest.approx(want, rel=1e-9), name
    (events,) = trace["devices"].values()
    kernels = [d for n, s, d in events
               if n.startswith(("pool_pack.", "pool_unpack_update."))]
    assert len(kernels) == 7
    assert read("pool_kernel_ms", c) == pytest.approx(sum(kernels) / 1e6)
    assert 0.0 <= read("device_idle_pct", c) < 1.0


def eight_steps(damage: str):
    """Eight 10 ms steps as a traced olmo-1b-4L window has them: the two
    packs (3 ms each), a fusion (3 ms) and the chunk census (0.5 ms).
    ``dropped``: as on the chip, the events of two steps are missing but
    for the first pack's, which stretches over its step (there a
    ``chunk_l1norm_roofline`` of 123% was read, every other op at 6/8 of
    its time). ``stalled``: every event kept, but the window ran 9 ms
    longer with the device idle (there ``device_idle_pct`` read 10.1%)."""
    events = []
    for i in range(8):
        t = i * 10 * MS
        if damage == "dropped" and i in (2, 5):
            events.append(["pool_pack.24", t, 10 * MS])
            continue
        events += [["pool_pack.24", t, 3 * MS],
                   ["pool_pack.25", t + 3 * MS, 3 * MS],
                   ["fusion.461", t + 6 * MS, 3 * MS],
                   ["chunk_l1norm.2", t + 9 * MS, 0.5 * MS]]
    end = (89 if damage == "stalled" else 80) * MS
    return {"host": [["bench.window", 0.0, end]],
            "devices": {"TPU:0": events},
            "ops": {"pool_pack.24": tracing.MOSAIC,
                    "pool_pack.25": tracing.MOSAIC, "fusion.461": "fusion",
                    "chunk_l1norm.2": tracing.MOSAIC}}


@pytest.mark.parametrize("damage,complete,steady", [
    ("none", True, True), ("dropped", False, True),
    ("stalled", True, False)])
def test_damaged_traces_seen_on_chip_are_rejected(damage, complete, steady):
    """The harness traces another window unless the trace is complete
    and kept the untraced pace (here 80 ms a window)."""
    t = eight_steps(damage)
    assert tracing.complete(t, 8) is complete
    assert tracing.steady(t, 0.080) is steady
    # the census's time per call stays what it was, dropped events or not
    assert tracing.mosaic_seconds(t, 8)["chunk_l1norm"] == \
        pytest.approx(0.5e-3)
