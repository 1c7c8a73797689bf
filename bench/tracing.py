"""Profiler traces, reduced to what the per-layer metrics read.

A traced run records one steady window with ``jax.profiler`` and keeps
of it a compact form (also the form of the committed test fixture)::

    {"host":    [[span name, start_ns, duration_ns], ...],   # bench.* spans
     "devices": {"TPU:0": [[op name, start_ns, duration_ns], ...], ...},
     "ops":     {op name: opcode, ...},
     "labels":  {op name: short text, ...}}

Device events are the per-operation line ("XLA Ops") of each device
plane, named by HLO instruction. ``ops`` maps instruction names to
opcodes (``custom-call:<target>`` for custom calls, from the compiled
window's HLO text), so that kernels are told apart from other ops by
what they are; ``labels`` keeps a short text of each (name, result type,
opcode) for the breakdown. Loops and calls (``CONTAINERS``) hold other
ops and count neither as busy time nor in the breakdown.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

MOSAIC = "custom-call:tpu_custom_call"

_NAME = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*")
_OPCODE = re.compile(r"\s*([\w\-]+)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def _skip_type(line: str, i: int) -> int:
    """Index just past the result type that starts at ``line[i]``: a
    tuple in balanced parentheses, or one token."""
    if line.startswith("(", i):
        depth = 0
        for j in range(i, len(line)):
            depth += {"(": 1, ")": -1}.get(line[j], 0)
            if depth == 0:
                return j + 1
        return len(line)
    j = line.find(" ", i)
    return len(line) if j < 0 else j


def hlo_opcodes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> opcode of every instruction in an HLO module's
    text (``Compiled.as_text()``)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _NAME.match(line)
        if not m:
            continue
        op = _OPCODE.match(line, _skip_type(line, m.end()))
        if not op:
            continue
        name, op = m.group(1), op.group(1)
        if op == "custom-call":
            t = _TARGET.search(line)
            op = f"custom-call:{t.group(1) if t else '?'}"
        out[name] = op
    return out


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


CONTAINERS = ("while", "conditional", "call")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def parse_event(text: str) -> Tuple[str, str, str]:
    """(instruction name, opcode, short label) of a device op event, whose
    name on a TPU is the instruction's HLO text ("%fusion.4 = bf16[8,2]{..}
    fusion(...), ..."). Other names pass through as their own opcode."""
    m = _NAME.match(text)
    op = m and _OPCODE.match(text, _skip_type(text, m.end()))
    if not op:
        return text, text, text
    head = _LAYOUT.sub("", text[:op.end() - 1])
    return m.group(1), op.group(1), head.lstrip("%")[:160]


def compact(xplane_path: str, span_prefix: str = "bench.") -> Dict:
    """The compact form of a profiler trace (see the module docstring):
    the ops of each device plane's "XLA Ops" line and the host spans
    whose names start with ``span_prefix``."""
    import jax
    data = jax.profiler.ProfileData.from_file(xplane_path)
    host, devices, ops, labels = [], {}, {}, {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            events = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    name, op, label = parse_event(e.name)
                    ops.setdefault(name, op)
                    labels.setdefault(name, label)
                    events.append([name, float(e.start_ns),
                                   float(e.duration_ns)])
            if events:
                devices[plane.name[len("/device:"):]] = events
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith(span_prefix))
    return {"host": host, "devices": devices, "ops": ops,
            "labels": labels}


# -- interval arithmetic ----------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: Iterable[Interval], lo: float,
         hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def subtract(base: Sequence[Interval],
             cut: Sequence[Interval]) -> List[Interval]:
    """Parts of the union ``base`` not covered by the union ``cut``."""
    out, j = [], 0
    for a, b in base:
        cur = a
        while j < len(cut) and cut[j][1] <= cur:
            j += 1
        k = j
        while k < len(cut) and cut[k][0] < b:
            if cut[k][0] > cur:
                out.append((cur, cut[k][0]))
            cur = max(cur, cut[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


# -- views the metric readers share -------------------------------------------

def window(trace: Dict, span: str = "bench.window") -> Interval:
    """The traced window: the host span around the traced window call."""
    spans = [(s, s + d) for n, s, d in trace["host"] if n == span]
    if not spans:
        raise ValueError(f"no {span} span in the trace")
    return min(a for a, _ in spans), max(b for _, b in spans)


def opcode(trace: Dict, name: str) -> str:
    """An op's opcode; for a name the HLO map lacks, the name less its
    numeric suffix."""
    op = trace.get("ops", {}).get(name)
    return op if op is not None else re.sub(r"\.\d+$", "", name)


def device_intervals(trace: Dict, dev: str, keep=None) -> List[Interval]:
    """Union of the op intervals of device ``dev`` inside the window,
    of the ops whose opcode ``keep(opcode, name)`` accepts (all when
    None)."""
    lo, hi = window(trace)
    iv = [(s, s + d) for n, s, d in trace["devices"][dev]
          if opcode(trace, n) not in CONTAINERS
          and (keep is None or keep(opcode(trace, n), n))]
    return union(clip(iv, lo, hi))


def busy_s(trace: Dict) -> float:
    """Seconds in which an op ran, averaged over the devices."""
    devs = list(trace["devices"])
    return sum(length(device_intervals(trace, d)) for d in devs) \
        / len(devs) / 1e9


def window_s(trace: Dict) -> float:
    lo, hi = window(trace)
    return (hi - lo) / 1e9


def host_label(trace: Dict, t: float) -> str:
    """What the host was doing at ``t``: the innermost bench span that
    holds it, less its ``bench.`` prefix (``window`` alone means the
    window call was waiting on the device, its loss read)."""
    best = None
    for n, s, d in trace["host"]:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (n, d)
    if best is None:
        return "between_windows"
    name = best[0][len("bench."):]
    return "loss_read" if name == "window" else name


def breakdown(trace: Dict, top: int = 10) -> Dict:
    """The device ops that took most time (seconds summed over the
    window, averaged over devices; loops and calls, which hold other ops,
    left out) and the longest idle gaps of the first device, each named
    by what the host was doing in it."""
    lo, hi = window(trace)
    devs = sorted(trace["devices"])
    per_op: Dict[str, float] = {}
    labels = trace.get("labels", {})
    for dev in devs:
        for n, s, d in trace["devices"][dev]:
            part = min(s + d, hi) - max(s, lo)
            if part > 0 and opcode(trace, n) not in CONTAINERS:
                key = labels.get(n, n)
                per_op[key] = per_op.get(key, 0.0) + part / 1e9 / len(devs)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    busy = device_intervals(trace, devs[0])
    gaps = subtract([(lo, hi)], busy)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[host_label(trace, (a + b) / 2), (b - a) / 1e9]
                          for a, b in gaps]}


def kernel_kind(name: str) -> str:
    """A Mosaic call's kernel: its instruction name less the suffix."""
    return re.sub(r"\.\d+$", "", name)


def op_counts(trace: Dict, dev: str) -> Dict[str, int]:
    """Events per op of device ``dev`` inside the window."""
    lo, hi = window(trace)
    counts: Dict[str, int] = {}
    for n, s, d in trace["devices"][dev]:
        if s + d > lo and s < hi:
            counts[n] = counts.get(n, 0) + 1
    return counts


def complete(trace: Dict, steps: int) -> bool:
    """Whether the trace holds every event of the window's steps. The
    profiler can drop a run of events (seen on the chip: a 0.6 s stretch
    of one step missing); every op of the step body then shows a count
    that is no multiple of ``steps``, where whole traces hold each op
    once per window or a multiple of ``steps`` times."""
    return all(c == 1 or c % steps == 0
               for dev in trace["devices"]
               for c in op_counts(trace, dev).values())


def steady(trace: Dict, window_s_expected: float,
           slack: float = 0.03) -> bool:
    """Whether the traced window kept the pace of the untraced ones: no
    longer than ``window_s_expected`` (a window at the measured rate) by
    more than ``slack``. The profiler can stall the device with every
    event kept (seen on the chip: 1.43 s idle inside a 13.2 s window, an
    idle share of 10.1% against 0.35%), which ``complete`` cannot see."""
    return window_s(trace) <= window_s_expected * (1.0 + slack)


def mosaic_seconds(trace: Dict, steps: int) -> Dict[str, float]:
    """Device seconds per step of each Mosaic kernel, averaged over the
    devices: each of its instructions' mean event time, times the calls
    per step its event count gives (so a dropped event leaves the time
    per call unbiased)."""
    lo, hi = window(trace)
    devs = list(trace["devices"])
    by_kind: Dict[str, float] = {}
    for dev in devs:
        times: Dict[str, List[float]] = {}
        for n, s, d in trace["devices"][dev]:
            if opcode(trace, n) == MOSAIC and s + d > lo and s < hi:
                times.setdefault(n, []).append(d)
        counts = op_counts(trace, dev)
        for n, ds in times.items():
            if counts.get(n, 0) == 0:
                continue
            per_step = max(1, round(counts[n] / steps))
            k = kernel_kind(n)
            by_kind[k] = by_kind.get(k, 0.0) + sum(ds) / len(ds) \
                * per_step / 1e9 / len(devs)
    return by_kind


def host_span_seconds(trace: Dict, span: str) -> float:
    lo, hi = window(trace)
    return length(clip([(s, s + d) for n, s, d in trace["host"]
                        if n == span], lo, hi)) / 1e9


def roofline_pct(ctx: Dict, kinds: Sequence[str]) -> Optional[float]:
    """The kernels ``kinds``' share of their HBM roofline, in percent:
    the bytes they need per step (``ctx["kernel_bytes"]``) at the chip's
    peak bandwidth, over their device seconds per step. None where the
    trace holds none of them or the count does not cover them all."""
    by_kind = mosaic_seconds(ctx["trace"], ctx["steps"])
    need = ctx["kernel_bytes"]
    if not kinds or any(k not in by_kind or k not in need for k in kinds):
        return None
    secs = sum(by_kind[k] for k in kinds)
    least = sum(need[k] for k in kinds) / ctx["peaks"].hbm_bytes_per_s
    return 100.0 * least / secs
