"""Device time by program scope, and host time by program span.

The program names the phases of its step with ``jax.named_scope``
(``model``; GradientFlow's ``gf.pack``, ``gf.master_pack``,
``gf.reduce``, ``gf.update``, ``gf.apply_inflight``, ``gf.csc_select``)
and its host work with ``jax.profiler.TraceAnnotation`` spans named
``repro.*``. The scopes reach each compiled instruction's
``metadata={op_name="jit(window)/while/body/.../gf.pack/..."}``; the
backward of the model shows as ``transpose(jvp(model))`` there.

An instruction belongs to the innermost program scope of its
``op_name``, and is backward (``bwd``) where the path holds a
``transpose(``. A fusion carries the metadata of its root, so it belongs
to its root's scope; where the root has none, to the one scope all
scoped instructions of its fused computation share, if they share one.

A compact trace (``bench.tracing``) that holds the program's spans keeps
the ``repro.`` host spans beside the ``bench.`` ones
(``tracing.compact(path, span_prefix=SPAN_PREFIXES)``), and maps the
compiled window's instructions to their scopes under ``"scopes"``::

    {"scopes": {instruction name: [scope, bwd], ...}, ...}

The readers of this module return None where the trace holds no such
map, or no op or span of what they read.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from bench import tracing

SCOPES = ("model", "gf.pack", "gf.master_pack", "gf.reduce", "gf.update",
          "gf.apply_inflight", "gf.csc_select")
SPAN_PREFIXES = ("bench.", "repro.")
# Spans of the prefetch thread: they overlap the training loop's waits
# without being what the loop does.
PRODUCER_SPANS = ("repro.data.fetch",)

Scope = Tuple[str, bool]

_NAME = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*")
_HEADER = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
_OP_NAME = re.compile(r'metadata=\{[^{}]*?\bop_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")


def scope_of(op_name: str) -> Optional[Scope]:
    """(innermost program scope, bwd) of an ``op_name`` path, or None.
    Transformation wrappers are taken off each part of the path:
    ``transpose(jvp(model))`` is the scope ``model``."""
    for part in reversed(op_name.split("/")):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        if part in SCOPES:
            return part, "transpose(" in op_name
    return None


def hlo_scopes(hlo_text: str) -> Dict[str, Scope]:
    """Instruction name -> (scope, bwd) of every instruction of an HLO
    module's text (``Compiled.as_text()``) that lies in a program scope;
    the others are left out."""
    scopes: Dict[str, Scope] = {}
    roots: Dict[str, Optional[Scope]] = {}
    inner: Dict[str, set] = {}
    fusions: Dict[str, str] = {}
    comp = None
    for line in hlo_text.splitlines():
        m = _NAME.match(line)
        if not m:
            h = _HEADER.match(line)
            if h and line.rstrip().endswith("{"):
                comp = h.group(1)
            continue
        name = m.group(2)
        op = _OP_NAME.search(line)
        scope = scope_of(op.group(1)) if op else None
        if m.group(1):
            roots[comp] = scope
        if scope is not None:
            scopes[name] = scope
            inner.setdefault(comp, set()).add(scope)
        elif " fusion(" in line:
            calls = _CALLS.search(line)
            if calls:
                fusions[name] = calls.group(1)
    for name, called in fusions.items():
        scope = roots.get(called)
        if scope is None and len(inner.get(called, ())) == 1:
            (scope,) = inner[called]
        if scope is not None:
            scopes[name] = scope
    return scopes


def _scope(trace: Dict, name: str) -> Optional[Scope]:
    s = trace["scopes"].get(name)
    return None if s is None else (s[0], bool(s[1]))


def scope_seconds(trace: Dict, steps: int) -> Dict[Scope, float]:
    """Device seconds per step of each (scope, bwd), averaged over the
    devices: the union of its ops' intervals inside the window, so that
    an op is counted once and ops that overlap are not counted twice;
    loops and calls (``tracing.CONTAINERS``) left out."""
    lo, hi = tracing.window(trace)
    devs = list(trace["devices"])
    out: Dict[Scope, float] = {}
    for dev in devs:
        by: Dict[Scope, List[tracing.Interval]] = {}
        for n, s, d in trace["devices"][dev]:
            scope = _scope(trace, n)
            if scope is not None and \
                    tracing.opcode(trace, n) not in tracing.CONTAINERS:
                by.setdefault(scope, []).append((s, s + d))
        for scope, iv in by.items():
            secs = tracing.length(tracing.union(tracing.clip(iv, lo, hi)))
            out[scope] = out.get(scope, 0.0) + secs / 1e9 / steps \
                / len(devs)
    return {k: v for k, v in out.items() if v > 0}


def scope_ms(ctx: Dict, scope: str,
             bwd: Optional[bool] = None) -> Optional[float]:
    """Device milliseconds per step in ``scope`` (its backward part alone
    with ``bwd=True``, its forward part with ``False``, both with None);
    None where the trace maps no scopes or holds no op of it."""
    trace = ctx["trace"]
    if "scopes" not in trace:
        return None
    secs = [v for (name, b), v in scope_seconds(trace, ctx["steps"]).items()
            if name == scope and (bwd is None or b == bwd)]
    return 1e3 * sum(secs) if secs else None


def scoped_share(trace: Dict) -> float:
    """Share of the busy device time in which an op of a program scope
    ran, averaged over the devices."""
    lo, hi = tracing.window(trace)
    shares = []
    for dev in trace["devices"]:
        busy = tracing.device_intervals(trace, dev)
        iv = [(s, s + d) for n, s, d in trace["devices"][dev]
              if _scope(trace, n) is not None
              and tracing.opcode(trace, n) not in tracing.CONTAINERS]
        scoped = tracing.length(tracing.union(tracing.clip(iv, lo, hi)))
        shares.append(scoped / tracing.length(busy) if busy else 0.0)
    return sum(shares) / len(shares)


def unscoped_ops(trace: Dict, top: int = 10) -> List[List]:
    """The ops outside every program scope that took most time: [label,
    seconds over the window averaged over the devices], longest first."""
    lo, hi = tracing.window(trace)
    devs = list(trace["devices"])
    labels = trace.get("labels", {})
    per_op: Dict[str, float] = {}
    for dev in devs:
        for n, s, d in trace["devices"][dev]:
            part = min(s + d, hi) - max(s, lo)
            if part > 0 and _scope(trace, n) is None and \
                    tracing.opcode(trace, n) not in tracing.CONTAINERS:
                key = labels.get(n, n)
                per_op[key] = per_op.get(key, 0.0) + part / 1e9 / len(devs)
    return [[n, s] for n, s in
            sorted(per_op.items(), key=lambda kv: -kv[1])[:top]]


def span_ms(ctx: Dict, span: str) -> Optional[float]:
    """Host milliseconds per step in the spans named ``span`` inside the
    traced window; None where the trace holds none."""
    trace = ctx["trace"]
    if not any(n == span for n, _, _ in trace["host"]):
        return None
    return 1e3 * tracing.host_span_seconds(trace, span) / ctx["steps"]


def breakdown(trace: Dict, top: int = 10) -> Dict:
    """``tracing.breakdown`` with each idle gap named by the innermost
    span of the training loop that holds it, less its prefix: a wait on
    the prefetch queue reads ``data.get``, the stacking and placing of a
    window's batches ``loop.place``. The prefetch thread's spans, which
    overlap those waits, name no gap."""
    loop = [h for h in trace["host"] if h[0] not in PRODUCER_SPANS]
    return tracing.breakdown(dict(trace, host=loop), top)
