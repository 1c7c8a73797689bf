"""The program's device scopes and host spans, on the tiny cell (CPU).

Device scopes: in the compiled window every matmul is in ``model``, the
work of the pool's pack, of the master pack and of the update is in its
``gf.*`` scope, every collective on four virtual chips is in
``gf.reduce``, and the scopes change the program's metadata alone. Host
spans: under the profiler, the loop's waits on a slow source show as
``repro.data.get`` inside ``repro.loop.batches`` inside the benchmark's
``bench.batches``, on one clock."""
from __future__ import annotations

import collections
import contextlib
import json
import os
import re
import subprocess
import sys
import textwrap
import time

import pytest

import bench_tiny
from bench import scopes, tracing

COLLECTIVES = {"all-reduce", "all-reduce-start", "reduce-scatter",
               "all-gather", "all-gather-start", "collective-permute",
               "collective-permute-start", "all-to-all"}
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def build(root, chips: int = 1):
    """(trainer, TrainConfig, mesh, config, traffic) of the tiny cell."""
    from bench import harness
    from bench.spec import Spec
    bench_tiny.make_root(str(root), chips=chips, limits=bench_tiny.LIMITS)
    spec = Spec(str(root))
    config, traffic = spec.config("tiny"), spec.traffic("tiny")
    return harness.build(config, traffic) + (config, traffic)


@contextlib.contextmanager
def no_persistent_cache():
    """JAX's persistent cache keys a program by its IR less debug info, so
    a program that differs from a cached one in scopes alone would come
    back with the cached one's metadata: compile with the cache off."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def window_hlo(root, chips: int = 1) -> str:
    """The compiled text of the tiny cell's window program."""
    import jax
    from repro.launch import train
    from repro.parallel.collectives import compat_set_mesh
    trainer, cfg, mesh, _, _ = build(root, chips)
    K = cfg.window_steps
    with compat_set_mesh(mesh):
        batch = trainer.abstract_train_batch()
        batches = jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct((K,) + s.shape, s.dtype,
                                               sharding=sh),
            batch, train.window_sharding(trainer, batch))
        lowered = trainer.build_train_window(K).lower(
            trainer.abstract_state(), batches)
        with no_persistent_cache():
            return lowered.compile().as_text()


def _marked(fn, marker):
    """``fn`` with its work in an extra scope ``marker``, which names the
    work by the function that does it, whatever scope it is called in."""
    import jax

    def wrapped(*a, **k):
        with jax.named_scope(marker):
            return fn(*a, **k)
    return wrapped


@pytest.fixture(scope="module")
def windows(tmp_path_factory):
    """The window's HLO text with the program's scopes and three markers
    (the pool's pack of the gradients into the staging buffer, its pack
    of the params, the optimizer's span update), and with every
    ``jax.named_scope`` made a no-op."""
    import jax
    from repro import optim
    from repro.core.pool import GradientPool
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(GradientPool, "pack_into",
                   _marked(GradientPool.pack_into, "probe.grad_pack"))
        mp.setattr(GradientPool, "pack",
                   _marked(GradientPool.pack, "probe.param_pack"))
        mp.setattr(optim, "update_view",
                   _marked(optim.update_view, "probe.update"))
        marked = window_hlo(tmp_path_factory.mktemp("marked"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        plain = window_hlo(tmp_path_factory.mktemp("plain"))
    return marked, plain


def _computations_holding(text, opcodes):
    """Names of the computations of ``text`` that hold an instruction
    with one of ``opcodes``."""
    out, comp = set(), None
    for line in text.splitlines():
        m = scopes._NAME.match(line)
        if not m:
            h = scopes._HEADER.match(line)
            if h and line.rstrip().endswith("{"):
                comp = h.group(1)
            continue
        if any(f" {op}(" in line for op in opcodes):
            out.add(comp)
    return out


def test_every_matmul_is_in_the_model_scope(windows):
    text, _ = windows
    ops = tracing.hlo_opcodes(text)
    got = scopes.hlo_scopes(text)
    matmuls = [n for n, op in ops.items() if op in ("dot", "convolution")]
    assert matmuls
    with_dot = _computations_holding(text, ("dot", "convolution"))
    fusions = [m.group(2) for m in map(scopes._NAME.match,
                                       text.splitlines())
               if m and " fusion(" in m.string
               and re.search(r"calls=%?([\w.\-]+)", m.string).group(1)
               in with_dot]
    for name in matmuls + fusions:
        assert got.get(name, (None,))[0] == "model", name
    # both directions are there
    assert {got[n][1] for n in matmuls} == {False, True}


@pytest.mark.parametrize("marker,scope", [
    ("probe.grad_pack", "gf.pack"), ("probe.param_pack", "gf.master_pack"),
    ("probe.update", "gf.update")])
def test_the_work_of_each_phase_is_in_its_scope(windows, marker, scope):
    text, _ = windows
    tagged = {scopes.scope_of(n)
              for n in _OP_NAME.findall(text) if marker in n}
    assert tagged == {(scope, False)}


def test_scopes_change_metadata_alone(windows):
    marked, plain = windows
    assert scopes.hlo_scopes(plain) == {}
    assert scopes.hlo_scopes(marked)
    assert collections.Counter(tracing.hlo_opcodes(marked).values()) == \
        collections.Counter(tracing.hlo_opcodes(plain).values())


def test_every_collective_is_in_the_reduce_scope(tmp_path):
    """On four virtual chips (a 4x1 data mesh), every collective of the
    window, the gradient exchange and the loss's mean, is in
    ``gf.reduce``."""
    script = textwrap.dedent(f"""
        import json, os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, {os.path.dirname(__file__)!r})
        from bench import scopes, tracing
        import test_program_scopes as t
        text = t.window_hlo({str(tmp_path)!r}, chips=4)
        got = scopes.hlo_scopes(text)
        print(json.dumps([[n, op, got.get(n)] for n, op in
                          tracing.hlo_opcodes(text).items()
                          if op in t.COLLECTIVES]))
    """)
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=600,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-4000:]
    found = json.loads(proc.stdout.strip().splitlines()[-1])
    assert found
    assert {op for _, op, _ in found} & {"all-reduce", "all-reduce-start"}
    for name, op, scope in found:
        assert scope == ["gf.reduce", False], (name, op, scope)


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[1] + inner[2] <= \
        outer[1] + outer[2]


def test_host_spans_nest_on_one_clock(tmp_path):
    """A window over a source that takes 50 ms a batch, traced after the
    first: the loop waits on the prefetch queue, and its waits sit in its
    own ``repro.loop.batches`` inside the benchmark's ``bench.batches``;
    the dispatch and the loss read sit in the window call."""
    import jax
    from bench import harness, traffic as traffic_mod
    from repro.data.pipeline import DataPipeline
    from repro.launch import train
    from repro.parallel.collectives import compat_set_mesh
    trainer, cfg, mesh, config, traffic = build(tmp_path / "root")
    fast = traffic_mod.source(traffic, config["model"]["vocab_size"],
                              bench_tiny.SEED)

    class Slow:
        def batch(self, *a, **k):
            time.sleep(0.05)
            return fast.batch(*a, **k)

    K = cfg.window_steps
    pipe = DataPipeline(Slow(), cfg.global_batch, cfg.seq_len)
    trace_dir = str(tmp_path / "trace")
    with compat_set_mesh(mesh):
        state = trainer.init_state(jax.random.PRNGKey(0))
        loop = train.WindowLoop(trainer, pipe, K)
        harness._span_batches(loop)
        pipe.start(0)
        try:
            state = loop(0, K, state)
            jax.profiler.start_trace(trace_dir)
            try:
                harness._window(loop, K, K, state)
            finally:
                jax.profiler.stop_trace()
        finally:
            pipe.stop()
    trace = tracing.compact(tracing.newest_xplane(trace_dir),
                            span_prefix=scopes.SPAN_PREFIXES)
    spans = collections.defaultdict(list)
    for span in trace["host"]:
        spans[span[0]].append(span)
    (window,) = spans["bench.window"]
    (ours,) = spans["repro.loop.batches"]
    (theirs,) = spans["bench.batches"]
    assert _inside(ours, theirs) and _inside(theirs, window)
    gets = spans["repro.data.get"]
    assert len(gets) == K
    assert all(_inside(g, ours) for g in gets)
    (place,) = spans["repro.loop.place"]
    assert _inside(place, ours) and place[1] >= max(g[1] for g in gets)
    for name in ("repro.loop.dispatch", "repro.loop.loss_read"):
        (span,) = spans[name]
        assert _inside(span, window) and span[1] >= ours[1] + ours[2]
    assert spans["repro.data.fetch"]
    # at least one batch was made while the loop waited for it
    longest = max(gets, key=lambda g: g[2])
    assert longest[2] > 25e6
    assert tracing.host_label(trace, longest[1] + longest[2] / 2) in \
        ("data.get", "data.fetch")
    from bench.spec import Spec
    wait = Spec().reader("input_queue_wait_ms")({"trace": trace,
                                                 "steps": K})
    assert wait == pytest.approx(sum(g[2] for g in gets) / 1e6 / K)
