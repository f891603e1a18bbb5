"""The program's own span tree (ISSUE 26): parent/id/self time, spans on
the ``jax.profiler`` host plane, the serving tick's tree, the train
window's spans, and the names the programs and kernels carry in a device
trace."""
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import analysis
from autodist_tpu import metrics as M
from autodist_tpu.obs import spans as obs_spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------- parent, id, self time
def test_nested_spans_know_their_parent():
    tracer = obs_spans.SpanTracer()
    with tracer.span("outer", k=1) as attrs:
        with tracer.span("a"):
            with tracer.span("a.inner"):
                pass
        with tracer.span("b"):
            pass
        attrs["late"] = True
    by_name = {s.name: s for s in tracer.spans()}
    assert len({s.id for s in tracer.spans()}) == 4
    assert by_name["outer"].parent is None
    assert by_name["a"].parent == by_name["b"].parent == by_name["outer"].id
    assert by_name["a.inner"].parent == by_name["a"].id
    assert by_name["outer"].attrs == {"k": 1, "late": True}
    ev = by_name["a"].to_event()
    assert ev["args"]["id"] == by_name["a"].id
    assert ev["args"]["parent"] == by_name["outer"].id
    retro = tracer.add_span("wait", time.time(), 0.5, parent=by_name["b"].id)
    assert retro.parent == by_name["b"].id and retro.id not in {
        s.id for s in by_name.values()}


def test_parent_is_per_thread():
    tracer = obs_spans.SpanTracer()
    inside = threading.Event()
    release = threading.Event()

    def other():
        with tracer.span("other.top"):
            inside.set()
            release.wait(10)

    t = threading.Thread(target=other)
    with tracer.span("main.top"):
        t.start()
        assert inside.wait(10)
        with tracer.span("main.child"):
            pass
        release.set()
        t.join()
    by_name = {s.name: s for s in tracer.spans()}
    assert by_name["other.top"].parent is None
    assert by_name["main.child"].parent == by_name["main.top"].id
    # the stack unwinds: a span opened afterwards is a root again
    with tracer.span("after"):
        pass
    assert tracer.spans()[-1].parent is None


def test_span_closed_by_an_exception_still_unwinds():
    tracer = obs_spans.SpanTracer()
    with pytest.raises(RuntimeError):
        with tracer.span("boom"):
            raise RuntimeError("x")
    with tracer.span("next"):
        pass
    boom, nxt = tracer.spans()
    assert boom.attrs == {"error": True} and nxt.parent is None


def _span(name, start, dur, sid, parent=None):
    return obs_spans.Span(name=name, t_start_s=start, dur_s=dur, trace_id="t",
                          process=0, os_pid=1, tid=1, id=sid, parent=parent)


def test_self_time_takes_the_children_out_once():
    spans = [
        _span("tick", 10.0, 1.0, 1),
        _span("dispatch", 10.1, 0.3, 2, parent=1),
        _span("fetch", 10.3, 0.3, 3, parent=1),      # overlaps dispatch 0.1
        _span("late", 10.9, 0.5, 4, parent=1),       # reaches past the parent
        _span("leaf", 10.15, 0.1, 5, parent=2),
        _span("orphan", 20.0, 0.2, 6, parent=99),
    ]
    own = obs_spans.self_time(spans)
    assert own[1] == pytest.approx(1.0 - 0.5 - 0.1)
    assert own[2] == pytest.approx(0.2)
    assert own[3] == pytest.approx(0.3)
    assert own[5] == pytest.approx(0.1)
    assert own[6] == pytest.approx(0.2)


# ------------------------------------------------------- the profiler's clock
def test_spans_module_never_imports_jax(monkeypatch):
    """``obs/spans.py`` uses a jax that is already imported and never
    imports one. (The package's ``__init__`` imports jax at the parent
    commit already, so ``import autodist_tpu.obs.spans`` as a statement
    cannot show it: the module's own imports and a span opened with no
    jax in ``sys.modules`` do.)"""
    import ast

    with open(obs_spans.__file__, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert "jax" not in imported
    monkeypatch.delitem(sys.modules, "jax")
    tracer = obs_spans.SpanTracer()
    with tracer.span("no.jax"):
        assert "jax" not in sys.modules
    assert [s.name for s in tracer.spans()] == ["no.jax"]
    assert "jax" not in sys.modules


def test_only_spans_opens_a_trace_annotation():
    hits = []
    for base, _, files in os.walk(os.path.join(ROOT, "autodist_tpu")):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(base, name)
                with open(path, encoding="utf-8") as f:
                    if "TraceAnnotation(" in f.read():
                        hits.append(os.path.relpath(path, ROOT))
    assert hits == [os.path.join("autodist_tpu", "obs", "spans.py")]


def test_span_shows_on_the_profilers_host_plane(tmp_path):
    from perfbench.harness import trace as bench_trace

    tracer = obs_spans.SpanTracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.span("probe.outer"):
            with tracer.span("probe.inner"):
                jnp.ones((64, 64)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    tr = bench_trace.Trace.from_file(bench_trace.find_xplane(str(tmp_path)))
    found = {name: (start, start + dur) for name, start, dur in tr.host_events
             if name in ("probe.outer", "probe.inner")}
    assert set(found) == {"probe.outer", "probe.inner"}
    assert (found["probe.outer"][0] <= found["probe.inner"][0]
            <= found["probe.inner"][1] <= found["probe.outer"][1])
    # the ring holds the same two, parented
    outer, = [s for s in tracer.spans() if s.name == "probe.outer"]
    inner, = [s for s in tracer.spans() if s.name == "probe.inner"]
    assert inner.parent == outer.id


# ---------------------------------------------------------- the serving tick
TICK_CHILDREN = {"serve.admit", "serve.prefill_chunk", "serve.decode_step",
                 "serve.emit", "serve.tick_metrics", "serve.token_fetch"}


@pytest.fixture(scope="module")
def tiny_engine():
    from autodist_tpu.serve.server import _tiny_engine

    engine, _, _ = _tiny_engine(n_slots=4, n_pages=33)
    return engine


def test_one_request_yields_the_tick_tree(tiny_engine):
    from autodist_tpu.serve.batcher import ContinuousBatcher, RequestState

    tracer = obs_spans.get_tracer()
    tracer.clear()
    seen = []
    batcher = ContinuousBatcher(tiny_engine, registry=M.MetricsRegistry(),
                                on_tick=seen.append)
    batcher.start()
    try:
        # 20 prompt tokens at a chunk of 8: two dispatch-only chunks, then
        # the final one, which fetches the first token.
        req = batcher.submit(np.arange(1, 21, dtype=np.int32), 4)
        assert req.wait(120.0).state is RequestState.DONE
    finally:
        batcher.stop()
    spans = tracer.spans()
    by_id = {s.id: s for s in spans}
    ticks = [s for s in spans if s.name == "serve.tick"]
    assert len(ticks) >= len(seen) == 5      # 3 prefill ticks, 2 more decode
    assert [t.attrs["seq"] for t in ticks if t.attrs["progressed"]][:5] == [
        0, 1, 2, 3, 4]

    def children(parent):
        return [s for s in spans if s.parent == parent.id]

    def inside(child, parent):
        eps = 1e-3   # start is wall clock, duration perf_counter
        return (parent.t_start_s - eps <= child.t_start_s and
                child.t_start_s + child.dur_s
                <= parent.t_start_s + parent.dur_s + eps)

    first = ticks[0]
    assert [c.name for c in children(first)] == [
        "serve.admit", "serve.prefill_chunk", "serve.tick_metrics"]
    for s in spans:
        if s.parent is not None and s.name != "serve.queue_wait":
            assert inside(s, by_id[s.parent]), (s.name, by_id[s.parent].name)
        if s.name in TICK_CHILDREN - {"serve.token_fetch"}:
            assert by_id[s.parent].name == "serve.tick", s.name
    chunks = [s for s in spans if s.name == "serve.prefill_chunk"]
    assert [c.attrs["final"] for c in chunks] == [False, False, True]
    # the final chunk's tick: chunk, its fetch, the first token's
    # book-keeping, then a decode round in the same tick
    third = by_id[chunks[2].parent]
    assert [c.name for c in children(third)] == [
        "serve.prefill_chunk", "serve.token_fetch", "serve.emit",
        "serve.decode_step", "serve.emit", "serve.tick_metrics"]
    assert children(third)[1].attrs["program"] == "prefill_chunk"
    step = children(third)[3]
    assert [(c.name, c.attrs.get("program")) for c in children(step)] == [
        ("serve.decode_dispatch", None), ("serve.token_fetch", "decode_step")]
    # each fetch holds the wait for its program and nothing else
    for fetch in (children(third)[1], children(step)[1]):
        assert [(c.name, c.attrs["program"]) for c in children(fetch)] == [
            ("wait.tokens", fetch.attrs["program"])]
    hooks = [s for s in spans if s.name == "serve.on_tick"]
    assert len(hooks) == len(seen) and all(h.parent is None for h in hooks)
    # a tick's self time is what its children leave
    own = obs_spans.self_time(spans)
    assert 0.0 <= own[third.id] <= third.dur_s - sum(
        c.dur_s for c in children(third)) + 1e-6
    # every token has its time, in order, the first at t_first_token
    assert len(req.t_tokens) == len(req.tokens) == 4
    assert req.t_tokens == sorted(req.t_tokens)
    assert req.t_tokens[0] == req.t_first_token


def test_every_token_fetch_holds_one_wait_for_its_program(tiny_engine):
    """Two requests of several chunks: every decode step's fetch and every
    final chunk's fetch has exactly one ``wait.tokens`` child, inside it,
    carrying the fetch's ``program``; no ``wait.tokens`` lies anywhere
    else. Nothing counts the paged kernel's page groups any more."""
    from autodist_tpu.serve.batcher import ContinuousBatcher, RequestState

    tracer = obs_spans.get_tracer()
    tracer.clear()
    registry = M.MetricsRegistry()
    batcher = ContinuousBatcher(tiny_engine, registry=registry)
    batcher.start()
    try:
        reqs = [batcher.submit(np.arange(1, n, dtype=np.int32), 5)
                for n in (21, 12)]
        for r in reqs:
            assert r.wait(120.0).state is RequestState.DONE
    finally:
        batcher.stop()
    spans = tracer.spans()
    by_id = {s.id: s for s in spans}
    fetches = [s for s in spans if s.name == "serve.token_fetch"]
    waits = [s for s in spans if s.name == "wait.tokens"]
    steps = [s for s in spans if s.name == "serve.decode_step"]
    finals = [s for s in spans if s.name == "serve.prefill_chunk"
              and s.attrs["final"]]
    assert len(finals) == 2 and len(steps) >= 4
    assert len(fetches) == len(waits) == len(steps) + len(finals)
    assert sorted(by_id[f.parent].name for f in fetches
                  if f.attrs["program"] == "decode_step") == [
        "serve.decode_step"] * len(steps)
    for fetch in fetches:
        mine = [w for w in waits if w.parent == fetch.id]
        assert len(mine) == 1, fetch.attrs
        wait, = mine
        assert wait.attrs == {"program": fetch.attrs["program"]}
        assert fetch.t_start_s - 1e-3 <= wait.t_start_s
        assert wait.t_start_s + wait.dur_s <= (
            fetch.t_start_s + fetch.dur_s + 1e-3)
        assert wait.dur_s <= fetch.dur_s
    for s in spans:
        assert not {"kv_groups", "kv_groups_live"} & set(s.attrs), s.name
    assert not [n for n in registry.snapshot() if "kv_groups" in n]
    assert not hasattr(tiny_engine, "kv_groups")
    assert not hasattr(tiny_engine, "kv_groups_live")


def test_the_copy_starts_before_the_wait(tiny_engine, monkeypatch):
    """The copy of a program's tokens to the host is started before the
    host waits for the program (``wait.tokens``), so that it runs on the
    device as soon as the program has, as it did when ``jax.device_get``
    alone fetched them: every wait is on an array whose copy was started
    since the wait before it."""
    from jax._src.array import ArrayImpl

    from autodist_tpu.serve.batcher import ContinuousBatcher, RequestState

    events = []
    copy, wait = ArrayImpl.copy_to_host_async, jax.block_until_ready

    def copying(self):
        events.append(("copy", self))
        return copy(self)

    def waiting(x):
        events.append(("wait", x))
        return wait(x)

    monkeypatch.setattr(ArrayImpl, "copy_to_host_async", copying)
    monkeypatch.setattr(jax, "block_until_ready", waiting)
    batcher = ContinuousBatcher(tiny_engine, registry=M.MetricsRegistry())
    batcher.start()
    try:
        req = batcher.submit(np.arange(1, 21, dtype=np.int32), 4)
        assert req.wait(120.0).state is RequestState.DONE
    finally:
        batcher.stop()
    waits = [i for i, (kind, _) in enumerate(events) if kind == "wait"]
    assert len(waits) >= 4      # the final chunk's, then the decode steps'
    since = 0
    for i in waits:
        assert any(kind == "copy" and x is events[i][1]
                   for kind, x in events[since:i]), i
        since = i + 1


def test_span_fields_read_as_before():
    """The ring keeps plain records; what ``spans()`` hands out reads as
    the ``Span`` it always was: the OS pid, the thread id, the role and
    the trace id of the recording process and thread."""
    tracer = obs_spans.SpanTracer(trace_id="abc", process=3)
    with tracer.span("outer", k=1) as attrs:
        attrs["late"] = 2
    t0 = time.time()
    retro = tracer.add_span("retro", t0, 0.25, parent=7, why="x")
    other = []
    t = threading.Thread(target=lambda: other.append(
        (threading.get_ident() % 1_000_000,
         tracer.add_span("other", t0, 0.0))))
    t.start()
    t.join()
    outer, got_retro, got_other = tracer.spans()
    for sp in (outer, got_retro, got_other):
        assert isinstance(sp, obs_spans.Span)
        assert (sp.trace_id, sp.process, sp.os_pid) == ("abc", 3, os.getpid())
        assert isinstance(sp.t_start_s, float) and isinstance(sp.dur_s, float)
    assert outer.tid == threading.get_ident() % 1_000_000
    assert got_other.tid == other[0][0] and got_other == other[0][1]
    assert outer.attrs == {"k": 1, "late": 2} and outer.parent is None
    assert got_retro == retro
    assert (retro.name, retro.t_start_s, retro.dur_s, retro.parent,
            retro.attrs) == ("retro", t0, 0.25, 7, {"why": "x"})
    ev = retro.to_event()
    assert ev["pid"] == os.getpid() and ev["args"]["trace_id"] == "abc"
    assert ev["args"]["process"] == 3 and ev["args"]["why"] == "x"
    # the ring's bound and its count of drops hold as before
    small = obs_spans.SpanTracer(capacity=2)
    for i in range(5):
        small.add_span(f"s{i}", t0, 0.0)
    assert [s.name for s in small.spans()] == ["s3", "s4"]
    assert small.dropped == 3


@pytest.mark.skipif(not hasattr(os, "fork"), reason="needs os.fork")
def test_a_forked_child_records_its_own_pid():
    """After a fork the child's spans carry the child's OS pid (and the
    parent's, recorded before the fork, keep the parent's)."""
    import json
    import warnings

    tracer = obs_spans.SpanTracer(trace_id="fork", process=0)
    tracer.add_span("before", time.time(), 0.0)
    with tracer.span("warm"):
        pass
    read, write = os.pipe()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # jax warns of fork and threads
        pid = os.fork()
    if pid == 0:                                            # the child
        code = 1
        try:
            os.close(read)
            with tracer.span("in.child"):
                pass
            tracer.add_span("retro.child", time.time(), 0.0)
            got = [(s.name, s.os_pid, s.tid) for s in tracer.spans()]
            os.write(write, json.dumps(
                [os.getpid(), threading.get_ident() % 1_000_000, got]).encode())
            code = 0
        finally:
            os._exit(code)
    os.close(write)
    chunks = []
    while True:
        chunk = os.read(read, 1 << 16)
        if not chunk:
            break
        chunks.append(chunk)
    os.close(read)
    _, status = os.waitpid(pid, 0)
    assert os.WIFEXITED(status) and os.WEXITSTATUS(status) == 0
    child_pid, child_tid, got = json.loads(b"".join(chunks))
    assert child_pid == pid != os.getpid()
    assert [(n, p) for n, p, _ in got] == [
        ("before", os.getpid()), ("warm", os.getpid()),
        ("in.child", child_pid), ("retro.child", child_pid)]
    assert got[2][2] == got[3][2] == child_tid
    assert [s.os_pid for s in tracer.spans()] == [os.getpid()] * 2


def test_itl_histogram_holds_every_gap(tiny_engine):
    from autodist_tpu.serve.batcher import ContinuousBatcher, RequestState

    registry = M.MetricsRegistry()
    batcher = ContinuousBatcher(tiny_engine, registry=registry)
    batcher.start()
    try:
        reqs = [batcher.submit(np.arange(1, 6, dtype=np.int32), n)
                for n in (6, 3)]
        for r in reqs:
            assert r.wait(120.0).state is RequestState.DONE
    finally:
        batcher.stop()
    snap = registry.snapshot()
    assert snap["serve_itl_s"]["count"] == (6 - 1) + (3 - 1)
    assert snap["serve_tokens_generated_total"] == 9
    # each request's first token comes from prefill, the rest from decode
    assert snap["serve_decode_tokens_generated_total"] == 9 - 2
    assert "serve_tokens_per_sec" not in snap
    assert "serve_decode_tokens_per_sec" not in snap
    for r in reqs:
        gaps = np.diff(r.t_tokens)
        assert r.itl_s == pytest.approx(gaps.mean(), abs=1e-3)


# ---------------------------------------------------------- the train window
@pytest.fixture(scope="module")
def train_step():
    from autodist_tpu.api import AutoDist
    from autodist_tpu.strategy import AllReduce

    AutoDist.reset_default()
    try:
        autodist = AutoDist(strategy_builder=AllReduce())

        def loss_fn(p, b):
            return ((b["x"] @ p["w"]) ** 2).mean()

        params = {"w": np.ones((3, 1), np.float32)}
        batch = {"x": np.ones((16, 3), np.float32)}
        step = autodist.build(loss_fn, params, batch)
        yield step, step.init(params), batch
    finally:
        AutoDist.reset_default()


def test_train_window_spans(train_step):
    from autodist_tpu.data import DataLoader

    step, state, batch = train_step
    tracer = obs_spans.get_tracer()
    tracer.clear()
    loader = DataLoader({"x": np.ones((64, 3), np.float32)}, batch_size=16,
                        shuffle=False, epochs=1, drop_remainder=True)
    n = 0
    for b in loader.host_batches():
        window = step.plan.window_from_local({"x": b["x"][None]})
        state, _ = step.run(state, window, 1, stacked=True)
        n += 1
    state, _ = step(state, batch)
    names = [s.name for s in tracer.spans()]
    assert n == 4
    assert names.count("input.next") == n + 1     # the last finds the end
    assert names.count("input.stage") == n
    dispatch = [s for s in tracer.spans() if s.name == "train.window_dispatch"]
    assert [(d.attrs["program"], d.attrs["fresh"]) for d in dispatch] == [
        ("run[1/stacked]", True)] + [("run[1/stacked]", False)] * 3 + [
        ("step", True)]
    assert all(d.parent is None for d in dispatch)


# ------------------------------------------- names of programs and kernels
def _module_line(text):
    return text.split("\n", 1)[0]


def test_train_modules_are_named(train_step):
    step, state, batch = train_step
    assert "jit_train_step," in _module_line(
        analysis.compiled_hlo(step, state, batch))
    _, text = analysis.compiled_window(step, state, batch, 2)
    assert "jit_train_window," in _module_line(text)


def test_serving_modules_are_named(tiny_engine):
    e = tiny_engine
    if e._decode_fn is None:
        e._compile()
    cache = jax.eval_shape(lambda: e._cache)
    # the decode step's operands as the engine keeps them on the device
    # (its outputs end with the next step's tokens and lengths)
    args = (e.params, e._mirror(("tokens", None)), e._mirror(("lengths", None)),
            cache, e._mirror(("tables", None)), e._mirror(("samp", None)))
    decode = analysis.compiled_text(e._decode_fn, *args)
    assert "jit_serve_decode_step," in _module_line(decode)
    *_, tokens, lengths = jax.eval_shape(e._decode_fn, *args)
    assert tokens.shape == lengths.shape == (e.n_slots,)
    # a chunk's one int32 operand: its tokens, then start, length and row
    chunk = np.zeros(e.prefill_chunk + 3, np.int32)
    chunk[e.prefill_chunk:] = 0, 5, 0
    prefill = analysis.compiled_text(
        e._prefill_fn, e.params, chunk, cache, e._mirror(("table", 0)),
        e._mirror(("samp", 0)))
    assert "jit_serve_prefill_chunk," in _module_line(prefill)
    copy = e._make_page_copy_fn(e.pool.n_pages, e._cache_sh)
    assert "jit_serve_cow_copy," in _module_line(analysis.compiled_text(
        copy, cache, jnp.int32(1), jnp.int32(2)))


def test_spec_and_eval_functions_are_named():
    """The module name is ``jit_`` + the function's name: these programs
    are pinned by name without compiling them again."""
    import inspect

    from autodist_tpu.kernel import lowering
    from autodist_tpu.serve import spec

    src = inspect.getsource(spec.SpecDecodeEngine._compile_spec)
    for name in ("serve_spec_verify", "serve_draft_prefill",
                 "serve_draft_decode"):
        assert f"def {name}(" in src and f"{name}, donate_argnums" in src
    assert "lambda" not in src
    src = inspect.getsource(lowering.DistributedTrainStep)
    assert "def eval_step(" in src and "eval_step,\n" in src


# The kernels' names as a device trace shows them: compiled for a described
# v5e with the chip's own compiler (no chip needed). The topology is
# described inside a fixture, never at import (one libtpu per process).
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _custom_calls(text):
    """Names of the instructions that are Mosaic calls."""
    import re

    return re.findall(r"^\s*(?:ROOT )?%(\S+) = .*custom_call_target=\"tpu_custom_call\"",
                      text, flags=re.M)


def test_kernel_names_in_the_compiled_programs(one_chip):
    import importlib

    F = importlib.import_module("autodist_tpu.ops.flash_attention")
    PA = importlib.import_module("autodist_tpu.ops.paged_attention")

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def train_window(q, k, v):
        return jax.grad(lambda *a: F.flash_attention(
            *a, True, 128, 128, False).astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)

    qkv = sds((2, 1024, 16, 64))
    calls = _custom_calls(analysis.compiled_text(
        jax.jit(train_window), qkv, qkv, qkv))
    assert len(calls) == 3
    for want in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
        assert sum(want in c for c in calls) == 1, calls

    def serve_decode_step(q, kp, vp, tables, pos):
        return PA.paged_decode_attention(q, kp, vp, tables, pos,
                                         impl="kernel", interpret=False)

    pages = sds((65, 16, 25 * 64))
    calls = _custom_calls(analysis.compiled_text(
        jax.jit(serve_decode_step), sds((4, 25, 64)), pages, pages,
        sds((4, 16), jnp.int32), sds((4,), jnp.int32)))
    assert len(calls) == 1 and calls[0].startswith("paged_attention"), calls


@pytest.mark.parametrize("shape, dtype", [
    ((12, 1024, 16, 64), jnp.bfloat16),    # medium-train-1chip
    ((8, 1024, 25, 64), jnp.bfloat16),     # gpt2-xl's 25 heads
    ((1, 8192, 8, 128), jnp.bfloat16),     # a long sequence: operands crowd VMEM
    ((2, 1152, 4, 64), jnp.float32),       # 9 x 128: the largest tile does not divide it
], ids=["medium", "xl-heads", "seq8192", "seq1152-f32"])
def test_derived_flash_tiles_compile_for_the_chip(one_chip, shape, dtype):
    """The tiles and heads per step the kernel derives from the shape fit the
    chip's VMEM and pass Mosaic, and the calls keep the first operand the
    benchmark's roofline reader finds them by."""
    import importlib

    F = importlib.import_module("autodist_tpu.ops.flash_attention")
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def grads(q, k, v):
        return jax.grad(lambda *a: F.flash_attention(
            *a, True, None, None, False).astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)

    text = analysis.compiled_text(jax.jit(grads), x, x, x)
    calls = _custom_calls(text)
    assert len(calls) == 3, calls
    for want in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
        assert sum(want in c for c in calls) == 1, calls
    b, s, h, d = shape
    tile = F._tiles(s, d, dtype)[0]
    assert s % tile == 0 and tile % 128 == 0
    assert F._step_bytes(F._heads_per_step(b * h, tile, s, d, jnp.dtype(dtype).itemsize),
                         tile, s, d, jnp.dtype(dtype).itemsize) <= F._VMEM_BYTES


@pytest.mark.parametrize("program", ["decode-step", "prefill-chunk"])
def test_recurrent_state_stays_where_it_lies_in_the_serving_programs(one_chip, monkeypatch,
                                                                     program):
    """Nemotron-H's published widths (a Mamba, an attention and an expert
    layer), 16 rows, the cache and the per-slot state donated, the real
    Mosaic calls. A Mamba layer's state leaf ``[rows, 64, 64, 128]`` float32
    is a parameter and is updated in place: by the call named
    ``ssm_state_update``, whose output is aliased onto it (decode), or by a
    dynamic-update-slice of the chunk's row (a chunk). The compiled
    program holds no copy or other fusion of a leaf's size; the decode
    program holds one state call a Mamba layer, one paged call an attention
    layer and two grouped products an expert layer (relu², no gate), and
    no copy of the experts' weights as ``serving_params`` holds them."""
    from autodist_tpu.models import nemotron_h as N
    from autodist_tpu.models import routed
    from autodist_tpu.ops import grouped_matmul as GM
    from autodist_tpu.ops import paged_attention as PA
    from autodist_tpu.ops import ssm as SSM

    for mod in (PA, GM, SSM):
        monkeypatch.setattr(mod, "_should_interpret", lambda: False)
    monkeypatch.setattr(routed, "resolve", lambda choice, off_chip: "kernel")  # as on the chip
    pages, rows, table = 129, 16, 16
    cfg = N.NemotronHConfig(vocab_size=16384, hybrid_override_pattern="M*E",
                            experts_held=(0, 16), max_position_embeddings=2048)

    def described(tree, dtype=None):
        return jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(
            x.shape, dtype or x.dtype, sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = described(jax.eval_shape(lambda: N.serving_params(
        N.init_params(jax.random.PRNGKey(0), cfg), cfg)), jnp.bfloat16)
    cache = described(jax.eval_shape(lambda: N.init_paged_cache(cfg, pages, cfg.page_len)))
    state = described(jax.eval_shape(lambda: N.init_slot_state(cfg, rows)))

    def serve_decode_step(params, tokens, positions, cache, tables, state):
        return N.forward_paged_decode_step(params, tokens, positions, cache, tables,
                                           cfg, state=state)

    def serve_prefill_chunk(params, tokens, start, length, cache, table_, state, slot):
        return N.forward_paged_prefill_chunk(params, tokens, start, length, cache,
                                             table_, cfg, state=state, slot=slot)

    fn, args, donated = {
        "decode-step": (serve_decode_step,
                        (params, i32(rows), i32(rows), cache, i32(rows, table), state),
                        (3, 5)),
        "prefill-chunk": (serve_prefill_chunk,
                          (params, i32(1, cfg.prefill_chunk), i32(), i32(), cache,
                           i32(table), state, i32()), (4, 6)),
    }[program]
    text = analysis.compiled_text(jax.jit(fn, donate_argnums=donated), *args)

    calls = sorted(c.split(".")[0] for c in _custom_calls(text))
    own = ["paged_attention", "ssm_state_update"] if program == "decode-step" \
        else ["paged_attention"]
    assert calls == ["gmm"] * 2 + own, calls
    found = _leaf_sized(text, f"f32[{rows},64,64,128]")
    in_place = {"parameter", "tuple", "get-tuple-element", "bitcast",
                "dynamic-update-slice"}
    for op, line in found:
        if op == "fusion":
            assert "dynamic-update-slice" in line or "dynamic_update_slice" in line, \
                f"a fusion of the state's size, no write of a row: {line[:300]}"
        elif op == "custom-call":
            assert "ssm_state_update" in line, line[:300]
        else:
            assert op in in_place, f"{op} of the state's size: {line[:300]}"
    assert sum(op == "custom-call" for op, _ in found) == (program == "decode-step")
    # the held experts as the served tree holds them (width 1,856 padded to
    # 1,920) are read where they lie, not re-laid out a run
    assert not [op for op, _ in _leaf_sized(text, "bf16[16,2688,1920]") if op == "copy"]


@pytest.mark.parametrize("rows, queries", [(4, 1), (1, 1024)],
                         ids=["decode-step", "prefill-chunk"])
def test_eva_kernel_compiles_for_the_chip_at_published_widths(one_chip, rows, queries):
    """``eva_paged_attention`` at 32 heads of 128, pages of 16 heads-major, a
    table of 128 ring + 128 summary pages and a pool of four rows: Mosaic
    takes the page groups and head blocks the kernel derives, the call keeps
    the name the benchmark's readers find it by, and nothing the size of a
    pool leaf is copied around it."""
    import importlib

    PA = importlib.import_module("autodist_tpu.ops.paged_attention")

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def attend(q, kp, vp, tables, pos):
        return PA.eva_paged_attention(q, kp, vp, tables, pos, ring_pages=128,
                                      window=2048, impl="kernel", interpret=False)

    pool = sds((1025, 32, 16, 128))
    text = analysis.compiled_text(
        jax.jit(attend), sds((rows, 32, queries, 128)), pool, pool,
        sds((rows, 256), jnp.int32), sds((rows, queries), jnp.int32))
    calls = _custom_calls(text)
    assert len(calls) == 1 and calls[0].startswith("eva_paged_attention"), calls
    import re

    assert not re.search(r"= bf16\[1025,32,16,128\]\S* copy\(", text)


# (rows, queries a row, heads, head_dim, table width, pages' type)
PAGED_SHAPES = {
    "bfloat16": {
        "xl-decode": (4, 1, 25, 64, 64, jnp.bfloat16),
        "xl-chunk": (1, 16, 25, 64, 64, jnp.bfloat16),
        "xl-verify": (4, 5, 25, 64, 64, jnp.bfloat16),
        "xl-chunk-of-64": (1, 64, 25, 64, 64, jnp.bfloat16),
        "medium-decode": (8, 1, 16, 64, 64, jnp.bfloat16),
        "prime-width": (4, 1, 25, 64, 61, jnp.bfloat16),
    },
    "int8-and-float32": {
        "xl-decode-int8": (4, 1, 25, 64, 64, jnp.int8),
        "xl-chunk-int8": (1, 16, 25, 64, 64, jnp.int8),
        "4x128-f32": (4, 1, 4, 128, 64, jnp.float32),
    },
}


@pytest.mark.parametrize("shapes", PAGED_SHAPES.values(), ids=PAGED_SHAPES.keys())
def test_paged_kernel_compiles_for_the_chip_as_blocked(one_chip, shapes):
    """The groups and query tiles ``paged_blocking`` derives pass Mosaic and
    fit the VMEM a kernel may take unasked, at the widths the chip serves:
    bfloat16, float32 and int8 pages, one query a row, a chunk, a verify
    window, a chunk of several tiles, a table no group divides. (Two cases
    and not nine: this file stays smaller than ``test_attrib.py``, whose
    profile fixture finds no device plane in a process that has described a
    TPU, and the workers take files largest first.)"""
    from autodist_tpu.ops import paged_attention as PA

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def attend(q, kp, vp, tables, pos, *scales):
        ks, vs = scales if scales else (None, None)
        return PA.paged_verify_attention(q, kp, vp, tables, pos, k_scale=ks,
                                         v_scale=vs, impl="kernel",
                                         interpret=False)

    for name, (rows, n_q, h, d, n_tables, dtype) in shapes.items():
        pages = sds((257, 16, h * d), dtype)
        scales = (sds((257, 16, h), jnp.float32),) * 2 if dtype == jnp.int8 else ()
        q_type = jnp.float32 if dtype == jnp.float32 else jnp.bfloat16
        calls = _custom_calls(analysis.compiled_text(
            jax.jit(attend), sds((rows, n_q, h, d), q_type), pages, pages,
            sds((rows, n_tables), jnp.int32), sds((rows, n_q), jnp.int32), *scales))
        assert len(calls) == 1 and calls[0].startswith("paged_attention"), (name, calls)


# ------------------------------------------- the page pool stays where it lies
XL_PAGES, XL_PAGE_LEN, XL_HEADS, XL_HEAD_DIM, XL_TABLE = 257, 16, 25, 64, 64


def _leaf_sized(text, leaf):
    """``(opcode, the instruction's line)`` of every instruction of a
    compiled program whose result is, or holds, an array of shape ``leaf``."""
    import re

    out = []
    for m in re.finditer(r"^\s*(?:ROOT )?%\S+ = (.+?) ([\w-]+)\(.*$", text, flags=re.M):
        if leaf in m.group(1):
            out.append((m.group(2), m.group(0)))
    return out


@pytest.mark.parametrize("program", ["decode-step", "prefill-chunk", "verify"])
def test_serving_programs_leave_the_page_pool_where_it_lies(one_chip, monkeypatch,
                                                            program):
    """The mechanism's witness (PR 31): GPT-2 XL's widths (25 heads of 64,
    a few of its layers), 257 pages, the cache donated, the real Mosaic
    call. A layer's leaf ``[n_pages, page_len, heads * head_dim]`` is a
    parameter, is written by a scatter fusion in place and is read by the
    call named ``paged_attention`` as it lies: the compiled program holds no
    copy, slice, transpose or other fusion of a leaf's size (a pool whose
    last dim is the head_dim of 64 cost four whole-pool copies and two
    slices a layer; PERF.md section 6, PR 31). What the compiler's own
    prefetch moves into fast memory ahead of an operation (asynchronous,
    memory space ``S(1)``) is at most one layer's pair of leaves."""
    from autodist_tpu.models import transformer as T
    from autodist_tpu.ops import paged_attention as PA

    monkeypatch.setattr(PA, "_should_interpret", lambda: False)
    layers, rows, chunk, k1 = 4, 4, 16, 5
    cfg = T.TransformerConfig(
        vocab_size=50257, num_layers=layers, d_model=XL_HEADS * XL_HEAD_DIM,
        num_heads=XL_HEADS, d_ff=6400, max_seq_len=1024, dtype=jnp.bfloat16,
        paged_attention_impl="kernel")

    def described(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = described(jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg)))
    cache = described(jax.eval_shape(
        lambda: T.init_paged_kv_cache(cfg, XL_PAGES, XL_PAGE_LEN)))

    def serve_decode_step(params, tokens, positions, cache, tables):
        return T.forward_paged_decode_step(params, tokens, positions, cache, tables, cfg)

    def serve_prefill_chunk(params, tokens, start, length, cache, table):
        return T.forward_paged_prefill_chunk(params, tokens, start, length, cache,
                                             table, cfg)

    def serve_spec_verify(params, tokens, positions, cache, tables):
        return T.forward_paged_verify(params, tokens, positions, cache, tables, cfg)

    fn, args, donated = {
        "decode-step": (serve_decode_step,
                        (params, i32(rows), i32(rows), cache, i32(rows, XL_TABLE)), 3),
        "prefill-chunk": (serve_prefill_chunk,
                          (params, i32(1, chunk), i32(), i32(), cache, i32(XL_TABLE)), 4),
        "verify": (serve_spec_verify,
                   (params, i32(rows, k1), i32(rows), cache, i32(rows, XL_TABLE)), 3),
    }[program]
    text = analysis.compiled_text(jax.jit(fn, donate_argnums=(donated,)), *args)

    calls = _custom_calls(text)
    assert len(calls) == layers and all(c.startswith("paged_attention") for c in calls), calls
    leaf = f"bf16[{XL_PAGES},{XL_PAGE_LEN},{XL_HEADS * XL_HEAD_DIM}]"
    found = _leaf_sized(text, leaf)
    assert sum(op == "scatter" for op, _ in found) == 2 * layers
    in_place = {"parameter", "scatter", "tuple", "get-tuple-element", "bitcast",
                "copy-done", "slice-done"}      # the last two end a checked start
    arrivals = 0            # leaves the prefetch brings into fast memory
    for op, line in found:
        result = line.split(" = ", 1)[1]
        if op == "fusion":
            assert "/scatter\"" in line, f"a fusion of a leaf's size, no write: {line[:300]}"
        elif op == "copy-start" or (op == "custom-call" and "ConcatBitcast" in line):
            assert "S(1)" in result, f"a leaf moved, and not by the prefetch: {line[:300]}"
            arrivals += "S(1)" in result.split("}", 1)[0]
        else:
            assert op in in_place, f"{op} of a leaf's size: {line[:300]}"
    assert arrivals <= 2, f"{arrivals} leaves ride the prefetch"


def _entry(text):
    """The compiled program's entry computation (what runs at the top
    level, fusions' insides left out)."""
    return text[text.index("\nENTRY "):]


@pytest.mark.parametrize("program", ["decode-step", "prefill-chunk"])
def test_serving_programs_read_the_weights_as_placed(one_chip, monkeypatch, program):
    """The witness of ``DecodeModel.serving_params`` (PR 37): GPT-2 XL's
    widths, a few layers, the real Mosaic call. From the tree the engine
    places, the compiled program holds no float32 array of a projection's
    shape (no float32 weight streamed, nothing converted from one) and, at
    its top level, nothing of the tied table's size but the parameter the
    head's product reads: the float32 tree's program copies all 50,257 x
    1,600 of it for the lookup on every call (the check sees that too)."""
    import re

    from autodist_tpu.models import transformer as T
    from autodist_tpu.ops import paged_attention as PA

    monkeypatch.setattr(PA, "_should_interpret", lambda: False)
    cfg = T.TransformerConfig(
        vocab_size=50257, num_layers=2, d_model=XL_HEADS * XL_HEAD_DIM,
        num_heads=XL_HEADS, d_ff=6400, max_seq_len=1024, dtype=jnp.bfloat16,
        paged_attention_impl="kernel")

    def described(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    given = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    placed = jax.eval_shape(lambda: T.decode_model(cfg).serving_params(
        T.init_params(jax.random.PRNGKey(0), cfg)))
    cache = described(jax.eval_shape(
        lambda: T.init_paged_kv_cache(cfg, XL_PAGES, XL_PAGE_LEN)))

    def serve_decode_step(params, tokens, positions, cache, tables):
        return T.forward_paged_decode_step(params, tokens, positions, cache, tables, cfg)

    def serve_prefill_chunk(params, tokens, start, length, cache, table):
        return T.forward_paged_prefill_chunk(params, tokens, start, length, cache,
                                             table, cfg)

    def compiled(params):
        fn, args, donated = {
            "decode-step": (serve_decode_step,
                            (params, i32(4), i32(4), cache, i32(4, XL_TABLE)), 3),
            "prefill-chunk": (serve_prefill_chunk,
                              (params, i32(1, 16), i32(), i32(), cache, i32(XL_TABLE)), 4),
        }[program]
        return analysis.compiled_text(jax.jit(fn, donate_argnums=(donated,)), *args)

    weights = r"f32\[(?:1600,1600|1600,6400|6400,1600)\]"
    table = r"\[(?:50257,1600|1600,50257)\]"

    def table_ops(text):
        return [m.group(1) for m in re.finditer(
            r"^\s*(?:ROOT )?%\S+ = \S*" + table + r"\S* ([\w-]+)\(", _entry(text), flags=re.M)]

    text = compiled(described(placed))
    assert not re.search(r"= " + weights, text), "a float32 weight in the program"
    assert not re.search(r"= f32" + table, _entry(text))
    assert table_ops(text) == ["parameter"], table_ops(text)
    assert len(_custom_calls(text)) == cfg.num_layers

    parent = compiled(described(given))         # what the check would see
    assert re.search(r"= " + weights, parent)
    assert "copy" in table_ops(parent), table_ops(parent)


@pytest.mark.parametrize("program", ["decode-step", "prefill-chunk"])
def test_latent_serving_programs_leave_the_page_pool_where_it_lies(one_chip, monkeypatch,
                                                                  program):
    """The twin for a pool with no head axis (PR 36): Kimi K2's published
    widths, the dense layer and one expert layer, 32 rows, pages of 128
    positions, the cache donated, the real Mosaic calls. A layer's one leaf
    ``[n_pages, 128, 640]`` (576 columns in whole lane tiles) is a parameter,
    is written by a scatter in place and is read by ``mla_paged_attention``
    (decode) or gathered a block of positions at a time (a chunk) as it
    lies: no copy, transpose or other fusion of a leaf's size. At 576
    columns XLA laid the 128 positions minor and copied the whole pool
    twice a layer in both programs (PERF.md section 6, PR 36). The decode
    program holds one latent call a layer and three grouped products an
    expert layer, by name."""
    from autodist_tpu.models import kimi_k2 as K
    from autodist_tpu.models import routed
    from autodist_tpu.ops import grouped_matmul as GM
    from autodist_tpu.ops import paged_attention as PA

    monkeypatch.setattr(PA, "_should_interpret", lambda: False)
    monkeypatch.setattr(GM, "_should_interpret", lambda: False)
    monkeypatch.setattr(routed, "resolve", lambda choice, off_chip: "kernel")   # as on the chip
    pages, rows, table = 301, 32, 64
    cfg = K.KimiK2Config(vocab_size=20480, num_hidden_layers=2, experts_held=(0, 12),
                         max_position_embeddings=8192)

    def described(tree, dtype=None):
        return jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(
            x.shape, dtype or x.dtype, sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = described(jax.eval_shape(
        lambda: K.init_params(jax.random.PRNGKey(0), cfg)), jnp.bfloat16)
    cache = described(jax.eval_shape(lambda: K.init_paged_cache(cfg, pages, cfg.page_len)))

    def serve_decode_step(params, tokens, positions, cache, tables):
        return K.forward_paged_decode_step(params, tokens, positions, cache, tables, cfg)

    def serve_prefill_chunk(params, tokens, start, length, cache, table_):
        return K.forward_paged_prefill_chunk(params, tokens, start, length, cache,
                                             table_, cfg)

    fn, args, donated = {
        "decode-step": (serve_decode_step,
                        (params, i32(rows), i32(rows), cache, i32(rows, table)), 3),
        "prefill-chunk": (serve_prefill_chunk,
                          (params, i32(1, cfg.prefill_chunk), i32(), i32(), cache,
                           i32(table)), 4),
    }[program]
    text = analysis.compiled_text(jax.jit(fn, donate_argnums=(donated,)), *args)

    calls = sorted(c.split(".")[0] for c in _custom_calls(text))
    latent = ["mla_paged_attention"] * 2 if program == "decode-step" else []
    assert calls == ["gmm"] * 3 + latent, calls
    assert cfg.page_width == 640
    found = _leaf_sized(text, f"bf16[{pages},{cfg.page_len},640]")
    assert sum(op == "scatter" for op, _ in found) == 2
    in_place = {"parameter", "scatter", "tuple", "get-tuple-element", "bitcast", "while"}
    for op, line in found:
        if op == "fusion":
            assert "/scatter\"" in line, f"a fusion of a leaf's size, no write: {line[:300]}"
        else:
            assert op in in_place, f"{op} of a leaf's size: {line[:300]}"
