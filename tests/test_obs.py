"""Observability subsystem tests (ISSUE 3): chrome-trace golden shape,
cross-process trace-id stitching, OpenMetrics parity across export
surfaces, profiler-vs-compiled-cost agreement, and the overhead guard."""
import asyncio
import json
import os
import sys
import time

import jax
import numpy as np
import pytest

from autodist_tpu import metrics as M
from autodist_tpu import obs
from autodist_tpu.obs import spans as obs_spans


# ----------------------------------------------------------- chrome traces
def test_chrome_trace_export_golden_shape(tmp_path):
    tracer = obs.SpanTracer(trace_id="cafe1234", process=3)
    with tracer.span("outer", phase="x"):
        with tracer.span("inner"):
            time.sleep(0.002)

    @tracer.traced("decorated")
    def f():
        return 7

    assert f() == 7
    tracer.add_span("retro", time.time() - 1.0, 0.5, request_id=42)
    with pytest.raises(RuntimeError):
        with tracer.span("failing"):
            raise RuntimeError("boom")

    path = tracer.export(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"]["trace_id"] == "cafe1234"
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {
        "outer", "inner", "decorated", "retro", "failing"}
    for e in xs:
        # Golden shape: the complete-event keys Perfetto/chrome require.
        assert set(e) >= {"name", "ph", "ts", "dur", "pid", "tid", "args"}
        assert e["args"]["trace_id"] == "cafe1234"
        assert e["args"]["process"] == 3
        assert e["dur"] >= 0
    inner = next(e for e in xs if e["name"] == "inner")
    outer = next(e for e in xs if e["name"] == "outer")
    # Nesting: inner lies within outer on the µs timeline.
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e3
    failing = next(e for e in xs if e["name"] == "failing")
    assert failing["args"]["error"] is True
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert meta and meta[0]["name"] == "process_name"


def test_ring_buffer_bounds_memory_and_counts_drops():
    tracer = obs.SpanTracer(capacity=8, trace_id="t", process=0)
    for i in range(20):
        tracer.add_span(f"s{i}", time.time(), 0.0)
    assert len(tracer.spans()) == 8
    assert tracer.dropped == 12
    assert tracer.spans()[-1].name == "s19"


def test_stitch_merges_parts_sharing_one_trace_id(tmp_path):
    # Two "processes" of one launch + a foreign trace that must not leak in.
    a = obs.SpanTracer(trace_id="deadbeef", process=0)
    b = obs.SpanTracer(trace_id="deadbeef", process=1)
    other = obs.SpanTracer(trace_id="ffffffff", process=0)
    a.add_span("chief.step", time.time(), 0.1)
    b.add_span("worker.step", time.time(), 0.1)
    other.add_span("stale.run", time.time(), 0.1)
    a.flush_part(str(tmp_path))
    b.flush_part(str(tmp_path))
    other.flush_part(str(tmp_path))
    merged = obs.stitch(str(tmp_path), trace_id="deadbeef")
    doc = json.load(open(merged))
    assert doc["otherData"] == {"trace_id": "deadbeef", "n_parts": 2}
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert names == {"chief.step", "worker.step"}
    ids = {e["args"]["trace_id"] for e in doc["traceEvents"]
           if e["ph"] == "X"}
    assert ids == {"deadbeef"}
    # Majority-id stitch without an explicit id picks the 2-part trace.
    assert obs.stitch(str(tmp_path)).endswith("trace-deadbeef.json")


@pytest.mark.slow
def test_two_process_launcher_run_stitches_one_trace(tmp_path):
    """Acceptance: a 2-process launcher run produces ONE chrome-trace JSON
    whose spans from both processes share one trace id, propagated through
    the launcher's AUTODIST_* env."""
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.runtime import launcher

    script = tmp_path / "spanner.py"
    script.write_text(
        "import time\n"
        "from autodist_tpu.obs import spans\n"
        "with spans.span('fleet.unit'):\n"
        "    time.sleep(0.01)\n"
    )
    out = tmp_path / "traces"
    out.mkdir()
    env_backup = os.environ.get("AUTODIST_TRACE_OUT")
    os.environ["AUTODIST_TRACE_OUT"] = str(out)
    try:
        code = launcher.launch(
            ResourceSpec.from_local_devices(),
            [sys.executable, str(script)],
            num_local_processes=2,
        )
    finally:
        if env_backup is None:
            os.environ.pop("AUTODIST_TRACE_OUT", None)
        else:
            os.environ["AUTODIST_TRACE_OUT"] = env_backup
    assert code == 0
    merged = [n for n in os.listdir(out) if n.startswith("trace-")]
    assert len(merged) == 1, os.listdir(out)
    doc = json.load(open(out / merged[0]))
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    ids = {e["args"]["trace_id"] for e in xs}
    assert len(ids) == 1
    # Spans from both fleet roles (0 = chief, 1 = worker) are present, and
    # the launcher's own fleet span stitched in too.
    roles = {e["args"]["process"] for e in xs if e["name"] == "fleet.unit"}
    assert roles == {0, 1}
    assert any(e["name"] == "launcher.fleet" for e in xs)


# ------------------------------------------------------------- openmetrics
def _populated_registry():
    reg = M.MetricsRegistry()
    reg.counter("demo_requests_total").inc(3)
    reg.gauge("demo_depth").set(7.5)
    h = reg.histogram("demo_latency_s")
    for v in (0.1, 0.2, 0.3, 0.4):
        h.observe(v)
    reg.histogram("demo_empty_s")  # registered, never observed
    return reg


def test_openmetrics_render_parse_roundtrip():
    reg = _populated_registry()
    text = obs.render_openmetrics(reg)
    assert text.endswith("# EOF\n")
    assert "nan" not in text  # empty histogram must not leak NaN samples
    samples = obs.parse_openmetrics(text)
    assert samples[("demo_requests_total", "")] == 3
    assert samples[("demo_depth", "")] == 7.5
    assert samples[("demo_latency_s_count", "")] == 4
    assert samples[("demo_latency_s", 'quantile="0.5"')] == pytest.approx(
        0.25, abs=0.06)
    # The empty histogram exports count/sum but no quantile samples.
    assert samples[("demo_empty_s_count", "")] == 0
    assert ("demo_empty_s", 'quantile="0.5"') not in samples
    # TYPE metadata: counters drop the _total suffix in the family name.
    assert "# TYPE demo_requests counter" in text
    assert "# TYPE demo_depth gauge" in text
    assert "# TYPE demo_latency_s summary" in text


def test_parse_openmetrics_rejects_malformed():
    with pytest.raises(ValueError):
        obs.parse_openmetrics("a 1\n")  # no EOF
    with pytest.raises(ValueError):
        obs.parse_openmetrics("a nan\n# EOF\n")
    with pytest.raises(ValueError):
        obs.parse_openmetrics("a{q=\"1\" 2\n# EOF\n")


class _CaptureWriter:
    """Minimal asyncio StreamWriter stand-in for driving _handle."""

    def __init__(self):
        self.data = b""
        self.closed = False

    def write(self, b):
        self.data += b

    async def drain(self):
        pass

    def close(self):
        self.closed = True


def test_serve_metrics_route_and_file_exporter_byte_identical(tmp_path):
    """Acceptance: serve GET /metrics and the file exporter emit
    byte-identical OpenMetrics renderings of the same registry snapshot."""
    from autodist_tpu.serve.server import ServeFrontend

    reg = _populated_registry()
    frontend = ServeFrontend(batcher=object(), registry=reg)

    async def drive():
        reader = asyncio.StreamReader()
        reader.feed_data(b"GET /metrics HTTP/1.1\r\n\r\n")
        reader.feed_eof()
        writer = _CaptureWriter()
        await frontend._handle(reader, writer)
        return writer.data

    raw = asyncio.run(drive())
    head, _, body = raw.partition(b"\r\n\r\n")
    assert b"200 OK" in head and b"text/plain" in head
    exporter = obs.FileExporter(str(tmp_path / "metrics.prom"), registry=reg)
    exporter.write_once()
    on_disk = open(exporter.path, "rb").read()
    assert body == on_disk  # byte-identical across surfaces
    obs.parse_openmetrics(on_disk.decode())  # and well-formed


def test_file_exporter_periodic_thread(tmp_path):
    reg = M.MetricsRegistry()
    c = reg.counter("ticks_total")
    path = str(tmp_path / "m.prom")
    with obs.FileExporter(path, registry=reg, interval_s=0.05):
        c.inc(5)
        time.sleep(0.2)
    samples = obs.parse_openmetrics(open(path).read())
    assert samples[("ticks_total", "")] == 5


# ---------------------------------------------------------------- profiler
def _tiny_step():
    import autodist_tpu.strategy as S
    from autodist_tpu.api import AutoDist
    from autodist_tpu.models import get_model

    model = get_model("mlp", in_dim=16, hidden=(32,), num_classes=4)
    params = model.init(jax.random.PRNGKey(0))
    batch = model.example_batch(8)
    AutoDist.reset_default()
    try:
        ad = AutoDist(strategy_builder=S.AllReduce())
        step = ad.build(model.loss_fn, params, batch)
    finally:
        AutoDist.reset_default()
    return step, params, batch


def test_step_profiler_flops_match_compiled_cost():
    """Acceptance: StepProfiler's per-step FLOPs agree with the jitted
    program's compiled cost analysis on the 8-device CPU mesh."""
    step, params, batch = _tiny_step()
    reg = M.MetricsRegistry()
    tracer = obs.SpanTracer(trace_id="prof", process=0)
    prof = obs.StepProfiler(step, registry=reg, tracer=tracer)
    state = step.init(params)
    for _ in range(2):
        state, metrics = prof.run(state, batch, 4)
    assert np.isfinite(float(np.asarray(metrics["loss"])[-1]))
    rep = prof.report()
    want = step.window_cost(state, batch, 1)["flops"]
    assert want > 0
    assert rep["flops_per_step"] == pytest.approx(want, rel=1e-9)
    # The window split is coherent: dispatch + device == wall.
    assert rep["wall_s"] >= rep["dispatch_gap_s"] >= 0
    assert rep["device_s"] == pytest.approx(
        rep["wall_s"] - rep["dispatch_gap_s"], rel=1e-6, abs=1e-9)
    # Compile tracking saw the fresh window program.
    assert rep["compiles"]["count"] >= 1
    # Registry + span surfaces carry the same story.
    snap = reg.snapshot()
    assert snap["obs_profiled_windows_total"] == 2
    assert snap["obs_flops_per_step"] == pytest.approx(want, rel=1e-9)
    assert any(s.name == "profiler.window" for s in tracer.spans())


def test_step_profiler_roofline_position():
    step, params, batch = _tiny_step()
    prof = obs.StepProfiler(
        step, registry=M.MetricsRegistry(),
        tracer=obs.SpanTracer(trace_id="r", process=0),
        peak_flops_per_chip=1e12, hbm_bw_bytes_per_s=1e11)
    state = step.init(params)
    state, _ = prof.run(state, batch, 2)
    rep = prof.report()
    roof = rep["roofline"]
    assert roof["t_roofline_s"] == pytest.approx(
        max(roof["t_mxu_s"], roof["t_hbm_lower_s"]))
    assert roof["vs_roofline"] > 0
    # Known peak -> an MFU is reported (tiny on a CPU mesh, but finite).
    assert 0 < rep["mfu"] < 1


@pytest.mark.slow
def test_profiler_overhead_guard():
    """Enabled-vs-disabled profiler cost on a tier-1 micro-run: wrapping
    run() must not meaningfully tax the window (host-side timers + one
    span; the cost-analysis lowering is cached after the first window)."""
    step, params, batch = _tiny_step()
    state = step.init(params)
    # Warm both paths fully (compile + cost-analysis cache).
    state, m = step.run(state, batch, 4)
    float(np.asarray(m["loss"])[-1])
    prof = obs.StepProfiler(
        step, registry=M.MetricsRegistry(),
        tracer=obs.SpanTracer(trace_id="o", process=0))
    state, _ = prof.run(state, batch, 4)

    def window_plain():
        nonlocal state
        state, m = step.run(state, batch, 4)
        float(np.asarray(m["loss"])[-1])

    def window_profiled():
        nonlocal state
        state, _ = prof.run(state, batch, 4)

    n = 20
    t0 = time.perf_counter()
    for _ in range(n):
        window_plain()
    plain = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n):
        window_profiled()
    profiled = time.perf_counter() - t0
    # Generous bound (CI noise): profiling may not double the window cost.
    assert profiled < plain * 2.0 + 0.25, (
        f"profiler overhead too high: {profiled:.3f}s vs {plain:.3f}s plain")


def test_window_cost_exposes_compiled_numbers():
    step, params, batch = _tiny_step()
    state = step.init(params)
    c1 = step.window_cost(state, batch, 1)
    c4 = step.window_cost(state, batch, 4)
    assert c1["flops"] > 0 and c1["bytes_accessed"] > 0
    # XLA counts a scan body once regardless of trip count: a 4-step
    # window's analysis reports per-body (= per-step) arithmetic, which is
    # exactly why per-step consumers must ask for num_steps=1.
    assert c4["flops"] == pytest.approx(c1["flops"], rel=0.05)
    assert c1["temp_bytes"] > 0


def test_compile_log_records_fresh_programs():
    step, params, batch = _tiny_step()
    state = step.init(params)
    assert step.compile_log == []
    state, _ = step(state, batch)
    state, _ = step(state, batch)
    state, _ = step.run(state, batch, 3)
    state, _ = step.run(state, batch, 3)
    programs = [e["program"] for e in step.compile_log]
    assert programs == ["step", "run[3]"]  # repeats hit the cache
    assert all(e["first_call_s"] > 0 for e in step.compile_log)


# --------------------------------------------------------------- aggregate
def test_host_aggregator_scores_and_escalation():
    from autodist_tpu.ft.heartbeat import MemoryTransport

    transport = MemoryTransport()
    reg = M.MetricsRegistry()

    fast = obs.HostAggregator(transport, process_id=0, registry=reg)
    slow = obs.HostAggregator(transport, process_id=1,
                              registry=M.MetricsRegistry())
    for _ in range(16):
        fast.observe_step(0.10)
        slow.observe_step(0.45)
    slow.tick()
    escalations = []

    class _Mon:
        def escalate(self, pid, reason=""):
            escalations.append((pid, reason))

    fast.monitor = _Mon()
    for _ in range(fast.escalate_after):
        fast.tick()
    scores = fast.straggler_scores()
    assert scores[1] > fast.straggler_threshold > scores[0]
    assert escalations and escalations[0][0] == 1
    assert "straggler" in escalations[0][1]
    snap = reg.snapshot()
    assert snap["obs_fleet_hosts"] == 2
    assert snap["obs_straggler_score_max"] == pytest.approx(scores[1])
    assert snap["obs_straggler_escalations_total"] == 1
    # Once per straggle episode, even as the over-threshold run continues.
    fast.tick()
    assert len(escalations) == 1


def test_host_aggregator_escalates_with_late_attached_monitor():
    """A monitor attached AFTER the straggler already crossed the
    consecutive-tick bar (the ObsRuntime.attach_monitor ordering) must
    still escalate on the next tick."""
    from autodist_tpu.ft.heartbeat import MemoryTransport

    transport = MemoryTransport()
    obs_a = obs.HostAggregator(transport, process_id=0,
                               registry=M.MetricsRegistry())
    obs_b = obs.HostAggregator(transport, process_id=1,
                               registry=M.MetricsRegistry())
    for _ in range(16):
        obs_a.observe_step(0.1)
        obs_b.observe_step(0.5)
    obs_b.tick()
    for _ in range(obs_a.escalate_after + 2):  # counter passes the bar
        obs_a.tick()
    escalations = []

    class _Mon:
        def escalate(self, pid, reason=""):
            escalations.append(pid)

    obs_a.monitor = _Mon()  # late attach
    obs_a.tick()
    assert escalations == [1]


def test_health_monitor_escalate_forces_suspect():
    from autodist_tpu.ft.heartbeat import (
        HealthMonitor, MemoryTransport, PeerState)

    clock = {"t": 1000.0}
    mon = HealthMonitor(MemoryTransport(), publish=False,
                        registry=M.MetricsRegistry(),
                        clock=lambda: clock["t"])
    mon.transport.publish(1, {"time": 1000.0})
    mon.tick()
    assert mon.peers()[1].state is PeerState.HEALTHY
    fired = []
    mon.on_transition(lambda pid, old, new: fired.append((pid, new)))
    mon.escalate(1, reason="straggler x2.1")
    assert mon.peers()[1].state is PeerState.SUSPECT
    assert fired == [(1, PeerState.SUSPECT)]
    # A fresh beat recovers the peer through the normal tick path.
    clock["t"] += 1.0
    mon.transport.publish(1, {"time": clock["t"]})
    mon.tick()
    assert mon.peers()[1].state is PeerState.HEALTHY


# ------------------------------------------------------------ obs runtime
def test_obs_runtime_through_autodist(tmp_path):
    import autodist_tpu.strategy as S
    from autodist_tpu.api import AutoDist
    from autodist_tpu.models import get_model

    model = get_model("mlp", in_dim=8, hidden=(8,), num_classes=2)
    params = model.init(jax.random.PRNGKey(0))
    batch = model.example_batch(8)
    AutoDist.reset_default()
    try:
        ad = AutoDist(
            strategy_builder=S.AllReduce(),
            observability=obs.ObsConfig(
                metrics_path=str(tmp_path / "train.prom"),
                metrics_interval_s=60.0),
        )
        assert ad.obs is not None and ad.obs.exporter is not None
        step = ad.build(model.loss_fn, params, batch)
        prof = ad.obs.profiler(step)
        state = step.init(params)
        state, _ = prof.run(state, batch, 2)
        ad.obs.close()
    finally:
        AutoDist.reset_default()
    samples = obs.parse_openmetrics(open(tmp_path / "train.prom").read())
    assert samples[("obs_profiled_windows_total", "")] == 1


def test_snapshot_write_records_spans(tmp_path):
    from collections import Counter

    from autodist_tpu.ft.snapshot import SnapshotManager

    # Snapshots write to the process-default tracer (shared across the
    # suite), so assert on per-name DELTAS, not fresh names.
    tracer = obs_spans.get_tracer()
    before = Counter(s.name for s in tracer.spans())
    mgr = SnapshotManager(str(tmp_path), registry=M.MetricsRegistry())
    state = {"w": np.ones((4, 4), np.float32)}
    path = mgr.snapshot(state, step=7, block=True)
    assert path is not None
    after = Counter(s.name for s in tracer.spans())
    assert after["ft.snapshot.device_to_host"] > before["ft.snapshot.device_to_host"]
    assert after["ft.snapshot.write"] > before["ft.snapshot.write"]


def test_tune_audit_recording():
    """Satellite: tune() selections are auditable after the fact — names,
    measured seconds, and the winner land in the registry, the span
    timeline, and last_tune_results."""
    from collections import Counter

    from autodist_tpu.api import AutoDist

    AutoDist.reset_default()
    try:
        ad = AutoDist()
        tracer = obs_spans.get_tracer()
        before = Counter(s.name for s in tracer.spans())
        ad._record_tune_obs(
            [("AllReduce", 0.002), ("PS", 0.005), ("Broken", float("inf"))],
            "AllReduce")
        assert ad.last_tune_results["selected"] == "AllReduce"
        assert ad.last_tune_results["measured"]["PS"] == 0.005
        snap = M.registry.snapshot()
        assert snap["tune_measured_ms_AllReduce"] == pytest.approx(2.0)
        assert snap["tune_measured_ms_PS"] == pytest.approx(5.0)
        assert "tune_measured_ms_Broken" not in snap  # failed: no number
        assert snap["tune_selected_ms"] == pytest.approx(2.0)
        after = Counter(s.name for s in tracer.spans())
        assert after["tune.candidate"] - before["tune.candidate"] == 3
        cands = [s for s in tracer.spans() if s.name == "tune.candidate"]
        sel = [s for s in cands if s.attrs.get("selected")]
        assert sel and sel[-1].attrs["candidate"] == "AllReduce"
        assert any(s.attrs.get("failed") and s.attrs["candidate"] == "Broken"
                   for s in cands)
    finally:
        AutoDist.reset_default()
