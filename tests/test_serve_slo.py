"""Serve-side SLO observability (PR 14): request-scoped tracing, SLO
spec/tracker/report, serve-aware sentry (SNT007/008/009) with router
demotion, doctor DOC007/DOC008, fleet metrics labels, and the recorder
overhead guard with serve records on (docs/observability.md § serving)."""
import asyncio
import json
import math
import os
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from autodist_tpu import metrics as M
from autodist_tpu.ft.heartbeat import MemoryTransport
from autodist_tpu.obs import recorder as obs_recorder
from autodist_tpu.obs import spans as obs_spans
from autodist_tpu.obs.doctor import diagnose
from autodist_tpu.obs.exporter import parse_openmetrics, render_openmetrics
from autodist_tpu.obs.recorder import FlightRecorder, flight_dir
from autodist_tpu.obs.sentry import CODES, Sentry, SentryConfig
from autodist_tpu.obs.slo import SLOSpec, SLOTracker, replay_flight_records
from autodist_tpu.serve.batcher import RequestState
from autodist_tpu.serve.engine import AdmissionDenied
from autodist_tpu.serve.replica import Replica, ReplicaState
from autodist_tpu.serve.router import Router, RouterConfig, build_test_fleet
from autodist_tpu.serve.server import RouterFrontend, mock_load_prompt
from autodist_tpu.utils import retry


# ------------------------------------------------------------ SLO tracker
class TestSLOTracker:
    def _clocked(self, spec=None):
        t = {"now": 1000.0}
        tracker = SLOTracker(spec=spec or SLOSpec(),
                             registry=M.MetricsRegistry(),
                             clock=lambda: t["now"])
        return tracker, t

    def test_percentiles_and_report_shape(self):
        tracker, _ = self._clocked()
        for i in range(100):
            tracker.observe(ttft_s=0.1 + 0.001 * i, itl_s=0.01,
                            queue_wait_s=0.05, ok=True)
        report = tracker.report()
        # Golden shape: the slo_report contract every surface renders.
        assert set(report) == {"slo", "measured", "burn_rate", "counts",
                               "compliant"}
        assert set(report["measured"]) == {
            "ttft_p50_s", "ttft_p99_s", "ttft_cached_p50_s",
            "ttft_uncached_p50_s", "prefix_hit_rate", "itl_p50_s",
            "itl_p99_s", "queue_wait_p99_s", "availability", "error_rate",
            "acceptance_rate", "acceptance_by_temperature"}
        assert set(report["burn_rate"]) == {"fast", "slow", "windows_s"}
        assert set(report["counts"]) == {"requests", "errors", "sheds",
                                         "window_requests",
                                         "spec_proposed", "spec_accepted",
                                         "sampled_streams",
                                         "greedy_streams"}
        assert set(report["compliant"]) == {
            "ttft_p50", "ttft_p99", "itl_p50", "itl_p99", "queue_wait_p99",
            "availability", "overall"}
        m = report["measured"]
        assert 0.14 < m["ttft_p50_s"] < 0.16
        assert m["ttft_p99_s"] <= 0.2 and m["availability"] == 1.0
        assert report["compliant"]["overall"] is True
        assert json.loads(tracker.report_json())  # JSON-serializable

    def test_rolling_window_forgets_old_samples(self):
        tracker, t = self._clocked(SLOSpec(window_s=10.0))
        tracker.observe(ttft_s=99.0)        # ancient outlier
        t["now"] += 60.0
        for _ in range(10):
            tracker.observe(ttft_s=0.1)
        assert tracker.percentile("ttft", 99.0) < 1.0

    def test_burn_rates_multi_window(self):
        spec = SLOSpec(availability=0.99, burn_fast_window_s=10.0,
                       burn_slow_window_s=100.0)
        tracker, t = self._clocked(spec)
        for _ in range(90):                 # old good traffic
            tracker.observe(ok=True)
        t["now"] += 50.0
        for _ in range(5):                  # recent: 50% bad
            tracker.observe(ok=True)
            tracker.observe(ok=False)
        burn = tracker.burn_rates()
        # fast window sees only the 50%-bad era: 0.5 / 0.01 = 50x budget.
        assert burn["fast"] == pytest.approx(50.0)
        assert burn["slow"] < burn["fast"]  # diluted by the good era

    def test_sheds_burn_the_budget(self):
        tracker, _ = self._clocked(SLOSpec(availability=0.9))
        for _ in range(8):
            tracker.observe(ok=True)
        tracker.observe(ok=False, shed=True)
        tracker.observe(ok=False, shed=True)
        report = tracker.report()
        assert report["counts"]["sheds"] == 2
        assert report["measured"]["availability"] == pytest.approx(0.8)
        assert report["compliant"]["availability"] is False
        assert report["compliant"]["overall"] is False

    def test_slo_gauges_render_through_exporter(self):
        reg = M.MetricsRegistry()
        tracker = SLOTracker(spec=SLOSpec(), registry=reg)
        tracker.observe(ttft_s=0.2, itl_s=0.02, ok=True)
        tracker.report()
        samples = parse_openmetrics(render_openmetrics(reg))
        assert samples[("slo_ttft_p50_s", "")] == pytest.approx(0.2)
        assert samples[("slo_compliant", "")] == 1.0

    def test_replay_keys_shed_deltas_by_source(self, tmp_path):
        # Router and batcher keep independent cumulative shed counters —
        # in one process they share an "r"; the src field keeps their
        # delta streams apart.
        rec = FlightRecorder(str(tmp_path), process_id=0)
        rec.record_event("shed", critical=False, src="router-0",
                         reason="x", total_shed=1)
        rec.record_event("shed", critical=False, src="batcher-5",
                         reason="x", total_shed=1)
        rec.record_event("shed", critical=False, src="router-0",
                         reason="x", total_shed=50)
        rec.record_event("shed", critical=False, src="batcher-5",
                         reason="x", total_shed=3)
        rec.close()
        tracker = replay_flight_records(
            obs_recorder.read_records(str(tmp_path)),
            spec=SLOSpec(window_s=1e9, burn_fast_window_s=1e9,
                         burn_slow_window_s=1e9))
        # 1 + 1 + (50-1) + (3-1) = 53 — not 4 (events), not garbage
        # (cross-source deltas).
        assert tracker.report()["counts"]["sheds"] == 53

    def test_replay_from_flight_records(self, tmp_path):
        rec = FlightRecorder(str(tmp_path), process_id=0)
        for i in range(20):
            rec.record_step(surface="serve", event="request",
                            request_id=f"r{i}", state="done", n_tokens=8,
                            ttft_s=0.3, itl_s=0.01, queue_wait_s=0.02)
        rec.record_event("shed", critical=False, reason="queue full")
        rec.close()
        tracker = replay_flight_records(
            obs_recorder.read_records(str(tmp_path)),
            spec=SLOSpec(window_s=1e9, burn_fast_window_s=1e9,
                         burn_slow_window_s=1e9))
        report = tracker.report()
        assert report["counts"]["requests"] == 21
        assert report["counts"]["sheds"] == 1
        assert report["measured"]["ttft_p50_s"] == pytest.approx(0.3)


# ------------------------------------------------------ TTFT attribution
class TestTTFTAttribution:
    """ISSUE 16 satellite: TTFT measures engine ADMISSION -> first token,
    regardless of how many prefill chunks (or how long a queue wait)
    precede it — queue time is ``queue_wait_s``, its own series."""

    def test_ttft_is_admit_relative(self):
        from autodist_tpu.serve.batcher import GenRequest

        req = GenRequest(request_id="r0", prompt=np.zeros(4, np.int32),
                         max_new_tokens=4, t_submit=100.0)
        req.t_admit = 103.0              # 3s queued behind a full pool
        req.t_first_token = 103.5
        assert req.ttft_s == pytest.approx(0.5)      # NOT 3.5

    def test_ttft_falls_back_to_submit(self):
        # A stub front (or an old flight record) may never stamp t_admit:
        # submit-relative is the conservative fallback, not a crash.
        from autodist_tpu.serve.batcher import GenRequest

        req = GenRequest(request_id="r1", prompt=np.zeros(4, np.int32),
                         max_new_tokens=4, t_submit=100.0)
        req.t_first_token = 100.25
        assert req.ttft_s == pytest.approx(0.25)

    def test_cached_split_percentiles_and_hit_rate(self):
        tracker = SLOTracker(spec=SLOSpec(), registry=M.MetricsRegistry())
        for _ in range(30):
            tracker.observe(ttft_s=0.01, ok=True, cached=True)
        for _ in range(10):
            tracker.observe(ttft_s=0.10, ok=True, cached=False)
        m = tracker.report()["measured"]
        assert m["ttft_cached_p50_s"] == pytest.approx(0.01)
        assert m["ttft_uncached_p50_s"] == pytest.approx(0.10)
        assert m["prefix_hit_rate"] == pytest.approx(0.75)


# ------------------------------------------------------- serve sentry codes
def _serve_sentry(monitor=None, cfg=None):
    return Sentry(config=cfg or SentryConfig(), registry=M.MetricsRegistry(),
                  monitor=monitor)


class TestServeSentry:
    def test_codes_documented(self):
        for code in ("SNT007", "SNT008", "SNT009"):
            assert code in CODES

    def test_clean_serve_stream_trips_nothing(self):
        s = _serve_sentry()
        for _ in range(64):
            s.observe_serve(ttft_s=0.2, itl_s=0.02, burn_rate=0.1,
                            replica_id=1)
        assert s.findings == []

    @pytest.mark.parametrize("name,feed,code", [
        ("ttft", lambda s: [s.observe_serve(ttft_s=0.2, replica_id=0)
                            for _ in range(12)]
         + [s.observe_serve(ttft_s=5.0, replica_id=0) for _ in range(4)],
         "SNT007"),
        ("itl", lambda s: [s.observe_serve(itl_s=0.05, replica_id=0)
                           for _ in range(12)]
         + [s.observe_serve(itl_s=2.0, replica_id=0) for _ in range(4)],
         "SNT008"),
        ("burn", lambda s: [s.observe_serve(burn_rate=50.0, replica_id=0)],
         "SNT009"),
    ])
    def test_seeded_regression_trips_exactly_its_code(self, name, feed,
                                                      code):
        s = _serve_sentry()
        feed(s)
        assert [f.code for f in s.findings] == [code], name
        assert s.findings[0].process_id == 0

    def test_once_per_episode_and_rearm(self):
        s = _serve_sentry()
        for _ in range(12):
            s.observe_serve(ttft_s=0.2, replica_id=3)
        for _ in range(6):
            s.observe_serve(ttft_s=5.0, replica_id=3)
        assert [f.code for f in s.findings] == ["SNT007"]  # once
        for _ in range(12):                                # recovery re-arms
            s.observe_serve(ttft_s=0.2, replica_id=3)
        for _ in range(6):
            s.observe_serve(ttft_s=5.0, replica_id=3)
        assert [f.code for f in s.findings] == ["SNT007", "SNT007"]

    def test_per_replica_episodes_are_independent(self):
        s = _serve_sentry()
        for rid in (0, 1):
            for _ in range(12):
                s.observe_serve(ttft_s=0.2, replica_id=rid)
        for rid in (0, 1):
            for _ in range(4):
                s.observe_serve(ttft_s=5.0, replica_id=rid)
        assert sorted((f.code, f.process_id) for f in s.findings) == [
            ("SNT007", 0), ("SNT007", 1)]

    def test_absolute_floor_suppresses_ms_noise(self):
        # 2ms -> 8ms is 4x the median but under the ITL floor: not a page.
        s = _serve_sentry()
        for _ in range(12):
            s.observe_serve(itl_s=0.002, replica_id=0)
        for _ in range(6):
            s.observe_serve(itl_s=0.008, replica_id=0)
        assert s.findings == []

    def test_ttft_regression_escalates_monitor(self):
        calls = []
        monitor = SimpleNamespace(
            escalate=lambda pid, reason="": calls.append((pid, reason)))
        s = _serve_sentry(monitor=monitor)
        for _ in range(12):
            s.observe_serve(ttft_s=0.2, replica_id=2)
        for _ in range(4):
            s.observe_serve(ttft_s=5.0, replica_id=2)
        assert calls and calls[0][0] == 2 and "SNT007" in calls[0][1]

    def test_fleet_burn_does_not_escalate(self):
        calls = []
        monitor = SimpleNamespace(
            escalate=lambda pid, reason="": calls.append(pid))
        s = _serve_sentry(monitor=monitor)
        s.observe_serve(burn_rate=50.0)          # unattributed fleet burn
        assert [f.code for f in s.findings] == ["SNT009"]
        assert calls == []                       # no host to demote

    def test_burn_gauge_is_fleet_level_only(self):
        reg = M.MetricsRegistry()
        s = Sentry(config=SentryConfig(), registry=reg)
        s.observe_serve(burn_rate=5.0)                  # fleet burn
        s.observe_serve(burn_rate=0.0, replica_id=2)    # per-replica calm
        # The dashboard gauge must keep showing the FLEET burn.
        assert reg.gauge("obs_sentry_burn_rate").value == 5.0

    def test_reset_serve_episodes_rearms_a_live_regression(self):
        s = _serve_sentry()
        for _ in range(12):
            s.observe_serve(ttft_s=0.2, replica_id=1)
        for _ in range(4):
            s.observe_serve(ttft_s=50.0, replica_id=1)
        assert [f.code for f in s.findings] == ["SNT007"]
        # Without traffic no recovery observation can clear the episode;
        # the router re-arms it when the demotion cooldown expires.
        s.reset_serve_episodes(1)
        for _ in range(4):
            s.observe_serve(ttft_s=50.0, replica_id=1)
        assert [f.code for f in s.findings] == ["SNT007", "SNT007"]


# --------------------------------------------------------- doctor verdicts
class TestDoctorServeVerdicts:
    def _steps(self, rec, n=12):
        for i in range(n):
            rec.record_step(surface="serve", event="tick", active=4,
                            pool_utilization=0.9)

    def test_pool_exhaustion_death_is_doc007(self, tmp_path):
        rec = FlightRecorder(flight_dir(str(tmp_path)))
        self._steps(rec)
        rec.record_event("pool_pressure", critical=False,
                         reason="page pool exhausted (0 of 56 pages free)",
                         free_pages=0, used_pages=56, queue_depth=9)
        rec.record_event(
            "error",
            error="EngineDeadError: page pool exhausted; admissions "
                  "deferred past every client deadline")
        d = diagnose(str(tmp_path))
        assert d.verdict == "pool_exhaustion" and d.code == "DOC007"
        assert any("page-pool-exhausted" in e.detail for e in d.evidence)

    def test_silent_death_inside_pressure_window_is_doc007(self, tmp_path):
        rec = FlightRecorder(flight_dir(str(tmp_path)))
        self._steps(rec)
        rec.record_event("pool_pressure", critical=False,
                         reason="page pool exhausted (0 of 56 pages free)",
                         free_pages=0, queue_depth=12)
        # No terminal event at all: the SIGKILL'd-mid-pressure shape.
        d = diagnose(str(tmp_path))
        assert d.code == "DOC007"

    def test_failover_storm_is_doc008(self, tmp_path):
        rec = FlightRecorder(flight_dir(str(tmp_path)))
        self._steps(rec)
        for rid in (0, 1, 2):
            rec.record_event("replica_transition", critical=False,
                             replica=rid, old="ready", new="dead")
        for i in range(8):
            rec.record_event("reroute", critical=False,
                             request_id=f"g{i}", delivered=3,
                             from_replica=i % 3, reason="replica died")
        d = diagnose(str(tmp_path))
        assert d.verdict == "failover_storm" and d.code == "DOC008"
        assert d.stats["replica_dead_transitions"] == 3

    def test_single_supervised_kill_stays_doc006(self, tmp_path):
        # One replica death with its orderly failover is a crash, not a
        # storm — the chaos replica_death class pins DOC006.
        rec = FlightRecorder(flight_dir(str(tmp_path)))
        self._steps(rec)
        rec.record_event("replica_transition", critical=False, replica=1,
                         old="ready", new="dead")
        for i in range(3):
            rec.record_event("reroute", critical=False, request_id=f"g{i}",
                             delivered=2, from_replica=1,
                             reason="replica 1 died")
        rec.record_event("error", error="EngineDeadError: killed")
        rec.close(ok=True)
        assert diagnose(str(tmp_path)).code == "DOC006"

    def test_stale_deaths_do_not_storm_a_preemption(self, tmp_path):
        # Two fully-recovered single failovers long ago must not
        # reclassify a later routine preemption as a failover storm.
        t = {"now": 1000.0}
        rec = FlightRecorder(flight_dir(str(tmp_path)),
                             clock=lambda: t["now"])
        for rid in (0, 2):
            rec.record_event("replica_transition", critical=False,
                             replica=rid, old="ready", new="dead")
        t["now"] = 5000.0            # far outside the 600s storm window
        self._steps(rec)
        rec.record_event("preempt", step=7)
        d = diagnose(str(tmp_path))
        assert d.verdict == "preemption" and d.code == "DOC004"

    def test_clean_pressure_window_stays_doc000(self, tmp_path):
        # Pool pressure that RECOVERED (the chaos page_exhaustion class's
        # graceful path) must not read as a collapse.
        rec = FlightRecorder(flight_dir(str(tmp_path)))
        self._steps(rec)
        rec.record_event("pool_pressure", critical=False,
                         reason="page pool exhausted", free_pages=0)
        self._steps(rec)
        rec.close(ok=True)
        assert diagnose(str(tmp_path)).code == "DOC000"


# ------------------------------------------------- labeled fleet exposition
class TestLabeledExposition:
    def test_labels_share_one_type_comment_and_parse(self):
        snap = {
            'serve_replica_up{replica="0"}': 1.0,
            'serve_replica_up{replica="1"}': 0.0,
            "serve_router_requests_total": 5.0,
        }
        text = render_openmetrics(snapshot=snap)
        assert text.count("# TYPE serve_replica_up gauge") == 1
        samples = parse_openmetrics(text)
        assert samples[("serve_replica_up", 'replica="0"')] == 1.0
        assert samples[("serve_replica_up", 'replica="1"')] == 0.0
        assert samples[("serve_router_requests_total", "")] == 5.0

    def test_unlabeled_rendering_unchanged(self):
        reg = M.MetricsRegistry()
        reg.counter("a_total").inc()
        reg.gauge("b").set(2.0)
        reg.histogram("c_s").observe(0.5)
        text = render_openmetrics(reg)
        assert text == (
            "# TYPE a counter\na_total 1\n"
            "# TYPE b gauge\nb 2\n"
            "# TYPE c_s summary\n"
            'c_s{quantile="0.5"} 0.5\nc_s{quantile="0.9"} 0.5\n'
            'c_s{quantile="0.99"} 0.5\nc_s_count 1\nc_s_sum 0.5\n'
            "# EOF\n")

    def test_labeled_histogram_renders_and_parses(self):
        h = M.Histogram()
        h.observe(1.0)
        snap = {'serve_x_s{replica="2"}': h.summary()}
        samples = parse_openmetrics(render_openmetrics(snapshot=snap))
        assert samples[("serve_x_s", 'replica="2",quantile="0.5"')] == 1.0
        assert samples[("serve_x_s_count", 'replica="2"')] == 1.0


# ------------------------------------------- router demotion (stub fleet)
class _StubEngine:
    decode_model = object()
    n_slots = 4
    max_len = 64
    page_utilization = 0.0
    page_fragmentation = 0.0
    chaos_host = 0
    pool = SimpleNamespace(free_pages=0, used_pages=0, utilization=0.0)

    @staticmethod
    def check_admissible(prompt_len, max_new_tokens):
        return None

    @staticmethod
    def admit(prompt, max_new_tokens, request_id="", sampling=None):
        return AdmissionDenied("no free row (stub)", retryable=True)

    @staticmethod
    def prefill_pending():
        return []

    @staticmethod
    def release(slot):
        pass


def _stub_router(tmp_path, n=3, **router_kw):
    transport = MemoryTransport()
    cfg = RouterConfig(heartbeat_interval_s=0.02, health_interval_s=0.01,
                       suspect_after_misses=2, dead_after_misses=4,
                       dispatch_interval_s=0.002,
                       sentry_demote_cooldown_s=0.3)
    replicas = {
        rid: Replica(rid, _StubEngine, transport,
                     persist_path=str(tmp_path / f"r{rid}.json"),
                     heartbeat_interval_s=cfg.heartbeat_interval_s,
                     registry=M.MetricsRegistry())
        for rid in range(n)
    }
    return Router(replicas, transport, config=cfg,
                  registry=M.MetricsRegistry(), **router_kw)


class TestRouterDemotion:
    def _seed_regression(self, router, rid, signal="ttft_s"):
        for _ in range(12):
            router.serve_sentry.observe_serve(replica_id=rid,
                                              **{signal: 0.2})
        for _ in range(4):
            router._observe_serve(replica_id=rid, **{signal: 50.0})

    def test_snt007_demotes_then_cooldown_readmits(self, tmp_path):
        router = _stub_router(tmp_path)
        router.start()
        try:
            assert retry.wait_until(
                lambda: all(router.replica_state(r) is ReplicaState.READY
                            for r in range(3)), 10.0, interval_s=0.005)
            self._seed_regression(router, 1)
            router._sweep_health(force=True)
            assert router.replica_state(1) is ReplicaState.SUSPECT
            assert 1 not in router._routable()
            # Cooldown expiry re-admits (the replica kept beating READY).
            assert retry.wait_until(
                lambda: router.replica_state(1) is ReplicaState.READY,
                10.0, interval_s=0.01)
        finally:
            router.stop(drain=False)

    def test_fleet_burn_never_demotes_replica_zero(self, tmp_path):
        # A fleet-level SNT009 carries process_id -1, NOT the sentry's
        # default host id 0 — else replica 0 would be demoted for a
        # fleet-wide overload exactly when capacity matters most.
        router = _stub_router(tmp_path)
        router.start()
        try:
            assert retry.wait_until(
                lambda: all(router.replica_state(r) is ReplicaState.READY
                            for r in range(3)), 10.0, interval_s=0.005)
            router._apply_sentry_findings(
                router.serve_sentry.observe_serve(burn_rate=50.0))
            assert "SNT009" in router.serve_sentry.codes()
            assert router._sentry_demoted == {}
            router._sweep_health(force=True)
            assert router.replica_state(0) is ReplicaState.READY
        finally:
            router.stop(drain=False)

    def test_per_replica_burn_demotes_the_failing_replica(self, tmp_path):
        router = _stub_router(tmp_path)
        router.start()
        try:
            assert retry.wait_until(
                lambda: all(router.replica_state(r) is ReplicaState.READY
                            for r in range(3)), 10.0, interval_s=0.005)
            now = time.monotonic()
            with router._lock:
                router._replica_outcomes[2].extend(
                    (now, False) for _ in range(20))
            router._sweep_health(force=True)
            assert any(f.code == "SNT009" and f.process_id == 2
                       for f in router.serve_sentry.findings)
            assert 2 in router._sentry_demoted
            assert router.replica_state(2) is ReplicaState.SUSPECT
        finally:
            router.stop(drain=False)

    def test_persistent_regressor_redemotes_after_cooldown(self, tmp_path):
        # A replica that is STILL sick when its cooldown expires must be
        # demoted again — the episode re-arms on re-admission (a demoted
        # replica serves no traffic, so recovery can never clear it).
        router = _stub_router(tmp_path)   # cooldown 0.3s
        router.start()
        try:
            assert retry.wait_until(
                lambda: all(router.replica_state(r) is ReplicaState.READY
                            for r in range(3)), 10.0, interval_s=0.005)
            self._seed_regression(router, 1)
            router._sweep_health(force=True)
            assert router.replica_state(1) is ReplicaState.SUSPECT
            assert retry.wait_until(     # cooldown expires, re-admitted
                lambda: router.replica_state(1) is ReplicaState.READY,
                10.0, interval_s=0.01)
            for _ in range(4):           # the regression never stopped
                router._observe_serve(ttft_s=50.0, replica_id=1)
            assert 1 in router._sentry_demoted
            snt007 = [f for f in router.serve_sentry.findings
                      if f.code == "SNT007" and f.process_id == 1]
            assert len(snt007) == 2
        finally:
            router.stop(drain=False)

    def test_maintenance_window_suppresses_demotion(self, tmp_path):
        # During a rolling upgrade latency degrades BY DESIGN (shrunken
        # fleet, cold restarts): verdicts still record, demotions do not.
        router = _stub_router(tmp_path)
        router.start()
        try:
            assert retry.wait_until(
                lambda: all(router.replica_state(r) is ReplicaState.READY
                            for r in range(3)), 10.0, interval_s=0.005)
            with router._lock:
                router._maintenance_until = float("inf")
            self._seed_regression(router, 1)
            assert "SNT007" in router.serve_sentry.codes()   # recorded
            assert 1 not in router._sentry_demoted           # suppressed
            with router._lock:                               # window closes
                router._maintenance_until = time.monotonic() - 1.0
            for _ in range(12):
                router.serve_sentry.observe_serve(ttft_s=0.2, replica_id=2)
            for _ in range(4):
                router._observe_serve(ttft_s=50.0, replica_id=2)
            assert 2 in router._sentry_demoted               # live again
        finally:
            router.stop(drain=False)

    def test_never_demotes_last_routable_replica(self, tmp_path):
        router = _stub_router(tmp_path, n=1)
        router.start()
        try:
            assert retry.wait_until(
                lambda: router.replica_state(0) is ReplicaState.READY,
                10.0, interval_s=0.005)
            self._seed_regression(router, 0, signal="itl_s")
            # SNT008 fired, but the demotion overlay skipped the LAST
            # routable replica (the monitor escalation still marks it
            # SUSPECT transiently until its next healthy beat clears it).
            assert "SNT008" in router.serve_sentry.codes()
            assert 0 not in router._sentry_demoted
            assert retry.wait_until(
                lambda: router.replica_state(0) is ReplicaState.READY,
                10.0, interval_s=0.01)
        finally:
            router.stop(drain=False)


# ------------------------------------- real fleet: trace + SLO + frontend
@pytest.fixture(scope="module")
def routed_run(tmp_path_factory):
    """One real 3-replica fleet run with a mid-decode kill: shared by the
    trace-continuity, slo_report, and fleet-metrics tests (engine compiles
    amortized across them, like tests/test_router.py's fleet fixture)."""
    registry = M.MetricsRegistry()
    workdir = str(tmp_path_factory.mktemp("slo-fleet"))
    router, control = build_test_fleet(
        n_replicas=3, journal_dir=workdir, registry=registry)
    obs_spans.get_tracer().clear()
    rng = np.random.default_rng(7)
    prompts = [np.asarray(mock_load_prompt(rng, i), np.int32)
               for i in range(24)]
    router.start()
    for rep in router.replicas.values():
        assert rep.wait_ready(120.0)

    def killer():
        def armed():
            with router._lock:
                return any(f.replica_id == 1 and len(f.front.tokens) > 0
                           for f in router._flights.values())

        if retry.wait_until(armed, 60.0, interval_s=0.005):
            router.replicas[1].kill("test: injected mid-decode death")

    thread = threading.Thread(target=killer, daemon=True)
    thread.start()
    fronts = [router.submit(p, max_new_tokens=8) for p in prompts]
    states = [f.wait(240.0).state for f in fronts]
    thread.join(timeout=5.0)
    yield {"router": router, "registry": registry, "fronts": fronts,
           "states": states,
           "trace": obs_spans.get_tracer().to_chrome_trace()}
    router.stop(drain=False)


class TestRoutedRun:
    def test_all_completed_with_failover(self, routed_run):
        assert all(s is RequestState.DONE for s in routed_run["states"])
        snap = routed_run["registry"].snapshot()
        assert snap.get("serve_router_requests_rerouted_total", 0) >= 1

    def test_trace_continuity_across_failover(self, routed_run):
        """ONE trace id; the rerouted request's span chain crosses the
        killed replica and its survivor; the journal watermark rides the
        failover span."""
        trace = routed_run["trace"]
        failovers = [e for e in trace["traceEvents"]
                     if e.get("name") == "serve.failover"]
        assert failovers, "no failover span recorded"
        found = False
        for ev in failovers:
            rid = ev["args"]["request_id"]
            chain = obs_spans.events_for_request(trace, rid)
            names = [e["name"] for e in chain]
            routes = {e["args"].get("replica") for e in chain
                      if e["name"] == "serve.router.route"}
            if len(routes) < 2 or ev["args"]["delivered"] < 1:
                continue   # a victim that had not delivered yet
            found = True
            assert "serve.router.admit" in names
            assert "serve.request" in names
            assert ev["args"]["delivered"] >= 1        # journal watermark
            assert ev["args"]["from_replica"] == 1     # the killed replica
            assert 1 in routes and routes - {1}        # plus a survivor
            # Device-level spans carry the same id: the engine's chunks
            # and batched decode steps are part of the request's chain.
            assert any(n in ("serve.prefill_chunk", "serve.decode_step",
                             "serve.queue_wait") for n in names)
            assert {e["args"].get("trace_id") for e in chain} == {
                trace["otherData"]["trace_id"]}
            # Chronology: admit precedes the failover, which precedes the
            # final delivery span's close.
            t_admit = min(e["ts"] for e in chain
                          if e["name"] == "serve.router.admit")
            t_req = max(e["ts"] + e["dur"] for e in chain
                        if e["name"] == "serve.request")
            assert t_admit <= ev["ts"] <= t_req
        assert found, "no request's chain crossed two replicas"

    def test_slo_report_measured_and_bounded(self, routed_run):
        router = routed_run["router"]
        # A survivor may sit SUSPECT for a heartbeat or two when the host is
        # busy (it recovers by itself); the report below is about the
        # settled fleet, so let it settle.
        retry.wait_until(
            lambda: router.slo_report()["router"]["replicas_ready"] == 2,
            30.0, interval_s=0.1)
        report = router.slo_report()
        m = report["measured"]
        for key in ("ttft_p50_s", "ttft_p99_s", "itl_p50_s", "itl_p99_s",
                    "queue_wait_p99_s"):
            assert math.isfinite(m[key]) and m[key] >= 0, key
        assert m["ttft_p99_s"] >= m["ttft_p50_s"]
        assert m["availability"] == 1.0
        assert report["compliant"]["overall"] is True
        assert report["router"]["replicas"][1] == "dead"
        assert report["router"]["replicas_ready"] == 2
        assert json.dumps(report, default=str)

    def test_request_flight_records_carry_slo_inputs(self, routed_run):
        # The route decision is flight-recorded with its inputs.
        # (Recorder may be disabled in this process — assert via spans'
        # sibling surface instead: the router's route spans exist.)
        trace = routed_run["trace"]
        routes = [e for e in trace["traceEvents"]
                  if e.get("name") == "serve.router.route"]
        assert len(routes) >= 24
        resumed = [e for e in routes if e["args"].get("resume_from", 0) > 0]
        assert resumed, "no route span carried a resume watermark"

    def test_fleet_metrics_byte_parity_and_labels(self, routed_run):
        router = routed_run["router"]
        # Quiesce so the exposition is stable between the two renders.
        router.stop(drain=False)

        async def fetch(path):
            frontend = RouterFrontend(router, port=0)
            server = await asyncio.start_server(
                frontend._handle, "127.0.0.1", 0)
            port = server.sockets[0].getsockname()[1]
            reader, writer = await asyncio.open_connection("127.0.0.1",
                                                           port)
            writer.write(f"GET {path} HTTP/1.1\r\n\r\n".encode())
            await writer.drain()
            raw = await reader.read()
            writer.close()
            server.close()
            await server.wait_closed()
            head, _, body = raw.partition(b"\r\n\r\n")
            return head.split()[1].decode(), body

        status, body = asyncio.run(fetch("/metrics"))
        assert status == "200"
        expected = render_openmetrics(
            snapshot=router.metrics_snapshot()).encode()
        assert body == expected          # byte parity with THE renderer
        samples = parse_openmetrics(body.decode())
        for rid in range(3):
            assert ("serve_replica_outstanding",
                    f'replica="{rid}"') in samples
        assert samples[("serve_replica_up", 'replica="1"')] == 0.0

        status, body = asyncio.run(fetch("/slo"))
        assert status == "200"
        doc = json.loads(body)
        assert set(doc) >= {"slo", "measured", "burn_rate", "compliant"}

        status, body = asyncio.run(fetch("/healthz"))
        doc = json.loads(body)
        assert set(doc) >= {"ok", "replicas", "replicas_ready"}


# -------------------------------------------- batcher serve instrumentation
def test_batcher_emits_itl_queue_wait_and_request_records(tmp_path):
    from autodist_tpu.serve.batcher import ContinuousBatcher
    from autodist_tpu.serve.server import _tiny_engine

    registry = M.MetricsRegistry()
    tracker = SLOTracker(spec=SLOSpec(), registry=registry)
    obs_recorder.enable(str(tmp_path / "flight"))
    obs_spans.get_tracer().clear()
    try:
        engine, _, _ = _tiny_engine(n_slots=8, n_pages=41)
        batcher = ContinuousBatcher(engine, registry=registry, slo=tracker)
        batcher.start()
        try:
            reqs = [batcher.submit(np.arange(1, 6, dtype=np.int32), 6)
                    for _ in range(4)]
            for r in reqs:
                assert r.wait(120.0).state is RequestState.DONE
        finally:
            batcher.stop()
    finally:
        obs_recorder.disable(ok=True)
    snap = registry.snapshot()
    assert snap["serve_itl_s"]["count"] >= 4
    assert snap["serve_ttft_s"]["count"] >= 4
    report = tracker.report()
    assert report["counts"]["requests"] == 4
    assert report["measured"]["availability"] == 1.0
    records = obs_recorder.read_records(str(tmp_path / "flight"))
    req_recs = [r for r in records if r.get("event") == "request"]
    assert len(req_recs) == 4
    for r in req_recs:
        assert r["state"] == "done" and r["request_id"]
        assert r["ttft_s"] > 0 and r["queue_wait_s"] >= 0
    ticks = [r for r in records if r.get("event") == "tick"]
    assert ticks and all("pool_utilization" in t and "tick_wall_s" in t
                         for t in ticks)
    # Spans carry the stable request id end to end.
    spans = obs_spans.get_tracer().spans()
    by_req = {s.attrs.get("request_id") for s in spans
              if s.name == "serve.queue_wait"}
    assert {r.request_id for r in reqs} <= by_req
    assert any(s.name == "serve.prefill_chunk"
               and s.attrs.get("request_id") in by_req for s in spans)
    assert any(s.name == "serve.decode_step"
               and set(s.attrs.get("request_ids") or [])
               & {r.request_id for r in reqs} for s in spans)
    # ServeFrontend serves the single-engine slo_report (GET /slo),
    # NaN-safe, and 404s with a pointer when no tracker was wired.
    from autodist_tpu.serve.server import ServeFrontend

    class _W:
        data = b""

        def write(self, b):
            self.data += b

    fe = ServeFrontend(batcher)
    w = _W()
    fe._slo(w)
    head, _, body = w.data.partition(b"\r\n\r\n")
    assert head.split()[1] == b"200"
    doc = json.loads(body)
    assert doc["counts"]["requests"] == 4
    assert b"NaN" not in body
    w404 = _W()
    ServeFrontend(SimpleNamespace(slo=None))._slo(w404)
    assert w404.data.split()[1] == b"404"


def test_recorder_overhead_guard_with_serve_records(tmp_path):
    """The recorder's cost on the serving path is bounded by how often it
    writes, so the guard counts writes (a wall-clock ratio on a shared CPU
    is a flake by construction): per N progressing ticks one tick record
    in 32, one decode record in 64 rounds, one request record a request,
    and an fsync only every ``fsync_every`` records."""
    from collections import Counter

    from autodist_tpu.serve.batcher import ContinuousBatcher
    from autodist_tpu.serve.server import _tiny_engine

    n_requests, max_new = 8, 24
    rec = obs_recorder.enable(str(tmp_path / "flight"), fsync_every=64,
                              fsync_interval_s=1e9)
    try:
        engine, _, _ = _tiny_engine(n_slots=8, n_pages=41)
        ticks = []
        batcher = ContinuousBatcher(engine, registry=M.MetricsRegistry(),
                                    on_tick=ticks.append)
        batcher.start()
        try:
            for _ in range(n_requests):   # one at a time: the ticks repeat
                req = batcher.submit(np.arange(1, 6, dtype=np.int32), max_new)
                assert req.wait(120.0).state is RequestState.DONE
        finally:
            batcher.stop()
        stats = rec.stats()
    finally:
        obs_recorder.disable(ok=True)
    # One chunk prefills the 5-token prompt and the same tick decodes the
    # second token: max_new - 1 ticks and decode rounds a request.
    assert len(ticks) == engine.decode_invocations == n_requests * (max_new - 1)
    records = obs_recorder.read_records(str(tmp_path / "flight"))
    by_event = Counter(r.get("event") for r in records
                       if r.get("kind") == "step")
    assert by_event["tick"] == -(-len(ticks) // 32)
    assert by_event["decode"] == -(-engine.decode_invocations // 64)
    assert by_event["request"] == n_requests
    # Everything else is per request (admit, prefilled), never per tick.
    per_tick = by_event["tick"] + by_event["decode"]
    assert sum(by_event.values()) - per_tick <= 3 * n_requests
    assert stats["errors"] == 0
    assert stats["fsyncs"] <= stats["events"] + stats["records"] // 64
