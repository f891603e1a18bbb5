"""Speculative decode over the paged KV-cache (ISSUE 15 acceptance bars).

- **lossless greedy equivalence** (the tentpole claim): spec-decode
  output is bit-identical to plain greedy for seeded prompts across
  page/chunk boundaries and draft lengths k in {1, 2, 4, 8}, with both
  an acceptance-friendly draft (the target itself) and a genuinely
  divergent one (real rejections every round), including mid-batch joins
  and failover journal replay through the router;
- **PageTable.rewind edge cases**: rejection at a page boundary,
  rejection of the entire draft, rejection under ``page_exhaustion``
  (extend starved) — ``free_pages``/``page_fragmentation`` invariants
  hold and no pages leak across 1k random accept/reject sequences;
- **multi-token batcher semantics**: a round emitting 0..k+1 tokens is
  truncated at exactly EOS / ``max_new_tokens`` / deadline, and the
  ``serve_spec_*`` gauges + SLO acceptance feed come from the engine's
  cumulative stats;
- **SLO accounting**: ITL percentiles weighted per emitted token (a
  multi-token burst can't fake a latency win) and ``slo_report`` carries
  ``acceptance_rate``;
- **exactly 5 compiled programs** (target decode + target prefill +
  verify + draft decode + draft prefill) for any request-length mix.
"""
import math
import time

import numpy as np
import pytest

from autodist_tpu import metrics as M
from autodist_tpu.serve import pages as serve_pages
from autodist_tpu.serve.spec import _SelftestRig

MAX_NEW = 10


@pytest.fixture(scope="module")
def rig():
    return _SelftestRig()


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(3)
    return [
        np.array([5, 17, 3, 88, 2], np.int32),            # short
        rng.integers(1, 127, size=8).astype(np.int32),    # exactly one page
        rng.integers(1, 127, size=16).astype(np.int32),   # chunk boundary
        rng.integers(1, 127, size=21).astype(np.int32),   # multi-chunk
        rng.integers(1, 127, size=11).astype(np.int32),   # page-crossing
    ]


@pytest.fixture(scope="module")
def expected(rig, prompts):
    return [rig.plain.generate(p, MAX_NEW) for p in prompts]


# ------------------------------------------------- greedy equivalence
@pytest.mark.parametrize("k", [1, 2, 4, 8])
def test_greedy_equivalence_same_draft(rig, prompts, expected, k):
    """Acceptance-friendly draft (the target itself): every k produces
    bit-identical streams, and the draft actually accelerates (tokens
    per round > 1)."""
    spec = rig.spec_engine(k, same_draft=True)
    got = [spec.generate(p, MAX_NEW) for p in prompts]
    assert got == expected
    stats = spec.spec_stats()
    assert stats["acceptance_rate"] == pytest.approx(1.0)
    assert stats["tokens_per_round"] > 1.0


@pytest.mark.parametrize("k", [2, 4])
def test_greedy_equivalence_divergent_draft(rig, prompts, expected, k):
    """A different-seed 1-layer draft rejects on (almost) every round —
    the stream must STILL be bit-identical: losslessness cannot depend on
    draft quality."""
    spec = rig.spec_engine(k, same_draft=False)
    got = [spec.generate(p, MAX_NEW) for p in prompts]
    assert got == expected
    assert spec.spec_stats()["acceptance_rate"] < 0.5


def test_each_verify_round_waits_under_one_wait_span(rig, prompts, expected,
                                                     monkeypatch):
    """The verify round's wait for the device is its own span: one
    ``wait.tokens`` (``program`` ``spec_verify``) inside every
    ``serve.spec_verify``, as the plain engine's fetches have theirs, on
    an array whose copy to the host was started before it."""
    import jax
    from jax._src.array import ArrayImpl

    from autodist_tpu.obs import spans as obs_spans

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
    spec = rig.spec_engine(2, same_draft=True)
    tracer = obs_spans.get_tracer()
    tracer.clear()
    assert spec.generate(prompts[3], MAX_NEW) == expected[3]
    waits = [i for i, (kind, _) in enumerate(events) if kind == "wait"]
    assert waits
    since = 0
    for i in waits:
        assert any(kind == "copy" and x is events[i][1]
                   for kind, x in events[since:i]), i
        since = i + 1
    spans = tracer.spans()
    rounds = [s for s in spans if s.name == "serve.spec_verify"]
    assert rounds
    for r in rounds:
        waits = [s for s in spans if s.parent == r.id and s.name == "wait.tokens"]
        assert [w.attrs for w in waits] == [{"program": "spec_verify"}]
        assert waits[0].dur_s <= r.dur_s


def test_invocation_reduction_at_friendly_workload(rig, prompts, expected):
    """The perf bar: >=2x fewer target-model program invocations per
    emitted token than plain greedy at the acceptance-friendly
    workload (k=4 -> ~1/(k+1) per token)."""
    spec = rig.spec_engine(4, same_draft=True)
    got = [spec.generate(p, MAX_NEW) for p in prompts]
    tokens = sum(len(g) for g in got)
    plain_per_token = (MAX_NEW - 1) / MAX_NEW   # prefill emits the first
    spec_per_token = spec.target_invocations / tokens
    assert spec_per_token <= 0.5 * plain_per_token
    assert got == expected


def test_mid_batch_join_matches_plain(rig):
    """A request joining mid-spec-decode sees the same stream on both
    engines — speculative batching is scheduling, never semantics."""
    spec = rig.spec_engine(4, same_draft=False)
    p1 = np.array([3, 9, 27], np.int32)
    p2 = np.array([44, 8, 15, 16, 23], np.int32)
    n = 8
    ref1 = rig.plain.generate(p1, n)
    ref2 = rig.plain.generate(p2, n)

    s1 = spec.admit(p1, n)
    first = None
    while first is None:
        first = spec.prefill_step(s1)
    got1 = [first]
    # A few solo spec rounds before the second request joins.
    while len(got1) < 4:
        got1.extend(spec.step_many()[s1])
    s2 = spec.admit(p2, n)
    first2 = None
    while first2 is None:
        first2 = spec.prefill_step(s2)
    got2 = [first2]
    while len(got1) < n or len(got2) < n:
        out = spec.step_many()
        if len(got1) < n and s1 in out:
            got1.extend(out[s1])
        if len(got2) < n and s2 in out:
            got2.extend(out[s2])
    spec.release(s1)
    spec.release(s2)
    assert got1[:n] == ref1
    assert got2[:n] == ref2


def test_near_ceiling_request_no_crash_and_lossless(rig):
    """A request whose timeline ends within spec_k tokens of max_len:
    the draft window hangs off the static ceiling — extension must clamp
    at max_len (never grow the table past max_pages) and the stream must
    stay bit-identical. Regression: uncapped extend used to raise from
    table.padded() and kill the scheduler tick."""
    spec = rig.spec_engine(4, same_draft=True)
    prompt = np.arange(1, 9, dtype=np.int32)          # 8 + 56 == max_len 64
    assert spec.generate(prompt, 56) == rig.plain.generate(prompt, 56)
    assert spec.pool.used_pages == 0
    assert spec.draft_pool.used_pages == 0


def test_mid_batch_join_keeps_acceptance(rig):
    """The spec round's draft feeds ride non-decoding rows against
    SCRATCH: a multi-chunk prompt prefilling while another slot decodes
    must keep its draft prompt KV intact — with the same-params draft,
    acceptance stays ~1.0 for BOTH requests (an occasional near-tie
    between the draft's 1-token program and the chunked verify program
    may reject — different XLA shapes, same model). Regression:
    decode-round writes through a mid-prefill slot's real draft table
    used to garble its cache (aggregate acceptance measured 0.656)."""
    spec = rig.spec_engine(4, same_draft=True)
    pa = np.array([3, 9, 27], np.int32)
    pb = np.arange(10, 30, dtype=np.int32)            # 20 tokens: 3 chunks
    n = 8
    ref_a = rig.plain.generate(pa, n)
    ref_b = rig.plain.generate(pb, n)

    sa = spec.admit(pa, n)
    first = None
    while first is None:
        first = spec.prefill_step(sa)
    got_a = [first]
    sb = spec.admit(pb, n)
    got_b = []
    # The batcher pattern: one prefill chunk for B, then a spec round —
    # B's prefill interleaves with A's speculative decode.
    while not got_b:
        fb = spec.prefill_step(sb)
        if fb is not None:
            got_b.append(fb)
        out = spec.step_many()
        if sa in out and len(got_a) < n:
            got_a.extend(out[sa])
    while len(got_a) < n or len(got_b) < n:
        out = spec.step_many()
        if len(got_a) < n and sa in out:
            got_a.extend(out[sa])
        if len(got_b) < n and sb in out:
            got_b.extend(out[sb])
    spec.release(sa)
    spec.release(sb)
    assert got_a[:n] == ref_a
    assert got_b[:n] == ref_b
    assert spec.spec_stats()["acceptance_rate"] >= 0.9


def test_exactly_five_programs(rig, prompts):
    spec = rig.spec_engine(4, same_draft=True)
    for p in prompts:
        spec.generate(p, 6)
    assert spec.compiled_programs == 5


def test_pools_balanced_after_mixed_run(rig, prompts):
    spec = rig.spec_engine(2, same_draft=False)
    for p in prompts:
        spec.generate(p, MAX_NEW)
    for pool in (spec.pool, spec.draft_pool):
        assert pool.used_pages == 0
        assert pool.free_pages == pool.usable_pages
        assert pool.fragmentation(0) == 0.0


# ------------------------------------------------- failover journal replay
@pytest.mark.slow
def test_failover_replay_reproduces_accepted_stream():
    """Kill a spec-decode replica mid-decode: the router's journal replay
    (prompt + delivered prefix resume, overlap token asserted bit-equal)
    must reproduce the same accepted stream on the survivor — the
    exactly-once contract holds across plain and speculative replicas
    because both emit the identical greedy stream."""
    from autodist_tpu.serve.batcher import RequestState
    from autodist_tpu.serve.replica import ReplicaState
    from autodist_tpu.serve.router import build_test_fleet
    from autodist_tpu.utils import retry

    registry = M.MetricsRegistry()
    router, control = build_test_fleet(
        n_replicas=2, registry=registry, spec_decode=True, spec_k=4)
    try:
        router.start()
        for rep in router.replicas.values():
            rep.wait_ready(120.0)
        rng = np.random.default_rng(7)
        prompts = [rng.integers(1, 127, size=int(rng.integers(3, 9)))
                   .astype(np.int32) for _ in range(8)]
        expected = [control.generate(p, 8) for p in prompts]
        fronts = [router.submit(p, max_new_tokens=8) for p in prompts]

        def on_victim():
            with router._lock:
                return any(
                    f.replica_id == 0 and len(f.front.tokens) > 0
                    for f in router._flights.values())

        assert retry.wait_until(on_victim, 60.0, interval_s=0.002)
        router.replicas[0].kill("test: mid-spec-decode death")
        states = [f.wait(120.0).state for f in fronts]
        assert all(s is RequestState.DONE for s in states), states
        assert [f.tokens for f in fronts] == expected
        ledger = router.ledger()
        assert all(v == 1 for v in ledger.values())
        assert router.replica_state(1) is ReplicaState.READY
    finally:
        router.stop(drain=False)


# ------------------------------------------------- PageTable.rewind edges
class TestRewind:
    def test_rewind_at_page_boundary_frees_exact_tail(self):
        pool = serve_pages.build_pool(9, page_len=8)      # 8 usable
        t = pool.alloc(24)                                # 3 pages
        held = list(t.pages)
        assert pool.rewind(t, 16) == 1                    # exact boundary
        assert t.pages == held[:2] and pool.free_pages == 6
        assert pool.rewind(t, 9) == 0                     # 9 tokens: 2 pages
        assert pool.rewind(t, 8) == 1                     # 1 page now
        assert t.pages == held[:1] and pool.free_pages == 7
        pool.release(t)
        assert pool.free_pages == 8 and pool.used_pages == 0

    def test_rewind_entire_draft(self):
        pool = serve_pages.build_pool(5, page_len=4)
        t = pool.alloc(16)                                # all 4 pages
        assert pool.rewind(t, 0) == 4                     # total rejection
        assert t.pages == [] and pool.free_pages == 4
        # An emptied table releases as a no-op (nothing double-freed).
        pool.release(t)
        assert pool.used_pages == 0

    def test_rewind_is_idempotent_and_never_grows(self):
        pool = serve_pages.build_pool(9, page_len=8)
        t = pool.alloc(20)                                # 3 pages
        assert pool.rewind(t, 64) == 0                    # beyond held: no-op
        assert pool.rewind(t, 12) == 1
        assert pool.rewind(t, 12) == 0                    # idempotent
        pool.release(t)

    def test_extend_under_exhaustion_fails_clean(self):
        """The page_exhaustion contract on the extend path: a refused
        extension changes NOTHING — no partial growth, no leak — and the
        chaos seam starves it exactly like a full pool."""
        from autodist_tpu.chaos import hooks as chaos_hooks

        pool = serve_pages.build_pool(4, page_len=4)      # 3 usable
        t = pool.alloc(12)                                # all 3
        held = list(t.pages)
        assert not pool.extend(t, 16)                     # pool empty
        assert t.pages == held and pool.free_pages == 0
        pool.rewind(t, 4)                                 # 2 pages free
        chaos_hooks.install(chaos_hooks.SEAM_SERVE_PAGES,
                            lambda **kw: "exhaust")
        try:
            assert not pool.extend(t, 12)                 # seam starves it
            assert len(t.pages) == 1
        finally:
            chaos_hooks.clear()
        assert pool.extend(t, 12)                         # heals after
        assert len(t.pages) == 3
        pool.release(t)
        assert pool.used_pages == 0

    def test_reclaim_refuses_unallocated(self):
        pool = serve_pages.build_pool(5, page_len=4)
        t = pool.alloc(4)
        freed = t.rewind(0)
        pool.reclaim(freed)
        with pytest.raises(ValueError, match="unallocated"):
            pool.reclaim(freed)                           # double reclaim

    def test_1k_random_accept_reject_sequences_no_leak(self):
        """1000 random alloc/extend/rewind/release sequences: the pool's
        accounting invariants hold at every step and balance to zero at
        the end — a rejection can never leak a page."""
        rng = np.random.default_rng(42)
        pool = serve_pages.build_pool(33, page_len=8)     # 32 usable
        live = []
        for step in range(1000):
            op = rng.integers(0, 4)
            if op == 0:                                   # admit
                t = pool.alloc(int(rng.integers(1, 80)))
                if t is not None:
                    live.append((t, t.capacity))
            elif op == 1 and live:                        # draft extends
                i = int(rng.integers(len(live)))
                t, _ = live[i]
                grown = int(rng.integers(1, 96))
                pool.extend(t, grown)
                live[i] = (t, t.capacity)
            elif op == 2 and live:                        # rejection rewind
                i = int(rng.integers(len(live)))
                t, cap = live[i]
                keep = int(rng.integers(0, cap + 1))
                pool.rewind(t, keep)
                live[i] = (t, t.capacity)
            elif op == 3 and live:                        # retire
                t, _ = live.pop(int(rng.integers(len(live))))
                pool.release(t)
            # Invariants every step: partition of the usable pool, no
            # double ownership, fragmentation in range.
            held = [p for t, _ in live for p in t.pages]
            assert len(held) == len(set(held))
            assert serve_pages.SCRATCH_PAGE not in held
            assert pool.used_pages == len(held)
            assert pool.free_pages + pool.used_pages == pool.usable_pages
            frag = pool.fragmentation(int(rng.integers(0, 200)))
            assert 0.0 <= frag <= 1.0
        for t, _ in live:
            pool.release(t)
        assert pool.used_pages == 0
        assert pool.free_pages == pool.usable_pages
        assert pool.fragmentation(0) == 0.0


# ------------------------------------------------- multi-token batcher
class _StubSpecModel:
    eos_id = 99


class _StubSpecEngine:
    """Minimal spec-shaped engine: admission always lands, each round
    emits a scripted burst per slot — exercises the batcher's multi-token
    truncation and gauge plumbing without device work."""

    decode_model = _StubSpecModel()
    max_len = 64
    page_utilization = 0.0
    page_fragmentation = 0.0
    chaos_host = 0

    def __init__(self, bursts):
        self.bursts = list(bursts)      # one list per round
        self.released = []
        self._slot = None
        self._stats = {"proposed": 0, "accepted": 0, "rounds": 0,
                       "emitted": 0}

    def check_admissible(self, prompt_len, max_new_tokens):
        return None

    def admit(self, prompt, max_new_tokens, request_id="", sampling=None):
        from autodist_tpu.serve.engine import Slot

        self._slot = Slot(0)
        return self._slot

    def prefill_pending(self):
        return []

    def step_many(self):
        if self._slot is None or not self.bursts:
            return {}
        burst = self.bursts.pop(0)
        self._stats["rounds"] += 1
        self._stats["proposed"] += 4
        self._stats["accepted"] += max(len(burst) - 1, 0)
        self._stats["emitted"] += len(burst)
        return {self._slot: burst}

    def spec_stats(self):
        s = dict(self._stats)
        s["acceptance_rate"] = s["accepted"] / max(s["proposed"], 1)
        s["tokens_per_round"] = s["emitted"] / max(s["rounds"], 1)
        return s

    def release(self, slot):
        self.released.append(slot)
        self._slot = None


def _run_stub(bursts, max_new, slo=None):
    from autodist_tpu.serve.batcher import ContinuousBatcher

    engine = _StubSpecEngine(bursts)
    registry = M.MetricsRegistry()
    batcher = ContinuousBatcher(engine, max_queue=4, registry=registry,
                                slo=slo)
    batcher.start()
    req = batcher.submit(np.array([1, 2], np.int32), max_new)
    req.wait(10.0)
    batcher.stop(drain=False)
    return req, engine, registry


def test_burst_truncates_at_max_new_tokens():
    req, engine, registry = _run_stub([[7, 8, 9, 10, 11]], max_new=3)
    assert req.tokens == [7, 8, 9]                 # overshoot discarded
    assert req.state.value == "done"
    assert engine.released                          # slot recycled
    assert registry.snapshot()["serve_tokens_generated_total"] == 3


def test_burst_truncates_at_eos_mid_list():
    req, engine, registry = _run_stub([[7, 99, 9, 10]], max_new=8)
    assert req.tokens == [7, 99]                   # EOS ends the stream
    assert req.state.value == "done"


def test_burst_truncates_at_deadline():
    """A burst landing after the deadline keeps at most ONE token (the
    round plain decode would also have delivered) and times out — the
    rest of the burst is discarded."""
    from autodist_tpu.serve.batcher import ContinuousBatcher

    class _SlowRound(_StubSpecEngine):
        def step_many(self):
            time.sleep(0.08)                # the round outlives the deadline
            return super().step_many()

    engine = _SlowRound([[7, 8, 9, 10, 11]])
    batcher = ContinuousBatcher(engine, max_queue=4,
                                registry=M.MetricsRegistry())
    batcher.start()
    req = batcher.submit(np.array([1, 2], np.int32), 8, timeout_s=0.02)
    req.wait(10.0)
    batcher.stop(drain=False)
    assert req.state.value == "timeout"
    assert len(req.tokens) <= 1             # never the whole burst


def test_multi_round_bursts_accumulate():
    req, engine, registry = _run_stub(
        [[1, 2], [3], [4, 5, 6]], max_new=6)
    assert req.tokens == [1, 2, 3, 4, 5, 6]
    snap = registry.snapshot()
    assert snap["serve_spec_acceptance_rate"] == pytest.approx(
        engine.spec_stats()["acceptance_rate"])
    assert snap["serve_spec_tokens_per_step"] == pytest.approx(
        engine.spec_stats()["tokens_per_round"])


def test_batcher_feeds_slo_acceptance():
    from autodist_tpu.obs.slo import SLOTracker

    slo = SLOTracker(registry=M.MetricsRegistry())
    _run_stub([[1, 2], [3, 4, 5]], max_new=5, slo=slo)
    report = slo.report()
    assert report["counts"]["spec_proposed"] == 8
    assert report["counts"]["spec_accepted"] == 3
    assert report["measured"]["acceptance_rate"] == pytest.approx(3 / 8)


# ------------------------------------------------- SLO per-token ITL
class TestSLOAccounting:
    def _tracker(self):
        from autodist_tpu.obs.slo import SLOTracker

        return SLOTracker(registry=M.MetricsRegistry())

    def test_itl_percentiles_weighted_per_token(self):
        """One 101-token request at slow ITL must outweigh ten 2-token
        requests at fast ITL: the p50 is per TOKEN, so a multi-token
        burst finishing short requests can't fake a latency win."""
        tr = self._tracker()
        for _ in range(10):
            tr.observe(itl_s=0.01, itl_tokens=1)    # 10 fast gaps
        tr.observe(itl_s=1.0, itl_tokens=100)       # 100 slow gaps
        assert tr.percentile("itl", 50.0) == pytest.approx(1.0)
        # Unweighted (the pre-change arithmetic) would have said 0.01.

    def test_unweighted_path_matches_numpy_percentile(self):
        tr = self._tracker()
        vals = [0.05, 0.2, 0.11, 0.4, 0.09]
        for v in vals:
            tr.observe(itl_s=v)
        assert tr.percentile("itl", 99.0) == pytest.approx(
            float(np.percentile(np.asarray(vals), 99.0)))

    def test_acceptance_rate_in_report_and_gauge(self):
        tr = self._tracker()
        assert math.isnan(tr.report()["measured"]["acceptance_rate"])
        tr.observe(spec_proposed=8, spec_accepted=6)
        tr.observe(spec_proposed=4, spec_accepted=0)
        report = tr.report()
        assert report["measured"]["acceptance_rate"] == pytest.approx(0.5)
        assert tr._g["acceptance_rate"].value == pytest.approx(0.5)

    def test_report_json_nan_safe_with_spec_fields(self):
        import json

        from autodist_tpu.obs.slo import json_safe

        tr = self._tracker()
        doc = json.loads(json.dumps(json_safe(tr.report())))
        assert doc["measured"]["acceptance_rate"] is None

    def test_replay_weights_itl_by_token_count(self):
        from autodist_tpu.obs.slo import replay_flight_records

        t0 = time.time()
        records = [
            {"kind": "step", "event": "request", "t": t0, "state": "done",
             "n_tokens": 101, "ttft_s": 0.2, "itl_s": 1.0,
             "queue_wait_s": 0.0},
        ] + [
            {"kind": "step", "event": "request", "t": t0, "state": "done",
             "n_tokens": 2, "ttft_s": 0.1, "itl_s": 0.01,
             "queue_wait_s": 0.0}
            for _ in range(10)
        ]
        tr = replay_flight_records(records, registry=M.MetricsRegistry())
        assert tr.percentile("itl", 50.0) == pytest.approx(1.0)
