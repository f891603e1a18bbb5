"""Serving subsystem tests: paged KV-cache decode correctness, page-pool
admission, continuous batcher semantics (page-availability admission,
backpressure, typed rejection, deadlines, page recycling), the metrics
registry, and the build_inference API seam.

The load-bearing test is the correctness anchor the acceptance bar names:
cached greedy decode must match the uncached full-sequence forward
token-for-token — including a request that JOINS MID-BATCH, which is the
case continuous batching actually creates (per-slot positions diverge).
The paged engine's other oracle pins (page-crossing and multi-chunk
prompts, both attention paths, chunked-prefill interleaving) live in
tests/test_serve_paged.py.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import metrics as M
from autodist_tpu.api import AutoDist
from autodist_tpu.models.transformer import (
    TransformerConfig,
    decode_model,
    init_params,
)
from autodist_tpu.serve import (
    AdmissionDenied,
    Backpressure,
    ContinuousBatcher,
    InferenceEngine,
    RequestState,
)
from autodist_tpu.serve.server import uncached_greedy
from autodist_tpu.strategy import AllReduce

CFG = TransformerConfig(
    vocab_size=97, num_layers=2, d_model=32, num_heads=2, d_ff=64,
    max_seq_len=32, causal=True, dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def engine(params):
    AutoDist.reset_default()
    try:
        autodist = AutoDist(strategy_builder=AllReduce())
        yield autodist.build_inference(
            params, decode_model=decode_model(CFG),
            n_slots=8, page_len=8, n_pages=33, prefill_chunk=8)
    finally:
        AutoDist.reset_default()


def admit_and_prefill(engine, prompt, n_new):
    """Admit + run every prefill chunk; returns (slot, first_token)."""
    slot = engine.admit(np.asarray(prompt, np.int32), n_new)
    assert not isinstance(slot, AdmissionDenied), slot
    first = None
    while first is None:
        first = engine.prefill_step(slot)
    return slot, first


# ----------------------------------------------------------- decode kernel
def test_cached_greedy_decode_matches_uncached_forward(params, engine):
    """Acceptance anchor: cached == uncached, token for token, INCLUDING a
    second request admitted mid-decode (slot positions diverge — the state
    continuous batching actually runs in)."""
    p1 = np.array([5, 17, 3, 88, 2], np.int32)
    p2 = np.array([9, 1, 42], np.int32)
    n_new = 10

    slot1, first1 = admit_and_prefill(engine, p1, n_new)
    got1 = [first1]
    for _ in range(3):  # r1 decodes alone for a few steps...
        got1.append(engine.step()[slot1])
    slot2, first2 = admit_and_prefill(engine, p2, n_new)  # ...r2 joins
    got2 = [first2]
    while len(got1) < n_new or len(got2) < n_new:
        out = engine.step()
        if len(got1) < n_new:
            got1.append(out[slot1])
        if len(got2) < n_new:
            got2.append(out[slot2])
    engine.release(slot1)
    engine.release(slot2)

    assert got1 == uncached_greedy(params, CFG, p1, n_new)
    assert got2 == uncached_greedy(params, CFG, p2, n_new)


def test_generate_matches_oracle_across_page_counts(params, engine):
    # Short (1 page) and long (3 pages, multiple prefill chunks) prompts:
    # same two compiled programs, same oracle stream.
    for prompt, n_new in (([7, 11, 13], 8), (list(range(1, 20)), 8)):
        got = engine.generate(np.asarray(prompt, np.int32), n_new)
        assert got == uncached_greedy(params, CFG, np.asarray(prompt), n_new)
    assert engine.compiled_programs == 2


def test_slot_accounting_and_release(engine):
    assert engine.active_slots == 0
    pool_free = engine.pool.free_pages
    slot = engine.admit(np.array([1, 2, 3], np.int32), 4)
    assert engine.active_slots == 1
    # prompt 3 + max_new 4 = 7 tokens -> 1 page of 8; capacity reserved.
    assert engine.pool.free_pages == pool_free - 1
    assert engine.active_tokens == engine.page_len
    engine.release(slot)
    assert engine.active_slots == 0 and engine.active_tokens == 0
    assert engine.pool.free_pages == pool_free


def test_admit_denies_impossible_request_typed(engine):
    denied = engine.admit(np.arange(30, dtype=np.int32) % 7, 100)
    assert isinstance(denied, AdmissionDenied)
    assert not denied.retryable
    assert "ceiling" in denied.reason


def test_admit_denies_exhausted_pool_retryable(params):
    """A pool too small for the load defers typed-retryable; releasing a
    request recycles its pages and admission proceeds."""
    AutoDist.reset_default()
    try:
        autodist = AutoDist(strategy_builder=AllReduce())
        # 8 pages (data-degree aligned) -> 7 usable after scratch.
        small = autodist.build_inference(
            params, decode_model=decode_model(CFG),
            n_slots=8, page_len=8, n_pages=8, prefill_chunk=8)
    finally:
        AutoDist.reset_default()
    s1 = small.admit(np.array([1, 2], np.int32), 30)   # 32 tok -> 4 pages
    assert not isinstance(s1, AdmissionDenied)
    denied = small.admit(np.array([3, 4], np.int32), 30)  # needs 4, 3 free
    assert isinstance(denied, AdmissionDenied) and denied.retryable
    assert "page pool exhausted" in denied.reason
    small.release(s1)
    s2 = small.admit(np.array([3, 4], np.int32), 30)
    assert not isinstance(s2, AdmissionDenied)
    small.release(s2)


# ---------------------------------------------------------------- batcher
def test_batcher_completes_all_with_page_recycling(engine):
    """More requests than rows or pages: completion requires recycling
    mid-run."""
    reg = M.MetricsRegistry()
    rng = np.random.default_rng(0)
    with ContinuousBatcher(engine, max_queue=64, registry=reg) as batcher:
        reqs = [
            batcher.submit(rng.integers(1, 96, size=int(rng.integers(2, 8))),
                           max_new_tokens=5)
            for _ in range(20)
        ]
        for r in reqs:
            r.wait(timeout=120)
    assert all(r.state is RequestState.DONE for r in reqs)
    assert all(len(r.tokens) == 5 for r in reqs)
    assert engine.pool.used_pages == 0  # every page recycled
    snap = reg.snapshot()
    assert snap["serve_requests_completed_total"] == 20
    assert snap["serve_tokens_generated_total"] == 100
    assert snap["serve_request_latency_s"]["count"] == 20
    assert np.isfinite(snap["serve_request_latency_s"]["p99"])


def test_batcher_matches_oracle_under_concurrency(params, engine):
    """Batched results are the same tokens the oracle produces — batching
    is scheduling, never semantics."""
    prompts = [np.array([3, 5, 7], np.int32), np.array([60, 2], np.int32),
               np.array([10, 20, 30, 40], np.int32)]
    with ContinuousBatcher(engine, registry=M.MetricsRegistry()) as batcher:
        reqs = [batcher.submit(p, max_new_tokens=6) for p in prompts]
        for r in reqs:
            r.wait(timeout=120)
    for p, r in zip(prompts, reqs):
        assert r.state is RequestState.DONE
        assert r.tokens == uncached_greedy(params, CFG, p, 6)


def test_backpressure_bounded_queue(engine):
    reg = M.MetricsRegistry()
    batcher = ContinuousBatcher(engine, max_queue=2, registry=reg)  # not started
    batcher.submit([1, 2], max_new_tokens=2)
    batcher.submit([3, 4], max_new_tokens=2)
    with pytest.raises(Backpressure):
        batcher.submit([5, 6], max_new_tokens=2)
    assert reg.snapshot()["serve_requests_rejected_total"] == 1


def test_over_ceiling_submit_is_typed_rejection(engine):
    """A request that can NEVER run (over the engine's static max_len)
    comes back already terminal REJECTED — typed admission at the edge,
    not an exception, never a stuck queue head."""
    reg = M.MetricsRegistry()
    batcher = ContinuousBatcher(engine, max_queue=8, registry=reg)
    req = batcher.submit(list(range(1, 31)), max_new_tokens=50)
    assert req.done
    assert req.state is RequestState.REJECTED
    assert req.unservable          # typed cause: HTTP 400 / replay-drop
    assert "ceiling" in req.error
    assert reg.snapshot()["serve_requests_rejected_total"] == 1
    # The queue stayed empty: the rejection never head-blocked anything.
    assert len(batcher._queue) == 0


def test_deadline_times_out_queued_request(engine):
    reg = M.MetricsRegistry()
    with ContinuousBatcher(engine, registry=reg) as batcher:
        req = batcher.submit([1, 2, 3], max_new_tokens=4, timeout_s=-0.001)
        req.wait(timeout=30)
    assert req.state is RequestState.TIMEOUT
    assert reg.snapshot()["serve_requests_timeout_total"] == 1


def test_done_callback_fires_from_scheduler(engine):
    got = []
    with ContinuousBatcher(engine, registry=M.MetricsRegistry()) as batcher:
        req = batcher.submit([4, 2], max_new_tokens=3)
        req.add_done_callback(lambda r: got.append(r.state))
        req.wait(timeout=60)
        deadline = time.monotonic() + 5
        while not got and time.monotonic() < deadline:
            time.sleep(0.01)
    assert got == [RequestState.DONE]
    # Late registration fires immediately.
    late = []
    req.add_done_callback(lambda r: late.append(r.id))
    assert late == [req.id]


# ---------------------------------------------------------------- one-shot
def test_oneshot_infer_matches_direct_apply():
    from autodist_tpu.models import get_model

    spec = get_model("mlp", in_dim=12, hidden=(16,), num_classes=4)
    params = spec.init(jax.random.PRNGKey(1))
    plan_engine = InferenceEngine.build(params, apply_fn=spec.apply)
    x = np.random.default_rng(0).normal(size=(16, 12)).astype(np.float32)
    got = plan_engine.infer(x)
    want = spec.apply(params, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


# ----------------------------------------------------------------- metrics
def test_metrics_registry_counters_gauges_histograms():
    reg = M.MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2)
    reg.gauge("g").set(7.5)
    h = reg.histogram("h")
    for v in range(100):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["c"] == 3
    assert snap["g"] == 7.5
    assert snap["h"]["count"] == 100
    assert abs(snap["h"]["p50"] - 49.5) < 1.5
    assert snap["h"]["p99"] >= 95
    with pytest.raises(TypeError):
        reg.gauge("c")
    text = reg.render_text()
    assert "c 3" in text and 'h{quantile="0.5"}' in text


def test_histogram_reservoir_bounds_memory():
    h = M.Histogram(max_samples=64)
    for v in range(10_000):
        h.observe(float(v))
    assert h.count == 10_000
    assert len(h._samples) == 64
    # A uniform reservoir over [0, 10k): p50 lands mid-range.
    assert 2_000 < h.percentile(50) < 8_000


# --------------------------------------------------------------- api seam
def test_build_inference_checkpoint_roundtrip(tmp_path, params):
    """build_inference(checkpoint=...) restores into plan shardings and the
    served decode matches the in-memory-params decode — the ModelItem +
    checkpoint + Strategy triangle the subsystem was specified around."""
    from autodist_tpu.checkpoint.saver import Saver

    saver = Saver(str(tmp_path))
    saver.save(params, step=3)
    AutoDist.reset_default()
    try:
        autodist = AutoDist(strategy_builder=AllReduce())
        engine = autodist.build_inference(
            jax.eval_shape(lambda: params),  # template only: shapes, no values
            decode_model=decode_model(CFG),
            checkpoint=str(tmp_path),
            n_slots=8, page_len=8, n_pages=17,
        )
    finally:
        AutoDist.reset_default()
    prompt = np.array([8, 6, 4], np.int32)
    assert engine.generate(prompt, 6) == uncached_greedy(params, CFG, prompt, 6)


def test_stop_fails_leftover_requests_terminally(engine):
    """No client may block forever on work nobody will run: stopping a
    batcher (here: one that never started) terminally fails whatever is
    still queued, and later submits are refused."""
    batcher = ContinuousBatcher(engine, registry=M.MetricsRegistry())
    r1 = batcher.submit([1, 2], max_new_tokens=2)
    batcher.stop()
    assert r1.wait(timeout=5).state is RequestState.REJECTED
    assert "stopped" in r1.error
    with pytest.raises(Backpressure, match="stopped"):
        batcher.submit([3, 4], max_new_tokens=2)
