"""EvaByte on the serving path, at a tiny size with every ratio kept
(2 layers, hidden 64, 4 heads of 16, window 64, chunk = page 16, max_len
512), float32, against the family's plain reference
(``perfbench/families/evabyte.py``: the whole sequence, no cache, no pages):

- the model's full-sequence ``forward`` equals the reference;
- chunked prefill then paged decode, on the engine's own pool and tables,
  give the reference's logits position by position across window
  boundaries, and what the engine serves is the reference's best byte;
- what lies past a row's counts is never read: a pool full of NaN changes
  nothing;
- the Mosaic kernel (interpret mode) equals the gather path;
- the pool's accounting under the two-segment layout;
- prefix sharing, int8 pages and speculation over a ring are refused, typed;
- the ring's counters and gauges read what a hand count gives.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from autodist_tpu import metrics as M  # noqa: E402
from autodist_tpu.api import AutoDist  # noqa: E402
from autodist_tpu.models import evabyte as E  # noqa: E402
from autodist_tpu.ops import paged_attention as pa  # noqa: E402
from autodist_tpu.serve import pages as serve_pages  # noqa: E402
from autodist_tpu.serve.batcher import ContinuousBatcher  # noqa: E402
from autodist_tpu.serve.engine import AdmissionDenied  # noqa: E402
from autodist_tpu.strategy import AllReduce  # noqa: E402
from perfbench.harness import manifest  # noqa: E402

FAM = manifest.load_family(os.path.join(ROOT, "perfbench", "families", "evabyte.py"))
MODEL = dict(
    family="evabyte", vocab_size=320, hidden_size=64, intermediate_size=176,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
    max_position_embeddings=512, rms_norm_eps=1e-5, norm_add_unit_offset=True,
    rope_theta=100000, window_size=64, chunk_size=16, num_pred_heads=8,
    param_dtype="float32", compute_dtype="float32")
SEED = 11
W, C = MODEL["window_size"], MODEL["chunk_size"]
RING = W // C
SEQ = np.random.default_rng(5).integers(0, 320, 200).astype(np.int32)


@pytest.fixture(scope="module", autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return FAM.make_params(MODEL, SEED)


@pytest.fixture(scope="module")
def ref_logits():
    return np.asarray(FAM.logits(FAM.reference_params(MODEL, SEED),
                                 jnp.asarray(SEQ), MODEL, "float32"))


def _engine(params, chunk, **more):
    AutoDist.reset_default()
    cfg = FAM.program_config(MODEL, prefill_chunk=chunk)
    autodist = AutoDist(strategy_builder=AllReduce())
    return cfg, autodist.build_inference(
        params, decode_model=E.decode_model(cfg), n_slots=8, max_len=512, **more)


@pytest.fixture(scope="module", params=[16, 32])
def served(request, params):
    try:
        yield _engine(params, request.param)
    finally:
        AutoDist.reset_default()


def _paged_logits(cfg, engine, prompt_len, total, poison=None):
    """Teacher-forced on SEQ: prefill ``SEQ[:prompt_len]`` chunk by chunk
    and decode positions ``prompt_len..total-1`` one at a time, through the
    model's two paged programs on the engine's pool and the table its
    ``admit`` reserved. Returns logits ``[total, V]`` (position ``p``
    predicts ``p + 1``)."""
    if poison is not None:
        engine._cache = jax.device_put(jax.tree.map(
            lambda x: jnp.full_like(x, poison), engine._cache), engine._cache_sh)
    slot = engine.admit(SEQ[:prompt_len], total - prompt_len)
    assert not isinstance(slot, AdmissionDenied), slot
    table = jnp.asarray(engine._table_np[slot.index])
    chunk = engine.prefill_chunk
    prefill = jax.jit(lambda p, t, s, n, c, tab: E.forward_paged_prefill_chunk(
        p, t, s, n, c, tab, cfg, return_logits=True))
    decode = jax.jit(lambda p, t, pos, c, tabs: E.forward_paged_decode_step(
        p, t, pos, c, tabs, cfg, return_logits=True))
    cache, out = engine._cache, np.zeros((total, 320), np.float32)
    try:
        for start in range(0, prompt_len, chunk):
            toks = np.zeros((1, chunk), np.int32)
            valid = SEQ[start:min(start + chunk, prompt_len)]
            toks[0, :len(valid)] = valid
            lg, cache = prefill(engine.params, jnp.asarray(toks), np.int32(start),
                                np.int32(prompt_len), cache, table)
            out[start:start + len(valid)] = np.asarray(lg[0, :len(valid)])
        tables = np.zeros((engine.n_slots, engine.max_pages), np.int32)
        tables[slot.index] = engine._table_np[slot.index]
        for p in range(prompt_len, total):
            toks = np.zeros(engine.n_slots, np.int32)
            pos = np.zeros(engine.n_slots, np.int32)
            toks[slot.index], pos[slot.index] = SEQ[p], p
            lg, cache = decode(engine.params, jnp.asarray(toks), jnp.asarray(pos),
                               cache, jnp.asarray(tables))
            out[p] = np.asarray(lg[slot.index])
    finally:
        # back in the pool's own sharding: another would compile the
        # engine's programs a second time
        engine._cache = jax.device_put(cache, engine._cache_sh)
        engine.release(slot)
    return out


def test_forward_equals_the_reference_across_three_boundaries(params, ref_logits):
    cfg = FAM.program_config(MODEL)
    got = E.forward(params, jnp.asarray(SEQ)[None], cfg)[0]
    assert SEQ.shape[0] // W == 3
    np.testing.assert_allclose(np.asarray(got), ref_logits, atol=1e-4, rtol=0)
    heads = E.forward(params, jnp.asarray(SEQ)[None, :40], cfg, pred_heads=True)
    assert heads.shape == (1, 40, 8, 320)
    np.testing.assert_allclose(np.asarray(heads[0, :, 0]), ref_logits[:40], atol=1e-4)


# prompt ends one before a boundary, on it, one past it; a decode that
# crosses one; a prompt of several windows whose decode crosses another
CASES = [(W - 1, W + 8), (W, W + 8), (W + 1, W + 9), (2 * W - 10, 2 * W + 6),
         (2 * W + 20, 3 * W + 8)]


@pytest.mark.parametrize("prompt_len,total", CASES)
def test_prefill_then_decode_give_the_reference_logits(served, ref_logits,
                                                       prompt_len, total):
    cfg, engine = served
    got = _paged_logits(cfg, engine, prompt_len, total)
    np.testing.assert_allclose(got, ref_logits[:total], atol=1e-4, rtol=0)
    assert engine.pool.used_pages == 0


@pytest.mark.parametrize("prompt_len,n_new", [(W - 1, 12), (2 * W - 6, 20)])
def test_what_the_engine_serves_is_the_references_best_byte(served, params,
                                                            prompt_len, n_new):
    _, engine = served
    tokens = engine.generate(SEQ[:prompt_len], n_new)
    assert len(tokens) == n_new
    seq = np.concatenate([SEQ[:prompt_len], np.asarray(tokens, np.int32)])
    table = np.asarray(FAM.logits(FAM.reference_params(MODEL, SEED),
                                  jnp.asarray(seq), MODEL, "float32"))
    at = np.arange(prompt_len - 1, prompt_len + n_new - 1)
    gap = table[at].max(-1) - table[at, np.asarray(tokens)]
    assert gap.max() <= 1e-4
    assert engine.compiled_programs == 2


def test_a_pool_full_of_nan_changes_nothing(served, ref_logits):
    """Recycled ring pages and summary rows whose window is open hold
    whatever was there: every entry a query sees was written first, and
    nothing else reaches the sum."""
    cfg, engine = served
    got = _paged_logits(cfg, engine, 2 * W + 20, 3 * W + 8, poison=jnp.nan)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, ref_logits[:3 * W + 8], atol=1e-4, rtol=0)


# ------------------------------------------------------------- the kernel
def _pool(rng, n_pages=40, h=4, d=16):
    return (jnp.asarray(rng.standard_normal((n_pages, h, C, d)), jnp.float32),
            jnp.asarray(rng.standard_normal((n_pages, h, C, d)), jnp.float32))


@pytest.mark.parametrize("positions, blocking", [
    ([[5], [W + 17], [3 * W + W - 1]], None),   # decode rows, partial last pages
    ([list(range(2 * W + 16, 2 * W + 48))], None),  # one prefill chunk in window 2
    ([list(range(0, 16))], None),               # the very first chunk
    # query tiles of 8 against groups of 32 entries: tiles that see a group
    # whole, tiles the counts cut through and tiles that skip it
    ([list(range(W, W + 32))], (2, 2, 8)),
    ([list(range(2 * W + 32, 3 * W))], (2, 4, 8)),
    ([list(range(3 * W, 3 * W + 64))], (1, 2, 16)),
])
def test_kernel_in_interpret_mode_equals_the_gather_path(positions, blocking,
                                                         monkeypatch):
    if blocking:
        monkeypatch.setattr(pa, "_eva_blocking", lambda *a: blocking)
    rng = np.random.default_rng(3)
    k, v = _pool(rng)
    pos = jnp.asarray(positions, jnp.int32)
    b, n_q = pos.shape
    tables = jnp.asarray(rng.permutation(np.arange(1, 40))[:b * (RING + 2)]
                         .reshape(b, RING + 2), jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, 4, n_q, 16)), jnp.float32)
    kw = dict(ring_pages=RING, window=W)
    want = pa.eva_paged_attention(q, k, v, tables, pos, impl="gather", **kw)
    got = pa.eva_paged_attention(q, k, v, tables, pos, impl="kernel", **kw)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    # what lies past a row's counts may hold anything
    exact, n_sum = pa.eva_entry_counts(pos.max(axis=1), W, C)
    for impl in ("gather", "kernel"):
        kk, vv = k, v
        for row in range(b):
            for e in range(RING + 2):
                first = e * C if e < RING else (e - RING) * C
                seen = exact[row] if e < RING else n_sum[row]
                if first >= seen:
                    kk = kk.at[tables[row, e]].set(jnp.nan)
                    vv = vv.at[tables[row, e]].set(jnp.nan)
        again = pa.eva_paged_attention(q, kk, vv, tables, pos, impl=impl, **kw)
        np.testing.assert_allclose(again, want, atol=1e-5, rtol=1e-5)


def test_chunk_summaries_follow_the_equations():
    rng = np.random.default_rng(4)
    k = rng.standard_normal((3, 2, C, 8)).astype(np.float32)
    v = rng.standard_normal((3, 2, C, 8)).astype(np.float32)
    mu, phi = (rng.standard_normal((2, 8)).astype(np.float32) for _ in range(2))
    ks, vs = pa.chunk_summaries(jnp.asarray(k), jnp.asarray(v), mu, phi)
    for j in range(3):
        for h in range(2):
            for got, x, w in ((ks, k, mu), (vs, v, phi)):
                z = k[j, h] @ w[h] * 8 ** -0.5
                p = np.exp(z - z.max())
                np.testing.assert_allclose(got[j, h], (p / p.sum()) @ x[j, h], atol=1e-5)


# ---------------------------------------------------------------- the pool
def test_admission_reserves_ring_and_summary_pages(served):
    _, engine = served
    layout = engine.layout
    assert (layout.window, layout.page_len, layout.ring_pages) == (W, C, RING)
    assert engine.max_pages == RING + 512 // (C * C)
    rows = [(20, 10), (60, 30), (100, 100), (300, 200)]
    slots, want = [], 0
    for prompt, new in rows:
        slot = engine.admit(np.zeros(prompt, np.int32), new)
        total = prompt + new
        n = min(RING, -(-total // C)) + -(-total // (C * C))
        assert len(engine._tables[slot.index].pages) == n
        row = engine._table_np[slot.index]
        assert (row[:min(RING, -(-total // C))] > 0).all()
        assert (row[RING:RING + -(-total // (C * C))] > 0).all()
        assert (row > 0).sum() == n
        want += n
        slots.append(slot)
    assert engine.pool.used_pages == want
    assert engine.ring_pages_in_use + engine.summary_pages_in_use == want
    assert engine.summary_pages_in_use == 1 + 1 + 1 + 2
    assert engine.page_utilization == pytest.approx(want / engine.pool.usable_pages)
    for slot in slots:
        engine.release(slot)
    assert engine.pool.used_pages == 0 and engine.ring_pages_in_use == 0
    assert engine.check_admissible(500, 12) is None
    denied = engine.check_admissible(500, 13)
    assert denied is not None and not denied.retryable
    assert engine.page_bytes == 2 * 2 * 4 * C * 16 * 4     # k, v x layers x H x c x d x f32


def test_a_pool_short_of_every_row_makes_requests_wait_and_serves_them(params):
    """Where the pool's share of the memory funds fewer pages than every
    row at the full timeline (the benchmark's cell: 751 pages, where four
    rows at ``max_len`` take 1,025), a request that needs more than are
    free waits in the queue, retryable, and is served when another
    retires: the same bytes as alone."""
    try:
        _, engine = _engine(params, 32, n_pages=16)
        assert engine.pool.usable_pages == 15 < 8 * engine.max_pages
        alone = engine.generate(SEQ[:150], 30)
        held = [engine.admit(SEQ[:150], 30) for _ in range(3)]   # 4 ring + 1 summary each
        denied = engine.admit(SEQ[:60], 10)                      # 4 + 1 of none free
        assert isinstance(denied, AdmissionDenied) and denied.retryable
        assert "need 5" in denied.reason
        for slot in held:
            engine.release(slot)
        batcher = ContinuousBatcher(engine, registry=M.MetricsRegistry())
        batcher.start()
        try:
            reqs = [batcher.submit(SEQ[:150], max_new_tokens=30) for _ in range(5)]
            assert all(r.wait(timeout=300) for r in reqs)
        finally:
            batcher.stop(drain=False, timeout_s=60.0)
        assert [list(r.tokens) for r in reqs] == [list(alone)] * 5
        # the fourth and fifth waited for a whole request to retire
        waits = sorted(r.queue_wait_s for r in reqs)
        assert waits[3] > 3 * max(waits[2], 1e-4)
        assert engine.pool.used_pages == 0
    finally:
        AutoDist.reset_default()


def test_layout_arithmetic():
    lay = serve_pages.CacheLayout(page_len=16, window=2048, prefill_chunk=512, page_axis=0)
    assert lay.split(24960) == (128, 98) and lay.split(100) == (7, 1)
    assert lay.table_width(32768) == 256
    assert lay.rolls(2047, 2048) == (1, 1) and lay.rolls(2048, 2049) == (0, 0)
    assert lay.rolls(0, 4096) == (2, 256)
    assert lay.resident_rows(5000) == 2048 + 312
    plain = serve_pages.CacheLayout(page_len=16)
    assert plain.split(100) == (7, 0) and plain.table_width(1024) == 64
    assert plain.rolls(0, 4096) == (0, 0) and plain.resident_rows(77) == 77
    for bad in (24, 96, 4096):
        with pytest.raises(ValueError):
            serve_pages.CacheLayout(page_len=16, window=2048, prefill_chunk=bad)
    with pytest.raises(ValueError):
        serve_pages.CacheLayout(page_len=16, window=100)


# ------------------------------------------------------- what it refuses
def test_prefix_cache_int8_pool_and_speculation_are_refused_typed(params):
    try:
        with pytest.raises(serve_pages.CacheFeatureRefused, match="prefix sharing"):
            _engine(params, 16, prefix_cache=True)
        AutoDist.reset_default()
        dm = E.decode_model(FAM.program_config(MODEL))
        # an int8 pool is known by its scale planes (as the engine detects
        # GPT-2's): a cache pytree that carries them over a ring is refused
        planes = lambda n, pl: dict(  # noqa: E731
            dm.init_paged_cache(n, pl), k_scale=jnp.zeros((n, 4, pl)),
            v_scale=jnp.zeros((n, 4, pl)))
        with pytest.raises(serve_pages.CacheFeatureRefused, match="int8"):
            AutoDist(strategy_builder=AllReduce()).build_inference(
                params, decode_model=dataclasses.replace(dm, init_paged_cache=planes),
                n_slots=8, max_len=512)
        AutoDist.reset_default()
        with pytest.raises(serve_pages.CacheFeatureRefused, match="speculative"):
            AutoDist(strategy_builder=AllReduce()).build_inference(
                params, decode_model=dm, draft_params=params,
                draft_decode_model=dm, n_slots=8, max_len=512)
        AutoDist.reset_default()
        with pytest.raises(ValueError, match="pages of 16"):
            AutoDist(strategy_builder=AllReduce()).build_inference(
                params, decode_model=dm, n_slots=8, max_len=512, page_len=8)
        AutoDist.reset_default()
        with pytest.raises(ValueError, match="divides the window"):
            AutoDist(strategy_builder=AllReduce()).build_inference(
                params, decode_model=dm, n_slots=8, max_len=512, prefill_chunk=48)
    finally:
        AutoDist.reset_default()


# ------------------------------------------------------ counters and gauges
def test_ring_counters_and_gauges_read_a_hand_count(params):
    """One request, 100 bytes in and 40 out: the prefill passes position 64
    (one boundary) and six chunk ends; the 39 decode steps write positions
    100..138 and pass 128 (one boundary) and two chunk ends (112, 128)."""
    from autodist_tpu.obs import spans as obs_spans

    try:
        _, engine = _engine(params, 16)
        reg = M.MetricsRegistry()
        seen = []
        obs_spans.get_tracer().clear()      # the ring is the process's

        def on_tick(_dt):
            seen.append((reg.gauge("serve_ring_pages_in_use").value,
                         reg.gauge("serve_summary_pages_in_use").value))

        batcher = ContinuousBatcher(engine, registry=reg, on_tick=on_tick)
        batcher.start()
        try:
            req = batcher.submit(SEQ[:100], max_new_tokens=40)
            assert req.wait(timeout=300)
        finally:
            batcher.stop(drain=False, timeout_s=60.0)
        assert len(req.tokens) == 40
        assert reg.counter("serve_window_rolls_total").value == 2
        assert reg.counter("serve_window_rolls_decode_total").value == 1
        assert reg.counter("serve_summary_chunks_total").value == 6 + 2
        # 140 positions: the whole ring of 4 pages and one summary page
        assert (4, 1) in seen
        assert (reg.gauge("serve_ring_pages_in_use").value,
                reg.gauge("serve_summary_pages_in_use").value) == (0, 0)
        stamped = [s.attrs for s in obs_spans.get_tracer().spans()
                   if s.name == "serve.tick_metrics" and "window_rolls" in s.attrs]
        assert stamped and stamped[-1]["window_rolls"] == 2
        assert stamped[-1]["window_rolls_decode"] == 1
        assert max(a["ring_pages"] for a in stamped) == 4
    finally:
        AutoDist.reset_default()
