"""Paged serving pins (ISSUE 12 acceptance bars).

- **token-stream bit-equality** on the same checkpoint between the paged
  engine (page-table gather or kernel, chunked prefill) and the plain
  oracle, greedy decode over the uncached ``forward``
  (``serve.server.uncached_greedy``) — including a request that joins
  mid-batch and a chunked prefill interleaved with a live decode;
- **page recycling**: retirement returns pages to the pool and a recycled
  page serves a new request correctly (stale KV rows are dead weight);
- **exactly two compiled serving programs** for any request-length mix;
- **page-pool unit semantics** (all-or-nothing alloc, scratch reservation,
  fragmentation accounting, double-free refusal);
- **analyzer accounting**: the static page pool joins the SLM passes'
  per-chip HBM budget as a named tenant.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.api import AutoDist
from autodist_tpu.models.transformer import (
    TransformerConfig,
    decode_model,
    init_params,
)
from autodist_tpu.serve import pages as serve_pages
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
def paged(params):
    AutoDist.reset_default()
    try:
        autodist = AutoDist(strategy_builder=AllReduce())
        yield autodist.build_inference(
            params, decode_model=decode_model(CFG),
            n_slots=8, page_len=8, n_pages=33, prefill_chunk=8)
    finally:
        AutoDist.reset_default()


@pytest.fixture(scope="module")
def oracle(params):
    """``oracle(prompt, n_new)``: the uncached forward's greedy stream on
    the same checkpoint."""
    return lambda prompt, n_new: uncached_greedy(params, CFG, prompt, n_new)


def prefill_all(engine, slot):
    first = None
    while first is None:
        first = engine.prefill_step(slot)
    return first


# ----------------------------------------------------- stream bit-equality
def test_paged_matches_oracle_greedy_streams(paged, oracle):
    """Same checkpoint, same prompts: identical greedy token streams from
    the paged gather path and the uncached forward — short, page-
    crossing, and multi-chunk prompts."""
    rng = np.random.default_rng(7)
    prompts = [
        np.array([5, 17, 3, 88, 2], np.int32),
        rng.integers(1, 96, size=12).astype(np.int32),   # crosses a page
        rng.integers(1, 96, size=20).astype(np.int32),   # 3 prefill chunks
    ]
    for p in prompts:
        assert paged.generate(p, 10) == oracle(p, 10), p


@pytest.mark.parametrize("impl", ["gather", "kernel"])
def test_paged_stream_equals_the_uncached_oracle(params, paged, oracle, impl):
    """The pool as a leaf a layer, ``[n_pages, page_len, heads * head_dim]``,
    on both attention paths (the kernel in interpret mode here): the greedy
    stream equals the uncached oracle's (``forward`` over the whole
    sequence for every token)."""
    import dataclasses

    from autodist_tpu.serve.engine import InferenceEngine

    cfg = dataclasses.replace(CFG, paged_attention_impl=impl)
    engine = InferenceEngine(params, paged.plan, decode_model=decode_model(cfg),
                             n_slots=4, page_len=8, n_pages=17, prefill_chunk=8)
    leaves = jax.tree_util.tree_leaves(engine._cache)
    assert len(leaves) == 2 * CFG.num_layers
    assert {leaf.shape for leaf in leaves} == {(engine.pool.n_pages, 8, CFG.d_model)}
    rng = np.random.default_rng(31)
    for n in (3, 8, 13, 21):         # inside a page, a whole page, across pages
        p = rng.integers(1, 96, size=n).astype(np.int32)
        assert engine.generate(p, 9) == oracle(p, 9), (impl, n)


def test_mid_batch_join_matches_solo_oracle(paged, oracle):
    """A request joining mid-decode, and the one it joins, each see the
    stream the oracle gives that request alone — batching (and paging) is
    scheduling, never semantics."""
    p1 = np.array([3, 9, 27], np.int32)
    p2 = np.array([44, 8, 15, 16, 23], np.int32)
    n = 8

    s1 = paged.admit(p1, n)
    got1 = [prefill_all(paged, s1)] + [paged.step()[s1] for _ in range(3)]
    s2 = paged.admit(p2, n)
    got2 = [prefill_all(paged, s2)]
    while len(got1) < n or len(got2) < n:
        out = paged.step()
        if len(got1) < n:
            got1.append(out[s1])
        if len(got2) < n:
            got2.append(out[s2])
    paged.release(s1)
    paged.release(s2)

    assert got1 == oracle(p1, n)
    assert got2 == oracle(p2, n)


def test_chunked_prefill_interleaves_with_decode_oracle(paged, oracle):
    """A long prompt prefills chunk-by-chunk BETWEEN decode steps of an
    already-active request; neither stream changes. This is the stall the
    paged engine deletes: the active decode advances one token per tick
    throughout the newcomer's prefill."""
    p_short = np.array([5, 17, 3, 88, 2], np.int32)
    p_long = np.arange(1, 21, dtype=np.int32)           # 3 chunks of 8
    n = 8

    ref_short = oracle(p_short, n)
    ref_long = oracle(p_long, n)

    s1 = paged.admit(p_short, n)
    got1 = [prefill_all(paged, s1)]
    s2 = paged.admit(p_long, n)
    got2 = []
    chunks = 0
    while not got2:
        first = paged.prefill_step(s2)        # ONE chunk...
        chunks += 1
        if first is not None:
            got2.append(first)
        out = paged.step()                    # ...then a decode tick
        if s1 in out and len(got1) < n:
            got1.append(out[s1])
        if got2 and s2 in out and len(got2) < n:
            got2.append(out[s2])
    assert chunks == 3                        # 20 tokens / 8-token chunks
    assert len(got1) >= 3                     # decode advanced every tick
    while len(got1) < n or len(got2) < n:
        out = paged.step()
        if len(got1) < n:
            got1.append(out[s1])
        if len(got2) < n:
            got2.append(out[s2])
    paged.release(s1)
    paged.release(s2)

    assert got1 == ref_short
    assert got2 == ref_long


def test_next_chunk_goes_out_behind_the_decode_step(paged):
    """With a row decoding, a prompt's next chunk is dispatched inside the
    decode step, between its dispatch and the wait for its tokens, so the
    device never waits for the host between the two programs; the final
    chunk stays with ``prefill_step``. The programs reach the device in the
    order they always did, so the streams are those of an engine that
    looks no chunk ahead; a row released mid-prefill leaves no chunk
    owed."""
    from autodist_tpu.obs import spans as obs_spans

    p_short = np.array([5, 17, 3, 88, 2], np.int32)
    p_long = np.arange(1, 29, dtype=np.int32)           # 4 chunks of 8

    def serve(lookahead):
        paged.prefill_lookahead = lookahead
        tracer = obs_spans.get_tracer()
        tracer.clear()
        s1 = paged.admit(p_short, 8)
        got1 = [prefill_all(paged, s1)]
        s2 = paged.admit(p_long, 4)
        got2, calls = [], 0
        while not got2:
            first = paged.prefill_step(s2)
            calls += 1
            if first is not None:
                got2.append(first)
            got1.append(paged.step()[s1])
        chunks = [s for s in tracer.spans() if s.name == "serve.prefill_chunk"
                  and s.attrs.get("prompt_len") == len(p_long)]
        by_id = {s.id: s for s in tracer.spans()}
        parents = [by_id[s.parent].name if s.parent in by_id else None
                   for s in chunks]
        paged.release(s1)
        paged.release(s2)
        return got1, got2, calls, parents, [s.attrs["final"] for s in chunks]

    try:
        ahead = serve(True)
        plain = serve(False)
    finally:
        paged.prefill_lookahead = True
    assert ahead[:3] == plain[:3] and ahead[2] == 4
    assert ahead[4] == plain[4] == [False, False, False, True]
    assert ahead[3] == [None, "serve.decode_step", "serve.decode_step", None]
    assert plain[3] == [None] * 4

    s1 = paged.admit(p_short, 8)
    prefill_all(paged, s1)
    s2 = paged.admit(p_long, 4)
    paged.prefill_step(s2)
    paged.step()                                # chunk 2 is out already
    assert paged._chunk_ahead[s2.index]
    paged.release(s2)
    assert not paged._chunk_ahead.any()
    s3 = paged.admit(p_long, 4)                 # the row again, from 0
    assert s3.index == s2.index
    assert prefill_all(paged, s3) == ahead[1][0]
    paged.release(s1)
    paged.release(s3)


# ---------------------------------------------------------- page recycling
def test_page_recycling_after_retirement_oracle(paged, oracle):
    """Retired pages return to the pool and are REUSED (LIFO) by the next
    admission; a recycled page's stale KV rows never leak into the new
    request's stream."""
    free0 = paged.pool.free_pages
    p = np.array([11, 22, 33, 44], np.int32)
    s = paged.admit(p, 12)                    # 16 tokens -> 2 pages
    held = list(paged._tables[s.index].pages)
    assert paged.pool.free_pages == free0 - 2
    prefill_all(paged, s)
    paged.step()
    paged.release(s)
    assert paged.pool.free_pages == free0

    q = np.array([7, 7, 7], np.int32)
    s2 = paged.admit(q, 12)                   # 15 tokens -> 2 pages
    reused = list(paged._tables[s2.index].pages)
    assert set(reused) & set(held)            # LIFO: warm pages come back
    got = [prefill_all(paged, s2)]
    while len(got) < 12:
        got.append(paged.step()[s2])
    paged.release(s2)
    assert got == oracle(q, 12)               # stale rows never read


def test_exactly_two_programs_for_any_length_mix(paged):
    """The compile-count acceptance pin: after short, page-crossing and
    multi-chunk requests, the engine holds exactly one compiled decode
    program and one compiled prefill-chunk program."""
    rng = np.random.default_rng(3)
    for size in (3, 9, 14, 19):
        paged.generate(rng.integers(1, 96, size=size).astype(np.int32), 6)
    assert paged.compiled_programs == 2


# ------------------------------------------------------------- pages.py unit
def test_dispatch_spans_count_the_paged_kernels_groups():
    """The decode step's and the prefill chunk's dispatch spans carry the
    page groups the paged kernel walks in a layer's call and those it does
    not skip (``kv_groups``, ``kv_groups_live``): the kernel's own blocking
    on the host's lengths. A table of 32 pages of 8 is two groups of 128
    keys a row, so a prompt of 140 tokens has its first 16 chunks skip the
    second group and the last two see it, and its decode steps walk two
    live groups beside the idle rows' one each. The registry's two
    counters add up to the spans' sums."""
    from autodist_tpu import metrics as M
    from autodist_tpu.obs import spans as obs_spans
    from autodist_tpu.ops.paged_attention import paged_group_counts
    from autodist_tpu.serve.batcher import ContinuousBatcher, RequestState

    cfg = TransformerConfig(
        vocab_size=97, num_layers=2, d_model=32, num_heads=2, d_ff=64,
        max_seq_len=256, causal=True, dtype=jnp.float32)
    AutoDist.reset_default()
    try:
        engine = AutoDist(strategy_builder=AllReduce()).build_inference(
            init_params(jax.random.PRNGKey(0), cfg),
            decode_model=decode_model(cfg),
            n_slots=4, page_len=8, n_pages=65, prefill_chunk=8)
    finally:
        AutoDist.reset_default()
    assert engine.max_pages == 32
    shape = (engine.max_pages, 8, 32, 4)    # table, page_len, lanes, float32

    tracer = obs_spans.get_tracer()
    tracer.clear()
    registry = M.MetricsRegistry()
    batcher = ContinuousBatcher(engine, registry=registry)
    batcher.start()
    try:
        req = batcher.submit(np.arange(1, 141, dtype=np.int32) % 96 + 1, 4)
        assert req.wait(300.0).state is RequestState.DONE
    finally:
        batcher.stop()
    spans = tracer.spans()
    chunks = [s for s in spans if s.name == "serve.prefill_chunk"]
    steps = [s for s in spans if s.name == "serve.decode_dispatch"]
    assert len(chunks) == 18 and len(steps) == 3
    for sp in chunks:
        want = paged_group_counts([sp.attrs["start"] + 8], 8, *shape)
        assert (sp.attrs["kv_groups"], sp.attrs["kv_groups_live"]) == want
    assert [s.attrs["kv_groups_live"] for s in chunks] == [1] * 16 + [2] * 2
    idle = engine.n_slots - 1                   # rows at position 0
    for i, sp in enumerate(steps):
        want = paged_group_counts([140 + i + 1] + [1] * idle, 1, *shape)
        assert (sp.attrs["kv_groups"], sp.attrs["kv_groups_live"]) == want
        assert want == (2 * engine.n_slots, 2 + idle)
    for sp in chunks + steps:
        assert 1 <= sp.attrs["kv_groups_live"] <= sp.attrs["kv_groups"]
    assert registry.counter("serve_kv_groups_total").value == sum(
        s.attrs["kv_groups"] for s in chunks + steps) == (
            18 * 2 + 3 * 2 * engine.n_slots)
    assert registry.counter("serve_kv_groups_live_total").value == sum(
        s.attrs["kv_groups_live"] for s in chunks + steps) == (
            16 + 4 + 3 * (2 + idle))


class TestPagePool:
    def test_alloc_is_all_or_nothing_and_scratch_reserved(self):
        pool = serve_pages.build_pool(5, page_len=8)     # 4 usable
        t1 = pool.alloc(17)                              # 3 pages
        assert t1 is not None and len(t1.pages) == 3
        assert serve_pages.SCRATCH_PAGE not in t1.pages
        assert pool.alloc(9) is None                     # needs 2, has 1
        t2 = pool.alloc(8)                               # exactly 1
        assert t2 is not None and pool.free_pages == 0
        pool.release(t1)
        pool.release(t2)
        assert pool.free_pages == 4 and pool.used_pages == 0

    def test_padded_table_pads_with_scratch(self):
        pool = serve_pages.build_pool(9, page_len=4)
        t = pool.alloc(10)                               # 3 pages
        row = t.padded(6)
        assert row.dtype == np.int32 and row.shape == (6,)
        assert list(row[:3]) == t.pages
        assert all(r == serve_pages.SCRATCH_PAGE for r in row[3:])

    def test_fragmentation_and_utilization(self):
        pool = serve_pages.build_pool(9, page_len=8)     # 8 usable
        t = pool.alloc(20)                               # 3 pages = 24 slots
        assert pool.utilization == pytest.approx(3 / 8)
        assert pool.fragmentation(20) == pytest.approx(4 / 24)
        assert pool.fragmentation(0) == 1.0
        pool.release(t)
        assert pool.fragmentation(0) == 0.0

    def test_double_free_refused(self):
        pool = serve_pages.build_pool(3, page_len=4)
        t = pool.alloc(4)
        stale = list(t.pages)
        pool.release(t)
        t.pages = stale                      # a buggy caller re-releasing
        with pytest.raises(ValueError, match="double free"):
            pool.release(t)

    def test_pages_for_tokens(self):
        assert serve_pages.pages_for_tokens(1, 8) == 1
        assert serve_pages.pages_for_tokens(8, 8) == 1
        assert serve_pages.pages_for_tokens(9, 8) == 2


# ------------------------------------------------------- analyzer accounting
def test_hbm_budget_accounts_serve_page_pool(paged):
    """The static page pool is a named tenant of the SLM budget: it rides
    the state sum, the summary, and can head the overcommit blame line."""
    from autodist_tpu.analysis import hbm_budget
    from autodist_tpu.resource_spec import ResourceSpec

    spec = ResourceSpec(resource_dict={
        "nodes": [{"address": "localhost", "chips": 1, "chief": True}],
        "tpu": {"hbm_gb": 16.0},
    })
    pool_bytes = paged.page_pool_bytes
    assert pool_bytes > 0
    base_findings, base = hbm_budget(paged.plan, resource_spec=spec)
    findings, summary = hbm_budget(
        paged.plan, resource_spec=spec, serve_pool_bytes=pool_bytes)
    assert summary["serve_pool_gb_per_chip"] == pytest.approx(pool_bytes / 1e9)
    assert summary["state_gb_per_chip"] == pytest.approx(
        base["state_gb_per_chip"] + pool_bytes / 1e9)
    # A pool sized past capacity must trip SLM001 and name the tenant.
    over, over_summary = hbm_budget(
        paged.plan, resource_spec=spec, serve_pool_bytes=32e9)
    assert any(f.code == "SLM001" for f in over)
    assert "serve.page_pool" in over_summary["top_vars"]


def test_pool_size_from_spec_caps_and_floors():
    from autodist_tpu.resource_spec import ResourceSpec

    spec = ResourceSpec(resource_dict={
        "nodes": [{"address": "localhost", "chips": 1, "chief": True}],
        "tpu": {"hbm_gb": 1.0},
    })
    # Plenty of HBM for tiny pages -> capped at max_useful (+ scratch).
    assert serve_pages.pool_size_from_spec(
        spec, bytes_per_page=1024, max_useful_pages=10) == 11
    # No budget at all -> floors at a functioning pool (+ scratch); the
    # analyzer, not the constructor, reports the overcommit.
    assert serve_pages.pool_size_from_spec(
        spec, bytes_per_page=1e12, min_useful_pages=4) == 5
