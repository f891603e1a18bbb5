"""Copy-on-write prefix sharing over the page pool (ISSUE 16).

- **radix unit semantics** (no device): chained block hashing, the
  match cap that keeps the final prompt token prefilling, insert/match/
  refcount/release cycles, COW frontier probing, LRU eviction that only
  ever takes refcount-0 leaves, refcount-underflow detection, and the
  purge leak check;
- **bit-identical streams** sharing on vs off: cold insert, warm match,
  page-boundary prefixes, mid-page COW divergence (exactly one frontier
  copy), mid-batch joins through the continuous batcher, spec-decode
  engines at k in {1, 4} sharing ONE tree across target + draft pools,
  and failover replay of a shared-prefix stream through the router;
- **accounting**: physical (deduped) pool utilization, the
  ``logical/physical`` sharing ratio, ``shared_fraction``, and the
  eviction-under-pressure zero-leak drain.

All CPU-sim (``JAX_PLATFORMS=cpu``); the ``--selftest-prefix`` CLI run
proves the >=5x TTFT / >=2x concurrency performance bars — this file
pins semantics.
"""
import numpy as np
import pytest

from autodist_tpu.serve import pages as serve_pages
from autodist_tpu.serve import prefix as serve_prefix
from autodist_tpu.serve.prefix import Lease, block_hashes, build_prefix_cache

MAX_NEW = 6
PAGE = 4  # unit-test block size


# ----------------------------------------------------------- unit: hashing
class TestBlockHashes:
    def test_chained_not_positional(self):
        a = block_hashes(np.arange(12, dtype=np.int32), PAGE)
        b = block_hashes(
            np.concatenate([[99], np.arange(1, 12)]).astype(np.int32), PAGE)
        assert len(a) == len(b) == 3
        # Changing block 0 changes EVERY downstream hash (the chain
        # commits to the whole prefix), even though blocks 1-2 are equal.
        assert a[0] != b[0] and a[1] != b[1] and a[2] != b[2]

    def test_only_full_blocks_and_limit(self):
        toks = np.arange(11, dtype=np.int32)     # 2 full blocks + 3 spare
        assert len(block_hashes(toks, PAGE)) == 2
        assert block_hashes(toks, PAGE, limit=1) == \
            block_hashes(toks, PAGE)[:1]

    def test_shared_prefix_shares_hashes(self):
        sys_p = np.arange(8, dtype=np.int32)
        a = block_hashes(np.concatenate([sys_p, [50, 51, 52, 53]]), PAGE)
        b = block_hashes(np.concatenate([sys_p, [60, 61, 62, 63]]), PAGE)
        assert a[:2] == b[:2] and a[2] != b[2]


# -------------------------------------------------------- unit: tree cycle
def _tree(n_pages=17):
    pool = serve_pages.build_pool(n_pages, PAGE)
    return build_prefix_cache(pool, PAGE), pool


def _admit_insert(cache, pool, prompt):
    """The engine's admit+prefill bookkeeping, tree side only: match,
    lease, alloc the suffix, adopt the full-prompt blocks."""
    m = cache.match(prompt)
    lease = cache.acquire(m)
    table = pool.alloc(len(prompt) - m.n_full * PAGE)
    assert table is not None
    pages = [nd.page for nd in lease.nodes] + list(table.pages)
    cache.insert(prompt, pages, lease)
    return lease, table


class TestRadixTree:
    def test_match_cap_leaves_final_token(self):
        cache, pool = _tree()
        prompt = np.arange(12, dtype=np.int32)   # exactly 3 full blocks
        lease, _ = _admit_insert(cache, pool, prompt)
        assert cache.cached_pages == 3
        # A full re-match may lease at most (12-1)//4 = 2 blocks: the
        # final prompt token always prefills, so the first generated
        # token always comes from the engine's own program.
        m = cache.match(prompt)
        assert m.n_full == 2
        # ... and the divergence block probes the adopted third block as
        # the COW frontier (3 of its 4 tokens usable).
        assert m.tail_node is not None and m.tail_len == 3
        cache.release(lease)

    def test_refcount_cycle_and_shared_pages(self):
        cache, pool = _tree()
        prompt = np.concatenate(
            [np.arange(8), [90, 91, 92, 93]]).astype(np.int32)
        l1, _ = _admit_insert(cache, pool, prompt)
        other = np.concatenate(
            [np.arange(8), [80, 81, 82, 83]]).astype(np.int32)
        m = cache.match(other)
        assert m.n_full == 2                     # shared 8-token prefix
        l2 = cache.acquire(m)
        assert cache.live_refcount == 3 + 2      # adopter holds 3, lease 2
        assert cache.shared_pages == 3
        cache.release(l2)
        cache.release(l1)
        assert cache.live_refcount == 0
        # Released pages stay CACHED (that is the point) until eviction.
        assert cache.cached_pages == 3 and pool.used_pages >= 3

    def test_cancel_rolls_back_tail_pin(self):
        cache, pool = _tree()
        prompt = np.arange(12, dtype=np.int32)
        lease, _ = _admit_insert(cache, pool, prompt)
        cache.release(lease)
        m = cache.match(prompt)                  # tail pins block 3
        l2 = cache.acquire(m)
        assert cache.live_refcount == 3          # 2 full + 1 tail pin
        cache.cancel(l2)
        assert cache.live_refcount == 0

    def test_insert_skips_present_blocks(self):
        cache, pool = _tree()
        prompt = np.arange(12, dtype=np.int32)
        l1, _ = _admit_insert(cache, pool, prompt)
        inserts_before = cache.inserts
        # A duplicate prefill loses the adoption race harmlessly: every
        # block is already present, so nothing is adopted — the request
        # keeps (and later recycles) its own pages.
        m = cache.match(prompt)
        l2 = cache.acquire(m)
        cache.unpin_tail(l2)
        t2 = pool.alloc(len(prompt) - m.n_full * PAGE)
        adopted = cache.insert(
            prompt, [nd.page for nd in l2.nodes] + list(t2.pages), l2)
        assert adopted == 0
        assert cache.inserts == inserts_before
        # An EXTENSION adopts only its novel suffix block.
        longer = np.arange(16, dtype=np.int32)   # first 12 already cached
        l3, _ = _admit_insert(cache, pool, longer)
        assert cache.inserts == inserts_before + 1
        cache.release(l3)
        cache.release(l2)
        cache.release(l1)

    def test_evict_lru_refcount0_leaves_only(self):
        cache, pool = _tree()
        a = np.concatenate([np.arange(8), [90, 91, 92, 93]]).astype(np.int32)
        b = np.concatenate([[70] * 8, [71, 72, 73, 74]]).astype(np.int32)
        la, _ = _admit_insert(cache, pool, a)
        lb, _ = _admit_insert(cache, pool, b)
        # While leased, NOTHING is evictable.
        assert cache.evict(10) == 0
        cache.release(la)
        cache.release(lb)
        # Touch chain A so chain B is the LRU victim.
        cache.release(cache.acquire(cache.match(a)))
        free_before = pool.free_pages
        assert cache.evict(1) == 1
        assert pool.free_pages == free_before + 1
        remaining = {tuple(nd.tokens) for nd in cache._owned.values()}
        assert tuple(b[8:]) not in remaining     # B's leaf went first
        # Interior nodes become evictable only once their subtree is
        # gone: purge peels leaves repeatedly down to an empty tree.
        assert cache.purge() == 5                # the 5 remaining pages
        assert cache.cached_pages == 0
        assert pool.used_pages == 0

    def test_release_underflow_raises(self):
        cache, pool = _tree()
        prompt = np.arange(12, dtype=np.int32)
        lease, _ = _admit_insert(cache, pool, prompt)
        cache.release(lease)
        rogue = Lease(nodes=list(cache._owned.values()))
        with pytest.raises(ValueError, match="underflow"):
            cache.release(rogue)

    def test_hash_collision_guard_compares_tokens(self):
        cache, pool = _tree()
        prompt = np.arange(12, dtype=np.int32)
        lease, _ = _admit_insert(cache, pool, prompt)
        cache.release(lease)
        # Forge a digest collision: a node whose key matches but whose
        # block differs must NOT be leased (the stored-tokens guard).
        root_child = next(iter(cache._root.children.values()))
        root_child.tokens = root_child.tokens + 1
        assert cache.match(prompt).n_full == 0


# ------------------------------------------------- engine rig (CPU-sim)
@pytest.fixture(scope="module")
def rig():
    """Control (sharing off) + sharing engine over ONE plan, equal pool
    bytes — the only delta between them is the radix tree."""
    import jax

    from autodist_tpu.models.transformer import (
        TransformerConfig, decode_model, init_params)
    from autodist_tpu.serve.engine import InferenceEngine

    import jax.numpy as jnp

    cfg = TransformerConfig(
        vocab_size=128, num_layers=1, d_model=32, num_heads=2, d_ff=64,
        max_seq_len=64, causal=True, dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    dm = decode_model(cfg)
    kw = dict(n_slots=8, page_len=8, n_pages=41, prefill_chunk=8,
              max_len=64)
    control = InferenceEngine.build(params, decode_model=dm, **kw)
    shared = InferenceEngine(params, control.plan, decode_model=dm,
                             prefix_cache=True, **kw)
    return control, shared, params, dm, cfg


@pytest.fixture(scope="module")
def shared_prompts():
    rng = np.random.default_rng(16)
    system = rng.integers(1, 128, size=24).astype(np.int32)  # 3 full blocks
    return system, [
        np.concatenate([system, rng.integers(1, 128, size=n)])
        .astype(np.int32) for n in (4, 7, 8, 11)]


class TestEngineSharing:
    def test_streams_bit_identical_cold_and_warm(self, rig, shared_prompts):
        control, shared, *_ = rig
        _system, prompts = shared_prompts
        expected = [control.generate(p, MAX_NEW) for p in prompts]
        assert [shared.generate(p, MAX_NEW) for p in prompts] == expected
        hits = shared.prefix_stats()["hits"]
        assert [shared.generate(p, MAX_NEW) for p in prompts] == expected
        assert shared.prefix_stats()["hits"] > hits   # warm pass matched

    def test_page_boundary_prefix(self, rig, shared_prompts):
        control, shared, *_ = rig
        system, _ = shared_prompts
        # Divergence exactly at a page boundary: full-block match only,
        # no COW frontier.
        rng = np.random.default_rng(21)
        p = np.concatenate(
            [system[:16], rng.integers(1, 128, size=8)]).astype(np.int32)
        cow_before = shared.prefix_stats()["cow_copies"]
        assert shared.generate(p, MAX_NEW) == control.generate(p, MAX_NEW)
        assert shared.prefix_stats()["cow_copies"] == cow_before

    def test_cow_copies_exactly_one_page(self, rig, shared_prompts):
        control, shared, *_ = rig
        _system, prompts = shared_prompts
        base = prompts[2]                         # 24 shared + 8 unique
        shared.generate(base, MAX_NEW)            # adopt its 4 full blocks
        rng = np.random.default_rng(22)
        # Diverge MID-page: 4 tokens into base's 4th block.
        p = np.concatenate(
            [base[:28], rng.integers(1, 128, size=4)]).astype(np.int32)
        cow_before = shared.prefix_stats()["cow_copies"]
        assert shared.generate(p, MAX_NEW) == control.generate(p, MAX_NEW)
        # Exactly ONE frontier page copied — never more, never a shared
        # write.
        assert shared.prefix_stats()["cow_copies"] == cow_before + 1

    @pytest.mark.parametrize("page_axis", [0, 1])
    def test_cow_copy_touches_one_page_of_every_leaf(self, rig, page_axis):
        """The copy follows the layout's page axis (0: a leaf a layer, as
        both model families hold their pools; 1: a pool stacked over
        layers): ``dst`` becomes ``src`` in every leaf with a page dim,
        every other page and every other leaf stays bit-equal."""
        import types

        import jax
        import jax.numpy as jnp

        from autodist_tpu.serve import pages as serve_pages
        from autodist_tpu.serve.engine import InferenceEngine

        n_pages, rng = 7, np.random.default_rng(23)
        shape = (n_pages, 8, 6) if page_axis == 0 else (3, n_pages, 8, 6)
        cache = {"k": [rng.standard_normal(shape).astype(np.float32)
                       for _ in range(2)],
                 "k_scale": [rng.standard_normal(shape[:-1]).astype(np.float32)],
                 "steps": np.arange(n_pages + 1, dtype=np.int32)}
        engine = types.SimpleNamespace(
            layout=serve_pages.CacheLayout(page_len=8, page_axis=page_axis))
        copy = InferenceEngine._make_page_copy_fn(engine, n_pages, None)
        out = copy(jax.tree_util.tree_map(jnp.asarray, cache),
                   jnp.int32(5), jnp.int32(2))
        before = jax.tree_util.tree_leaves(cache)
        after = [np.asarray(x) for x in jax.tree_util.tree_leaves(out)]
        assert len(before) == len(after) == 4
        for was, now in zip(before, after):
            want = was.copy()
            if was.ndim >= 2:
                index = (slice(None),) * page_axis
                want[index + (2,)] = was[index + (5,)]
            assert np.array_equal(now, want)
        # and through an engine, on GPT-2's own pool
        control, _shared, params, dm, _cfg = rig
        shared = InferenceEngine(
            params, control.plan, decode_model=dm, prefix_cache=True,
            n_slots=8, page_len=8, n_pages=16, prefill_chunk=8, max_len=64)
        leaves = jax.tree_util.tree_leaves(shared._cache)
        filled = [rng.standard_normal(x.shape).astype(x.dtype) for x in leaves]
        shared._cache = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(shared._cache),
            [jax.device_put(f, x.sharding) for f, x in zip(filled, leaves)])
        copies = shared.prefix_stats()["cow_copies"]
        shared._cow_page(9, 4)
        assert shared.prefix_stats()["cow_copies"] == copies + 1
        for was, now in zip(filled, jax.tree_util.tree_leaves(shared._cache)):
            want = was.copy()
            want[4] = was[9]
            assert np.array_equal(np.asarray(now), want)

    def test_mid_batch_join_through_batcher(self, rig, shared_prompts):
        from autodist_tpu.serve.batcher import ContinuousBatcher, RequestState

        control, shared, *_ = rig
        _system, prompts = shared_prompts
        expected = [control.generate(p, MAX_NEW) for p in prompts]
        batcher = ContinuousBatcher(shared, max_queue=32).start()
        try:
            reqs = [batcher.submit(prompts[i % len(prompts)], MAX_NEW)
                    for i in range(12)]
            states = [r.wait(120.0).state for r in reqs]
        finally:
            batcher.stop(drain=False)
        assert all(s is RequestState.DONE for s in states), states
        assert all(r.tokens == expected[i % len(prompts)]
                   for i, r in enumerate(reqs))
        # Cached admissions are visible per request (the TTFT split key).
        assert any(r.cached for r in reqs)

    def test_sharing_accounting(self, rig, shared_prompts):
        from autodist_tpu.serve.engine import AdmissionDenied

        _control, shared, *_ = rig
        system, prompts = shared_prompts
        shared.generate(prompts[0], MAX_NEW)      # warm the tree
        slots = []
        for p in prompts[:3]:
            s = shared.admit(p, MAX_NEW)
            assert not isinstance(s, AdmissionDenied)
            slots.append(s)
        try:
            logical, physical = shared._logical_physical_pages()
            assert physical < logical             # dedup is real
            assert shared.sharing_ratio == pytest.approx(
                logical / physical)
            assert 0.0 < shared.shared_fraction < 1.0
            assert shared.shared_fraction == pytest.approx(
                1.0 - physical / logical)
            # Physical utilization counts each shared page ONCE.
            assert shared.pool.used_pages < logical
            assert shared.prefix_stats()["shared_pages"] >= 3
        finally:
            for s in slots:
                shared.release(s)

    def test_drain_and_purge_leak_free(self, rig):
        _control, shared, *_ = rig
        cache = shared.prefix_cache
        assert cache.live_refcount == 0
        assert shared.pool.used_pages == cache.cached_pages
        cache.purge()
        assert shared.pool.used_pages == 0
        assert shared.pool.free_pages == shared.pool.usable_pages


class TestEvictionUnderPressure:
    def test_pressure_evicts_cold_then_recomputes(self, rig):
        """Fill the pool with one-off cached prefixes; later admissions
        must evict LRU leaves rather than defer, and every stream stays
        bit-identical — eviction costs recompute, never correctness."""
        control, _shared, params, dm, _cfg = rig
        from autodist_tpu.serve.engine import InferenceEngine

        engine = InferenceEngine(
            params, control.plan, decode_model=dm, n_slots=4, page_len=8,
            n_pages=17, prefill_chunk=8, max_len=40, prefix_cache=True)
        # One-off prompts adopt 2 blocks each; the pool (17 pages asked,
        # rounded up for shard divisibility on the test mesh) fills after
        # ~10 — the tail of the sweep MUST evict to admit.
        rng = np.random.default_rng(33)
        prompts = [rng.integers(1, 128, size=18).astype(np.int32)
                   for _ in range(14)]
        expected = [control.generate(p, MAX_NEW) for p in prompts]
        got = [engine.generate(p, MAX_NEW) for p in prompts]
        assert got == expected
        stats = engine.prefix_stats()
        assert stats["evictions"] > 0             # pressure was real
        assert stats["live_refcount"] == 0
        # Second pass: some prefixes were evicted (recompute), streams
        # still bit-identical.
        assert [engine.generate(p, MAX_NEW) for p in prompts] == expected
        engine.prefix_cache.purge()
        assert engine.pool.used_pages == 0
        assert engine.pool.free_pages == engine.pool.usable_pages


# ------------------------------------------------------ spec-decode rider
@pytest.mark.parametrize("k", [1, 4])
def test_spec_engine_shares_one_tree(rig, shared_prompts, k):
    """ONE tree spans target + draft pools: warm re-admission skips both
    prefills, streams stay bit-identical to plain greedy, and purge
    drains BOTH pools to zero (the 5-program pin holds)."""
    from autodist_tpu.serve.spec import SpecDecodeEngine

    control, _shared, params, dm, _cfg = rig
    _system, prompts = shared_prompts
    expected = [control.generate(p, MAX_NEW) for p in prompts]
    spec = SpecDecodeEngine(
        params, control.plan, params, control.plan, decode_model=dm,
        draft_decode_model=dm, spec_k=k, draft_n_pages=41, n_slots=8,
        page_len=8, n_pages=41, prefill_chunk=8, max_len=64,
        prefix_cache=True)
    assert spec.prefix_cache.draft_pool is spec.draft_pool
    assert [spec.generate(p, MAX_NEW) for p in prompts] == expected  # cold
    assert [spec.generate(p, MAX_NEW) for p in prompts] == expected  # warm
    assert spec.prefix_stats()["hits"] > 0
    assert spec.compiled_programs == 5
    assert spec.prefix_stats()["live_refcount"] == 0
    spec.prefix_cache.purge()
    assert spec.pool.used_pages == 0
    assert spec.draft_pool.used_pages == 0


# ------------------------------------------------------- failover replay
@pytest.mark.slow
def test_failover_replays_shared_prefix_stream():
    """Kill a prefix-caching replica mid-decode on a shared-prefix
    stream: journal replay re-prefills on the survivor (repopulating ITS
    tree organically) and the delivered stream stays bit-identical —
    the dead replica's tree is state, never truth."""
    from autodist_tpu import metrics as M
    from autodist_tpu.serve.batcher import RequestState
    from autodist_tpu.serve.replica import ReplicaState
    from autodist_tpu.serve.router import build_test_fleet
    from autodist_tpu.utils import retry

    registry = M.MetricsRegistry()
    router, control = build_test_fleet(
        n_replicas=2, registry=registry, prefix_cache=True)
    router.start()
    try:
        for rep in router.replicas.values():
            rep.wait_ready(120.0)
        rng = np.random.default_rng(44)
        system = rng.integers(1, 127, size=16).astype(np.int32)
        prompts = [np.concatenate([system, rng.integers(1, 127, size=4)])
                   .astype(np.int32) for _ in range(8)]
        expected = [control.generate(p, 8) for p in prompts]
        fronts = [router.submit(p, max_new_tokens=8) for p in prompts]

        def on_victim():
            with router._lock:
                return any(f.replica_id == 0 and len(f.front.tokens) > 0
                           for f in router._flights.values())

        assert retry.wait_until(on_victim, 60.0, interval_s=0.002)
        router.replicas[0].kill("test: mid-decode death")
        states = [f.wait(120.0).state for f in fronts]
        assert all(s is RequestState.DONE for s in states), states
        assert all(f.tokens == expected[i] for i, f in enumerate(fronts))
        assert all(v == 1 for v in router.ledger().values())
        # Failover re-prefill repopulated the SURVIVOR's tree (the dead
        # replica's tree died with it): its engine adopted the shared
        # system blocks.  The initial wave can admit before any prefill
        # completes (all misses), so assert warmth with one more
        # shared-prefix request — it MUST match the repopulated tree.
        survivors = [rep for rid, rep in router.replicas.items()
                     if router.replica_state(rid) is ReplicaState.READY]
        assert survivors
        assert any(
            rep.batcher.engine.prefix_stats()["inserts"] > 0
            for rep in survivors if rep.batcher is not None)
        warm_prompt = np.concatenate(
            [system, rng.integers(1, 127, size=4)]).astype(np.int32)
        warm_expected = control.generate(warm_prompt, 8)
        warm = router.submit(warm_prompt, max_new_tokens=8)
        assert warm.wait(120.0).state is RequestState.DONE
        assert warm.tokens == warm_expected
        assert any(
            rep.batcher.engine.prefix_stats()["hits"] > 0
            for rep in survivors if rep.batcher is not None)
    finally:
        router.stop(drain=False)
