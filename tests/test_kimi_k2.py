"""Kimi K2 (the DeepSeek-V3 block) at tiny widths on the CPU: the paged
programs against the dense forward, the absorbed decode against expanded
attention, both kernels in interpret mode against plain ``jnp``, routing
without drops, the shares of the experts adding up, the vocabulary slice,
one host fetch a tick with the device's facts on the spans, and what the
engine refuses over latent pages."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import metrics as M
from autodist_tpu.api import AutoDist
from autodist_tpu.models import kimi_k2 as K
from autodist_tpu.models import routed
from autodist_tpu.obs import spans as obs_spans
from autodist_tpu.ops import grouped_matmul as GM
from autodist_tpu.ops import paged_attention as PA
from autodist_tpu.serve import pages as serve_pages
from autodist_tpu.serve.batcher import ContinuousBatcher
from autodist_tpu.strategy import AllReduce

PAGE = 8


def _cfg(**more):
    kw = dict(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_hidden_layers=3, num_attention_heads=4,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, n_routed_experts=16,
        num_experts_per_tok=4, experts_held=(4, 4), max_position_embeddings=128,
        dtype=jnp.float32, page_len=PAGE, prefill_chunk=16,
        rope_scaling=dict(K._yarn_defaults(), factor=4,
                          original_max_position_embeddings=32))
    kw.update(more)
    return K.KimiK2Config(**kw)


def _params(cfg, seed=0, bias=0.05):
    params = K.init_params(jax.random.PRNGKey(seed), cfg)
    for i in range(cfg.first_k_dense_replace, cfg.num_hidden_layers):
        key = jax.random.fold_in(jax.random.PRNGKey(seed + 1), i)
        router = params[f"layers_{i}"]["router"]
        router["bias"] = bias * jax.random.normal(key, router["bias"].shape)
    return params


def _forward(params, tokens, cfg):
    return jax.jit(lambda p, t: K.forward(p, t, cfg))(params, tokens)


@pytest.fixture(scope="module")
def cfg():
    return _cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return _params(cfg)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(7), (2, 44), 0, 64)


# ------------------------------------------------- paged programs = forward
@pytest.mark.parametrize("chunk", [8, 16, 32])
@pytest.mark.parametrize("impl", ["gather", "kernel"])
def test_paged_prefill_and_decode_match_the_dense_forward(cfg, params, tokens,
                                                          chunk, impl):
    """A prompt of 32 in chunks of ``chunk`` through the pool, then 8
    decode steps beside an idle row: logits of both programs against the
    whole-sequence forward; the decode step's attention absorbed, through
    the gather rendering and through the kernel in interpret mode."""
    cfg = dataclasses.replace(cfg, prefill_chunk=chunk,
                              paged_attention_impl=impl)
    want = _forward(params, tokens, cfg)[0]
    n_tables = cfg.max_position_embeddings // PAGE
    cache = K.init_paged_cache(cfg, 40, PAGE)
    table = jnp.arange(3, 3 + n_tables, dtype=jnp.int32)
    prefill = jax.jit(lambda p, t, s, n, c, tb: K.forward_paged_prefill_chunk(
        p, t, s, n, c, tb, cfg, return_logits=True))
    decode = jax.jit(lambda p, t, pos, c, tb: K.forward_paged_decode_step(
        p, t, pos, c, tb, cfg, return_logits=True))
    row, got = tokens[0], []
    for start in range(0, 32, chunk):
        out, cache = prefill(params, row[start:start + chunk][None],
                             jnp.int32(start), jnp.int32(32), cache, table)
        got.append(out[0])
    np.testing.assert_allclose(jnp.concatenate(got), want[:32], atol=2e-5)
    tables = jnp.stack([table, jnp.zeros_like(table)])
    for p in range(32, 40):
        out, cache = decode(params, jnp.array([row[p], 0]), jnp.array([p, 0]),
                            cache, tables)
        np.testing.assert_allclose(out[0], want[p], atol=2e-5)
    assert cache["kv"][0].shape == (40, PAGE, cfg.page_width) and len(cache) == 1


def test_a_prefill_chunk_may_start_inside_a_page(cfg, params, tokens):
    """A shared prefix may end anywhere: a chunk that starts at position 5
    over pages written by an earlier chunk of another size."""
    want = _forward(params, tokens, cfg)[1]
    cache = K.init_paged_cache(cfg, 24, PAGE)
    table = jnp.arange(1, 17, dtype=jnp.int32)
    row = jnp.pad(tokens[1], (0, 16))
    head = dataclasses.replace(cfg, prefill_chunk=8)
    _, cache = K.forward_paged_prefill_chunk(
        params, row[:8][None], jnp.int32(0), jnp.int32(5), cache, table, head)
    out, _ = K.forward_paged_prefill_chunk(
        params, row[5:21][None], jnp.int32(5), jnp.int32(21), cache, table, cfg,
        return_logits=True)
    np.testing.assert_allclose(out[0], want[5:21], atol=2e-5)


def test_absorbed_decode_is_expanded_attention(cfg, params):
    attn = params["layers_1"]["attn"]
    w_k, w_v = K._up_projection(attn, cfg)
    key = jax.random.PRNGKey(3)
    q_nope, q_rope = (jax.random.normal(jax.random.fold_in(key, i), (5, 4, 8))
                      for i in range(2))
    latents = jax.random.normal(jax.random.fold_in(key, 2), (21, cfg.latent_width))
    mask = jnp.arange(21)[None, :] <= (16 + jnp.arange(5))[:, None]
    a = K.expanded_attention(q_nope, q_rope, latents, w_k, w_v, mask, cfg.softmax_scale)
    b = K.absorbed_attention(q_nope, q_rope, latents, w_k, w_v, mask, cfg.softmax_scale)
    np.testing.assert_allclose(a, b, atol=1e-5)
    assert cfg.softmax_scale == pytest.approx(16 ** -0.5 * (0.1 * np.log(4) + 1) ** 2)
    full = K.KimiK2Config()
    assert full.softmax_scale == pytest.approx(0.14468, abs=1e-5)
    assert full.latent_width == 576 and full.page_width == 640
    freq = K.yarn_inv_freq(full)
    base = 50000.0 ** (-np.arange(0, 64, 2) / 64)
    assert freq[0] == pytest.approx(base[0]) and freq[-1] == pytest.approx(base[-1] / 64)
    assert np.all(np.diff(freq) < 0)


# ------------------------------------------------------------- the kernels
@pytest.mark.parametrize("page_len,n_tables,keys,dtype", [
    (8, 16, 32, jnp.float32), (16, 8, 64, jnp.bfloat16), (8, 6, 16, jnp.float32),
    (8, 7, 512, jnp.float32)])
def test_the_latent_kernel_against_the_gather(page_len, n_tables, keys, dtype):
    """Rows at positions from the first slot to the table's end, an idle
    row at 0 on scratch, tables in any order; a table width that no group
    divides walks a page a step."""
    b, h, w, cv = 5, 4, 24, 16
    key = jax.random.PRNGKey(0)
    pages = jax.random.normal(key, (b * n_tables + 1, page_len, w)).astype(dtype)
    q = jax.random.normal(jax.random.fold_in(key, 1), (b, h, w)).astype(dtype)
    tables = jax.random.permutation(jax.random.fold_in(key, 2),
                                    b * n_tables).reshape(b, n_tables) + 1
    tables = tables.at[4].set(serve_pages.SCRATCH_PAGE).astype(jnp.int32)
    top = n_tables * page_len - 1
    positions = jnp.array([0, page_len - 1, page_len, top, 0], jnp.int32)
    want = PA.mla_paged_decode_attention(q, pages, tables, positions,
                                         value_width=cv, scale=0.3, impl="gather")
    got = PA.mla_paged_decode_attention(q, pages, tables, positions, value_width=cv,
                                        scale=0.3, impl="kernel", keys=keys)
    assert got.shape == (b, h, cv) and got.dtype == dtype
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(got.astype(jnp.float32), want.astype(jnp.float32),
                               atol=tol, rtol=tol)
    assert PA.latent_blocking(n_tables, page_len, keys) == {
        (8, 16, 32): 4, (16, 8, 64): 4, (8, 6, 16): 2, (8, 7, 512): 7}[
            (page_len, n_tables, keys)]


@pytest.mark.parametrize("tokens,k,first,count,tile", [
    (6, 4, 4, 4, 8), (40, 4, 0, 16, 8), (9, 2, 12, 4, 16), (5, 4, 8, 3, None)])
def test_the_grouped_product_against_plain_jnp(tokens, k, first, count, tile):
    key = jax.random.PRNGKey(tokens)
    ids = jnp.stack([jax.random.permutation(jax.random.fold_in(key, t), 16)[:k]
                     for t in range(tokens)]).astype(jnp.int32)
    wts = jax.random.uniform(jax.random.fold_in(key, 99), (tokens, k))
    x = jax.random.normal(jax.random.fold_in(key, 100), (tokens, 24))
    w = jax.random.normal(jax.random.fold_in(key, 101), (count, 24, 40))
    groups = GM.group_rows(ids, first, count, tile)
    held = (ids >= first) & (ids < first + count)
    assert int(groups.n_pairs) == int(held.sum())
    assert int(groups.n_hit) == len(set(np.asarray(ids)[np.asarray(held)].tolist()))
    rows = GM.gather_rows(x, groups)
    got = GM.combine_rows(GM.grouped_matmul(rows, w, groups, impl="kernel"),
                          groups, wts)
    ref = GM.combine_rows(GM.grouped_matmul(rows, w, groups, impl="reference"),
                          groups, wts)
    want = sum(jnp.where(ids == first + e, wts, 0.0).sum(-1)[:, None] * (x @ w[e])
               for e in range(count))
    np.testing.assert_allclose(got, want, atol=1e-4)
    np.testing.assert_allclose(ref, want, atol=1e-4)


# ------------------------------------------------------------------ routing
def _dense_experts(layer, u, cfg, first, count):
    """A per-token loop: every chosen expert in ``[first, first + count)``."""
    experts, weights = (np.asarray(a) for a in routed.route(layer["router"], u, cfg))
    e = jax.tree.map(np.asarray, layer["experts"])
    out = np.zeros(u.shape, np.float32)
    for t in range(u.shape[0]):
        for j, w in zip(experts[t], weights[t]):
            if first <= j < first + count:
                x = np.asarray(u[t])
                g, up = x @ e["gate"][j - first], x @ e["up"][j - first]
                out[t] += w * ((g / (1 + np.exp(-g)) * up) @ e["down"][j - first])
    return out


def test_routing_against_a_per_token_loop_and_nothing_dropped_under_skew():
    """Top-4 of 16 by ``sigma + b``, weights ``sigma`` over their sum times
    the factor; half of the tokens are sent to expert 5 (held), which no
    capacity turns away."""
    cfg = _cfg()
    params = _params(cfg, seed=3)
    layer = params["layers_1"]
    u = jax.random.normal(jax.random.PRNGKey(5), (24, 32))
    push = jnp.zeros((32, 16)).at[:, 5].set(1.0)
    layer = dict(layer, router=dict(layer["router"],
                                    kernel=layer["router"]["kernel"] + 0.0 * push))
    u = u.at[:12].add(3.0 * layer["router"]["kernel"][:, 5] /
                      jnp.linalg.norm(layer["router"]["kernel"][:, 5]))
    experts, weights = routed.route(layer["router"], u, cfg)
    sigma = jax.nn.sigmoid(u @ layer["router"]["kernel"])
    for t in range(24):
        chosen = np.argsort(-np.asarray(sigma[t] + layer["router"]["bias"]))[:4]
        assert set(chosen.tolist()) == set(np.asarray(experts[t]).tolist())
        w = np.asarray(sigma[t])[np.asarray(experts[t])]
        np.testing.assert_allclose(weights[t], w / w.sum() * 2.827, rtol=1e-5)
    assert int((experts[:12] == 5).any(-1).sum()) == 12, "the skew holds"
    out, pairs, hit = routed.expert_ffn(layer, u, cfg)
    shared = K.L.gated_mlp(layer["shared"], u)
    np.testing.assert_allclose(out - shared, _dense_experts(layer, u, cfg, 4, 4),
                               atol=1e-4)
    # the Mosaic kernel (interpreted here) in the layer's place
    np.testing.assert_allclose(routed.expert_ffn(layer, u, cfg, impl="kernel")[0],
                               out, atol=1e-4)
    held = (experts >= 4) & (experts < 8)
    assert int(pairs) == int(held.sum()) and int(pairs) >= 12
    assert int(hit) == len(set(np.asarray(experts)[np.asarray(held)].tolist()))
    # a token that is padding chooses no expert
    _, fewer, _ = routed.expert_ffn(layer, u, cfg, live=jnp.arange(24) >= 12)
    assert int(fewer) == int(held[12:].sum())


def test_the_shares_add_up():
    """16 experts over 4 shares of 4: the four partial results, the shared
    expert counted once, are the uncut layer."""
    whole = _cfg(experts_held=None)
    params = _params(whole, seed=11)
    layer = params["layers_2"]
    u = jax.random.normal(jax.random.PRNGKey(2), (20, 32))
    uncut, pairs, hit = routed.expert_ffn(layer, u, whole)
    assert int(pairs) == 20 * 4 and int(hit) <= 16
    shared = K.L.gated_mlp(layer["shared"], u)
    total = shared
    for first in (0, 4, 8, 12):
        cut = dataclasses.replace(whole, experts_held=(first, 4))
        part = dict(layer, experts=jax.tree.map(
            lambda w: w[first:first + 4], layer["experts"]))
        out, n, _ = routed.expert_ffn(part, u, cut)
        total = total + (out - shared)
        assert 0 < int(n) < 80
    np.testing.assert_allclose(total, uncut, atol=1e-4)


def test_the_vocabulary_slice(cfg, params, tokens):
    """A chip that holds the first 16 of 64 rows of the embedding and head
    gives the uncut model's first 16 logits for tokens of its slice."""
    ids = tokens % 16
    want = _forward(params, ids, cfg)[..., :16]
    cut = dict(params, embed={"embedding": params["embed"]["embedding"][:16]},
               head={"kernel": params["head"]["kernel"][:, :16]})
    got = _forward(cut, ids, dataclasses.replace(cfg, vocab_size=16))
    np.testing.assert_allclose(got, want, atol=1e-5)


# ------------------------------------------------------- through the engine
def _engine(params, cfg, **more):
    return AutoDist(strategy_builder=AllReduce()).build_inference(
        params, decode_model=K.decode_model(cfg), n_slots=4, max_len=128, **more)


def _is_greedy(params, cfg, prompt, served):
    """Every served token is the dense forward's argmax after what came
    before it (one whole-sequence forward over prompt + served)."""
    seq = np.concatenate([prompt, served]).astype(np.int32)
    best = np.asarray(_forward(params, jnp.asarray(seq[None]), cfg)[0].argmax(-1))
    return list(best[len(prompt) - 1: len(seq) - 1]) == list(served)


def test_the_engine_serves_the_dense_forwards_greedy_stream(cfg, params):
    try:
        engine = _engine(params, cfg)
        assert engine.page_len == PAGE and engine.prefill_chunk == 16
        assert engine.layout.latent and engine._kv_page is None
        # a page is priced from the one leaf a layer: rows x columns x layers
        assert engine.page_bytes == PAGE * cfg.page_width * 4 * 3
        prompt = np.arange(3, 40) % 64
        assert _is_greedy(params, cfg, prompt, engine.generate(prompt, 6))
        assert engine.compiled_programs == 2
    finally:
        AutoDist.reset_default()


def test_one_fetch_a_tick_brings_the_tokens_and_the_devices_facts(cfg, params,
                                                                 monkeypatch):
    """Every ``serve.token_fetch`` is one ``jax.device_get``, as without
    the facts; they land on the span of the program that produced them,
    non-final chunks included, and in the counters."""
    import autodist_tpu.serve.engine as engine_mod

    calls = []
    real = jax.device_get
    monkeypatch.setattr(engine_mod.jax, "device_get",
                        lambda x: calls.append(1) or real(x))
    tracer = obs_spans.get_tracer()
    tracer.clear()
    try:
        engine = _engine(params, cfg)
        registry = M.MetricsRegistry()
        batcher = ContinuousBatcher(engine, registry=registry)
        batcher.start()
        reqs = [batcher.submit(np.arange(5, 5 + n) % 64, max_new_tokens=5)
                for n in (40, 20, 7)]
        for r in reqs:
            assert r.wait(120) and len(r.tokens) == 5
        batcher.stop(drain=False, timeout_s=30)
    finally:
        AutoDist.reset_default()
    spans = tracer.spans()
    fetches = [s for s in spans if s.name == "serve.token_fetch"]
    assert len(calls) == len(fetches) > 0
    ticks = {s.id: 0 for s in spans if s.name == "serve.tick"}
    by_id = {s.id: s for s in spans}
    for s in fetches:
        top = s
        while top.parent in by_id and top.name != "serve.tick":
            top = by_id[top.parent]
        ticks[top.id] += 1
    assert max(ticks.values()) <= 2, "a final chunk's and the decode step's"
    steps = [s for s in spans if s.name == "serve.decode_step"]
    chunks = [s for s in spans if s.name == "serve.prefill_chunk"]
    assert steps and len(chunks) == 3 + 2 + 1
    for s in steps + chunks:
        assert 0 <= s.attrs["moe_experts_hit"] <= min(s.attrs["moe_pairs"], 8)
    assert any(not s.attrs["final"] for s in chunks)
    assert sum(s.attrs["moe_pairs"] for s in steps) == engine.fact_totals["moe_pairs"] \
        == registry.counter("serve_moe_pairs_total").value > 0
    assert registry.counter("serve_moe_experts_hit_total").value == \
        engine.fact_totals["moe_experts_hit"]
    assert registry.counter("serve_moe_steps_total").value == len(steps) == engine.fact_steps
    last = [s for s in spans if s.name == "serve.tick_metrics"][-1].attrs
    assert last["moe_steps"] == len(steps) and last["moe_pairs"] == engine.fact_totals["moe_pairs"]


def test_prefix_sharing_works_unchanged_over_latent_pages(cfg, params):
    """The timeline is plain: a second prompt that shares two pages and a
    half with the first maps onto its pages and serves the same stream."""
    try:
        a = np.arange(9, 9 + 30) % 64
        b = np.concatenate([a[:20], (a[20:] + 7) % 64])
        plain = _engine(params, cfg)
        want = [plain.generate(p, 5) for p in (a, b)]
        AutoDist.reset_default()
        shared = _engine(params, cfg, prefix_cache=True)
        got = [shared.generate(p, 5) for p in (a, b)]
        assert got == want
        stats = shared.prefix_stats()
        assert stats["hits"] >= 1 and stats["cow_copies"] >= 1
    finally:
        AutoDist.reset_default()


def test_int8_pages_and_speculation_over_latent_pages_are_refused_typed(cfg, params):
    with pytest.raises(serve_pages.CacheFeatureRefused, match="int8"):
        K.decode_model(dataclasses.replace(cfg, kv_quant=True))
    dm = K.decode_model(cfg)
    planes = lambda n, pl: dict(  # noqa: E731
        dm.init_paged_cache(n, pl), k_scale=jnp.zeros((n, pl, 1)))
    try:
        with pytest.raises(serve_pages.CacheFeatureRefused, match="int8"):
            AutoDist(strategy_builder=AllReduce()).build_inference(
                params, decode_model=dataclasses.replace(dm, init_paged_cache=planes),
                n_slots=4, max_len=128)
        AutoDist.reset_default()
        with pytest.raises(serve_pages.CacheFeatureRefused, match="speculative"):
            AutoDist(strategy_builder=AllReduce()).build_inference(
                params, decode_model=dm, draft_params=params,
                draft_decode_model=dm, n_slots=4, max_len=128)
        AutoDist.reset_default()
        with pytest.raises(ValueError, match=f"pages of {PAGE}"):
            AutoDist(strategy_builder=AllReduce()).build_inference(
                params, decode_model=dm, n_slots=4, max_len=128, page_len=16)
    finally:
        AutoDist.reset_default()
    assert dm.step_facts == ("moe_pairs", "moe_experts_hit")
    assert dm.steps_fact == "moe_steps"
    assert dm.verify_paged is None and dm.cache_layout.latent
