"""Nemotron-H (Mamba-2 mixers, relu² experts, grouped-KV attention) at tiny
widths on the CPU, one period of the published pattern kept: the paged
programs against the whole-sequence forward (prompts that end inside an
SSD block and inside an engine chunk, and prompts shorter than the
convolution), a slot recycled from another request, the state kernel in
interpret mode against plain ``jnp``, the grouped-KV fold against plain
grouped attention, the shares of the experts adding up, what the engine
carries, counts and refuses over per-slot state.

Tolerances: every leaf and product is float32 here (``dtype`` float32), so
the programs and the forward differ only in the order of their sums (the
dual form's blocks against a position at a time, the paged walk against
one softmax): a few ulps of logits that are O(1), held to 2e-5."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import metrics as M
from autodist_tpu.api import AutoDist
from autodist_tpu.models import nemotron_h as N
from autodist_tpu.models import routed
from autodist_tpu.obs import spans as obs_spans
from autodist_tpu.ops import paged_attention as PA
from autodist_tpu.ops import ssm as SSM
from autodist_tpu.serve import pages as serve_pages
from autodist_tpu.serve.batcher import ContinuousBatcher
from autodist_tpu.strategy import AllReduce

PAGE = 8
TOL = 2e-5


def _cfg(**more):
    kw = dict(
        vocab_size=64, hidden_size=32, hybrid_override_pattern="MEMEM*EME",
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        mamba_num_heads=8, mamba_head_dim=4, n_groups=2, ssm_state_size=16,
        chunk_size=8, moe_intermediate_size=16,
        moe_shared_expert_intermediate_size=24, n_routed_experts=16,
        num_experts_per_tok=4, experts_held=(4, 4), max_position_embeddings=128,
        dtype=jnp.float32, page_len=PAGE, prefill_chunk=16)
    kw.update(more)
    return N.NemotronHConfig(**kw)


def _params(cfg, seed=0, bias=0.05):
    params = N.init_params(jax.random.PRNGKey(seed), cfg)
    for i in cfg.layers_of("experts"):
        key = jax.random.fold_in(jax.random.PRNGKey(seed + 1), i)
        router = params[f"layers_{i}"]["router"]
        router["bias"] = bias * jax.random.normal(key, router["bias"].shape)
    return params


def _forward(params, tokens, cfg):
    return jax.jit(lambda p, t: N.forward(p, t, cfg))(params, tokens)


@pytest.fixture(scope="module")
def cfg():
    return _cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return _params(cfg)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(7), (2, 44), 0, 64)


def _programs(cfg):
    prefill = jax.jit(lambda p, t, s, n, c, tb, st, slot: N.forward_paged_prefill_chunk(
        p, t, s, n, c, tb, cfg, return_logits=True, state=st, slot=slot))
    decode = jax.jit(lambda p, t, pos, c, tb, st: N.forward_paged_decode_step(
        p, t, pos, c, tb, cfg, return_logits=True, state=st))
    return prefill, decode


def _garbage_state(cfg, n_slots):
    """A state whose every slot holds what an earlier request left."""
    return jax.tree.map(lambda s: s + 3.0, N.init_slot_state(cfg, n_slots))


# ------------------------------------------------- paged programs = forward
@pytest.mark.parametrize("chunk,attn,ssm", [
    (16, "gather", "reference"), (8, "kernel", "kernel"), (32, "gather", "kernel")])
def test_paged_prefill_and_decode_match_the_dense_forward(cfg, params, tokens,
                                                          chunk, attn, ssm,
                                                          monkeypatch):
    """A prompt of 29 (inside an SSD block of 8 and an engine chunk) in
    chunks through the pool into slot 1 of a state that holds garbage,
    then 11 decode steps beside an idle row and a row mid-prefill: both
    programs' logits against the whole-sequence forward, the kernels in
    interpret mode; the rows that are not decoding keep their state bit
    for bit."""
    cfg = dataclasses.replace(cfg, prefill_chunk=chunk, paged_attention_impl=attn)
    monkeypatch.setattr(SSM, "ssm_state_update",
                        functools.partial(SSM.ssm_state_update, impl=ssm))
    want = _forward(params, tokens, cfg)[0]
    prefill, decode = _programs(cfg)
    n_tables = cfg.max_position_embeddings // PAGE
    cache, state = N.init_paged_cache(cfg, 40, PAGE), _garbage_state(cfg, 3)
    table = jnp.arange(3, 3 + n_tables, dtype=jnp.int32)
    row, n, got = jnp.pad(tokens[0], (0, 32)), 29, []
    for start in range(0, n, chunk):
        out, cache, state = prefill(params, row[start:start + chunk][None],
                                    jnp.int32(start), jnp.int32(n), cache, table,
                                    state, jnp.int32(1))
        got.append(out[0])
    np.testing.assert_allclose(jnp.concatenate(got)[:n], want[:n], atol=TOL)
    tables = jnp.stack([jnp.zeros_like(table), table, jnp.zeros_like(table)])
    idle = jax.tree.map(lambda s: s[jnp.array([0, 2])], state)
    for p in range(n, 40):
        out, cache, state = decode(params, jnp.array([0, row[p], 5]),
                                   jnp.array([0, p, 0]), cache, tables, state)
        np.testing.assert_allclose(out[1], want[p], atol=TOL)
    for a, b in zip(jax.tree.leaves(idle),
                    jax.tree.leaves(jax.tree.map(lambda s: s[jnp.array([0, 2])], state))):
        assert bool((a == b).all())
    assert len(cache["k"]) == len(cache["v"]) == 1
    assert cache["k"][0].shape == (40, PAGE, 2 * 8)
    assert len(state["ssm"]) == len(state["conv"]) == 4
    assert state["ssm"][0].shape == (3, 8, 4, 16) and state["ssm"][0].dtype == jnp.float32
    assert state["conv"][0].shape == (3, 3, 32 + 2 * 2 * 16)


@pytest.mark.parametrize("n", [1, 2, 3])
def test_prompts_shorter_than_the_convolution(cfg, params, tokens, n):
    """A prompt of 1-3 tokens: the convolution's tail is taken from the
    zeros the prompt starts from, and decoding goes on from it."""
    want = _forward(params, tokens, cfg)[1]
    prefill, decode = _programs(cfg)
    cache, state = N.init_paged_cache(cfg, 20, PAGE), _garbage_state(cfg, 2)
    table = jnp.arange(1, 17, dtype=jnp.int32)
    row = jnp.pad(tokens[1], (0, 16))
    out, cache, state = prefill(params, row[:16][None], jnp.int32(0), jnp.int32(n),
                                cache, table, state, jnp.int32(0))
    np.testing.assert_allclose(out[0, :n], want[:n], atol=TOL)
    tables = jnp.stack([table, jnp.zeros_like(table)])
    for p in range(n, n + 6):
        out, cache, state = decode(params, jnp.array([row[p], 0]), jnp.array([p, 0]),
                                   cache, tables, state)
        np.testing.assert_allclose(out[0], want[p], atol=TOL)


def test_a_recycled_slot_gives_what_a_fresh_one_gives(cfg, params, tokens):
    """Slot 0 serves one prompt, then another: the second's logits are the
    same, bit for bit, as in a state that never served anything."""
    prefill, decode = _programs(cfg)
    table = jnp.arange(1, 17, dtype=jnp.int32)
    tables = table[None]

    def serve(state, row, n):
        cache, outs = N.init_paged_cache(cfg, 20, PAGE), []
        for start in range(0, n, 16):
            out, cache, state = prefill(params, row[start:start + 16][None],
                                        jnp.int32(start), jnp.int32(n), cache, table,
                                        state, jnp.int32(0))
        outs.append(out[0])
        for p in range(n, n + 4):
            out, cache, state = decode(params, row[p][None], jnp.array([p]), cache,
                                       tables, state)
            outs.append(out)
        return state, outs

    a, b = jnp.pad(tokens[0], (0, 16)), jnp.pad(tokens[1], (0, 16))
    used, _ = serve(N.init_slot_state(cfg, 1), a, 37)
    assert any(bool(jnp.any(s != 0)) for s in jax.tree.leaves(used))
    _, again = serve(used, b, 21)
    _, fresh = serve(N.init_slot_state(cfg, 1), b, 21)
    for x, y in zip(again, fresh):
        assert bool((x == y).all())


def test_the_dual_form_is_the_recurrence():
    """The chunk's SSD blocks against a position at a time, from a carried
    state, padded positions (dt = 0, x = 0) leaving it as it was."""
    key = jax.random.PRNGKey(4)
    ks = jax.random.split(key, 7)
    t, h, p, g, n = 24, 8, 4, 2, 16
    x = jax.random.normal(ks[0], (t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (t, h)) - 1.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), maxval=2.0))
    b, c = (jax.random.normal(k, (t, g, n)) for k in ks[3:5])
    d = jax.random.normal(ks[5], (h,))
    s0 = jax.random.normal(ks[6], (h, p, n))
    want_y, want_s = N.scan_positions(x, dt, a, b, c, d, s0)
    for block in (4, 8, 24):
        y, s = N.ssd_chunk(x, dt, a, b, c, d, s0, block)
        np.testing.assert_allclose(y, want_y, atol=1e-4, rtol=1e-5)
        np.testing.assert_allclose(s, want_s, atol=1e-4, rtol=1e-5)
    pad = lambda v: v.at[16:].set(0.0)  # noqa: E731
    _, s16 = N.scan_positions(x[:16], dt[:16], a, b[:16], c[:16], d, s0)
    _, s = N.ssd_chunk(pad(x), pad(dt), a, b, c, d, s0, 8)
    np.testing.assert_allclose(s, s16, atol=1e-4, rtol=1e-5)


# ------------------------------------------------------------- the kernels
@pytest.mark.parametrize("live", [
    [0, 1, 0, 1, 1], [1, 0, 0, 0, 0], [0, 0, 0, 0, 1], [1, 1, 1, 1, 1], [0, 0, 0, 0, 0]])
def test_the_state_kernel_against_plain_jnp(live):
    """Interpret mode against the plain update; a row that is not live
    keeps its state bit for bit and reads y = 0."""
    r, h, p, n, g = 5, 8, 16, 128, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    state = jax.random.normal(ks[0], (r, h, p, n))
    x = jax.random.normal(ks[1], (r, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (r, h)))
    a = -jnp.exp(jax.random.uniform(ks[3], (h,), maxval=2.7))
    d = jax.random.normal(ks[4], (h,))
    b, c = (jax.random.normal(k, (r, g, n)) for k in ks[5:7])
    live = jnp.array(live, bool)
    y0, s0 = SSM.ssm_state_update(state, x, dt, a, d, b, c, live, impl="reference")
    y1, s1 = SSM.ssm_state_update(state, x, dt, a, d, b, c, live, impl="kernel",
                                  interpret=True)
    np.testing.assert_allclose(y1, y0, atol=1e-4)
    np.testing.assert_allclose(s1, s0, atol=1e-5)
    assert bool((s1[~live] == state[~live]).all()) and bool((y1[~live] == 0).all())
    # the plain update is the recurrence's step
    bh, ch = (jnp.repeat(v, h // g, axis=1) for v in (b, c))
    want = (jnp.exp(dt * a)[..., None, None] * state
            + (dt[..., None] * x)[..., None] * bh[:, :, None, :])
    np.testing.assert_allclose(s0[live], want[live], atol=1e-5)
    np.testing.assert_allclose(
        y0[live], (jnp.einsum("rhpn,rhn->rhp", want, ch) + d[:, None] * x)[live],
        atol=1e-4)


def _grouped_plain(q, k, v, lengths):
    """Each query head against its KV head repeated, one row at a time."""
    rows, hq, dh = q.shape
    hkv = k.shape[-1] // dh
    out = []
    for r in range(rows):
        kk = jnp.repeat(k[r].reshape(-1, hkv, dh), hq // hkv, axis=1)
        vv = jnp.repeat(v[r].reshape(-1, hkv, dh), hq // hkv, axis=1)
        s = jnp.einsum("hd,thd->ht", q[r], kk) / jnp.sqrt(dh * 1.0)
        s = jnp.where(jnp.arange(kk.shape[0]) <= lengths[r], s, -jnp.inf)
        out.append(jnp.einsum("ht,thd->hd", jax.nn.softmax(s, -1), vv))
    return jnp.stack(out)


@pytest.mark.parametrize("impl", ["gather", "kernel"])
def test_the_grouped_kv_fold_is_plain_grouped_attention(impl):
    """32 query heads over 2 KV heads (16 a group) folded into the paged
    kernel's query axis, a decode step over three rows: the same as each
    head attending its KV head's timeline."""
    key = jax.random.PRNGKey(1)
    rows, hq, hkv, dh, page_len, n_tables = 3, 32, 2, 8, 8, 4
    pages = 1 + rows * n_tables
    kp = jax.random.normal(jax.random.fold_in(key, 0), (pages, page_len, hkv * dh))
    vp = jax.random.normal(jax.random.fold_in(key, 1), (pages, page_len, hkv * dh))
    q = jax.random.normal(jax.random.fold_in(key, 2), (rows, hq, dh))
    tables = (1 + jnp.arange(rows * n_tables, dtype=jnp.int32)).reshape(rows, n_tables)
    positions = jnp.array([3, 17, 31], jnp.int32)
    o = PA.paged_verify_attention(
        N._attend_folded(q, hkv), kp, vp, tables,
        jnp.broadcast_to(positions[:, None], (rows, hq // hkv)), impl=impl)
    got = N._unfold(o).reshape(rows, hq, dh)
    timeline = lambda p: p[tables].reshape(rows, -1, hkv * dh)  # noqa: E731
    want = _grouped_plain(q, timeline(kp), timeline(vp), positions)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # and the whole-sequence rendering the forward uses
    mask = jnp.arange(n_tables * page_len)[None, :] <= 31
    plain = N.grouped_attention(q[2][None], timeline(kp)[2], timeline(vp)[2], mask, hkv)
    np.testing.assert_allclose(plain[0], want[2], atol=1e-5)


# ------------------------------------------------------------------ experts
def test_the_eight_shares_add_up_to_the_uncut_layer():
    """16 experts over 8 shares of 2 (the deployment's eight chips): the
    partial results, the relu² shared expert counted once, are the uncut
    layer; each routed expert is ``W_down relu(W_up u)^2``, no gate."""
    whole = _cfg(experts_held=None)
    layer = _params(whole, seed=11)["layers_1"]
    u = jax.random.normal(jax.random.PRNGKey(2), (20, 32))
    uncut, pairs, hit = routed.expert_ffn(layer, u, whole)
    assert int(pairs) == 20 * 4 and 0 < int(hit) <= 16
    shared = routed.mlp(layer["shared"], u, whole)
    np.testing.assert_allclose(
        shared, jax.nn.relu(u @ layer["shared"]["up"]["kernel"]) ** 2
        @ layer["shared"]["down"]["kernel"], atol=1e-5)
    experts, weights = routed.route(layer["router"], u, whole)
    e = layer["experts"]
    loop = sum(jnp.where(experts == j, weights, 0.0).sum(-1)[:, None]
               * (jax.nn.relu(u @ e["up"][j]) ** 2 @ e["down"][j]) for j in range(16))
    np.testing.assert_allclose(uncut, shared + loop, atol=1e-4)
    total = shared
    for first in range(0, 16, 2):
        cut = dataclasses.replace(whole, experts_held=(first, 2))
        part = dict(layer, experts=jax.tree.map(lambda w: w[first:first + 2], e))
        out, n, _ = routed.expert_ffn(part, u, cut)
        total = total + (out - shared)
        assert int(n) < 80
    np.testing.assert_allclose(total, uncut, atol=1e-4)
    with pytest.raises(ValueError, match="expert_act"):
        routed.expert_ffn(layer, u, dataclasses.replace(whole, expert_act="gelu"))


def test_the_served_tree_pads_the_experts_width_with_zeros(cfg, params):
    """``serving_params`` pads the routed experts' width to a multiple of
    128 with zeros: the layer gives what it gave, every other leaf is the
    one given."""
    served = N.serving_params(params, cfg)
    layer, padded = params["layers_1"], served["layers_1"]
    assert padded["experts"]["up"].shape == (4, 32, 128)
    assert padded["experts"]["down"].shape == (4, 128, 32)
    u = jax.random.normal(jax.random.PRNGKey(3), (20, 32))
    np.testing.assert_allclose(routed.expert_ffn(padded, u, cfg)[0],
                               routed.expert_ffn(layer, u, cfg)[0], atol=1e-6)
    assert served["layers_0"] is params["layers_0"]
    assert padded["shared"] is layer["shared"] and padded["router"] is layer["router"]


def test_the_config_refuses_a_layer_it_cannot_build():
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        _cfg(hybrid_override_pattern="M-M*E")
    full = N.NemotronHConfig()
    assert full.num_hidden_layers == 52 and full.d_inner == 4096
    assert full.conv_dim == 6144 and full.kv_width == 256
    assert full.hybrid_override_pattern.count("M") == 23
    assert full.hybrid_override_pattern.count("*") == 6
    bias = N.dt_bias_init(jax.random.PRNGKey(0), full)
    dt = jax.nn.softplus(bias)
    assert float(dt.min()) >= 1e-3 * 0.999 and float(dt.max()) <= 0.1 * 1.001


# ------------------------------------------------------- through the engine
def _engine(params, cfg, **more):
    return AutoDist(strategy_builder=AllReduce()).build_inference(
        params, decode_model=N.decode_model(cfg), n_slots=4, max_len=128, **more)


def _is_greedy(params, cfg, prompt, served):
    seq = np.concatenate([prompt, served]).astype(np.int32)
    best = np.asarray(_forward(params, jnp.asarray(seq[None]), cfg)[0].argmax(-1))
    return list(best[len(prompt) - 1: len(seq) - 1]) == list(served)


def test_the_engine_serves_the_dense_forwards_greedy_stream(cfg, params):
    """Two requests one after another in slot 0, the second shorter than a
    chunk: each stream is the dense forward's greedy one; two programs;
    the state is priced and placed once."""
    try:
        engine = _engine(params, cfg)
        assert engine.page_len == PAGE and engine.prefill_chunk == 16
        assert engine.compiled_programs == 0
        # n_slots x 4 Mamba layers x (state + a tail of 3 rows of xBC)
        assert engine.ssm_state_bytes == engine.n_slots * 4 * (8 * 4 * 16 * 4 + 3 * 96 * 4)
        for prompt in (np.arange(3, 40) % 64, np.arange(20, 31) % 64):
            assert _is_greedy(params, cfg, prompt, engine.generate(prompt, 6))
        assert engine.compiled_programs == 2
    finally:
        AutoDist.reset_default()


def test_the_decode_spans_and_counters_carry_the_rows_updated(cfg, params):
    """``ssm_rows`` on each ``serve.decode_dispatch`` span is the rows the
    step decoded; ``serve_ssm_rows_total`` their sum; the gauge the state
    placed; the experts' facts as for Kimi."""
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
    dispatch = [s.attrs for s in spans if s.name == "serve.decode_dispatch"]
    steps = [s.attrs for s in spans if s.name == "serve.decode_step"]
    assert dispatch and all(d["ssm_rows"] == s["active"]
                            for d, s in zip(dispatch, steps))
    total = sum(d["ssm_rows"] for d in dispatch)
    assert total == engine.ssm_rows == registry.counter("serve_ssm_rows_total").value
    assert total == 3 * 4, "three requests, four decode steps each"
    assert registry.gauge("serve_ssm_state_bytes").value == engine.ssm_state_bytes
    last = [s.attrs for s in spans if s.name == "serve.tick_metrics"][-1]
    assert last["ssm_rows"] == total and last["moe_steps"] == len(steps)
    assert registry.counter("serve_moe_pairs_total").value == engine.fact_totals["moe_pairs"]


def test_the_pool_is_sized_after_the_state():
    """The state comes off the headroom before the pool takes its share."""
    class Spec:
        class tpu:
            hbm_bytes = 16e9

    plain = serve_pages.pool_size_from_spec(Spec, 1e6, params_bytes=4e9)
    less = serve_pages.pool_size_from_spec(Spec, 1e6, params_bytes=4e9,
                                           state_bytes=2e9)
    assert plain == int((16e9 * 0.8 - 4e9) * 0.5 // 1e6) + 1
    assert less == int((16e9 * 0.8 - 6e9) * 0.5 // 1e6) + 1


def test_prefix_sharing_int8_pages_and_speculation_are_refused_typed(cfg, params):
    with pytest.raises(serve_pages.CacheFeatureRefused, match="int8"):
        N.decode_model(dataclasses.replace(cfg, kv_quant=True))
    dm = N.decode_model(cfg)
    planes = lambda n, pl: dict(  # noqa: E731
        dm.init_paged_cache(n, pl), k_scale=jnp.zeros((n, pl, 1)))
    try:
        with pytest.raises(serve_pages.CacheFeatureRefused, match="prefix sharing"):
            _engine(params, cfg, prefix_cache=True)
        AutoDist.reset_default()
        with pytest.raises(serve_pages.CacheFeatureRefused, match="int8"):
            AutoDist(strategy_builder=AllReduce()).build_inference(
                params, decode_model=dataclasses.replace(dm, init_paged_cache=planes),
                n_slots=4, max_len=128)
        AutoDist.reset_default()
        with pytest.raises(serve_pages.CacheFeatureRefused, match="roll the state back"):
            AutoDist(strategy_builder=AllReduce()).build_inference(
                params, decode_model=dm, draft_params=params,
                draft_decode_model=dm, n_slots=4, max_len=128)
    finally:
        AutoDist.reset_default()
    assert dm.step_facts == ("moe_pairs", "moe_experts_hit")
    assert dm.verify_paged is None and dm.slot_state is not None
