"""What the engine places for its programs to read (``DecodeModel.
serving_params``, PR 37): GPT-2's paged programs read the products' leaves
and the tied table in the compute dtype, placed once, and the LayerNorm
leaves as given; a model that states nothing is placed as given. The same
tokens and logits come out of either tree."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import metrics as M
from autodist_tpu.api import AutoDist
from autodist_tpu.models import transformer as T
from autodist_tpu.serve.batcher import ContinuousBatcher
from autodist_tpu.serve.engine import tree_bytes
from autodist_tpu.strategy import AllReduce

CFG = T.TransformerConfig(vocab_size=96, num_layers=2, d_model=32, num_heads=4,
                          d_ff=64, max_seq_len=64, dtype=jnp.bfloat16)
DRAFT = T.TransformerConfig(vocab_size=96, num_layers=1, d_model=32, num_heads=4,
                            d_ff=64, max_seq_len=64, dtype=jnp.bfloat16)
NORMS = ("['ln1']", "['ln2']", "['ln_f']")


@pytest.fixture(scope="module")
def params():
    return T.init_params(jax.random.PRNGKey(0), CFG)


def _build(params, decode_model, **more):
    AutoDist.reset_default()
    try:
        return AutoDist(strategy_builder=AllReduce()).build_inference(
            params, decode_model=decode_model, n_slots=4, page_len=8,
            n_pages=33, **more)
    finally:
        AutoDist.reset_default()


def _leaves(tree):
    return {jax.tree_util.keystr(path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def _assert_gpt2_serving_tree(placed, given, cfg):
    """The products' leaves and both tables in ``cfg.dtype`` with the
    given values, the norms as given, the tied table once as ``head``."""
    placed, want = _leaves(placed), _leaves(given)
    table = want.pop("['embed']['embedding']")
    head = placed.pop("['head']")
    assert head.shape == (cfg.d_model, cfg.vocab_size) and head.dtype == cfg.dtype
    np.testing.assert_array_equal(np.asarray(head, np.float32),
                                  np.asarray(table.T.astype(cfg.dtype), np.float32))
    assert placed.keys() == want.keys()
    for path, leaf in placed.items():
        norm = any(n in path for n in NORMS)
        assert leaf.dtype == (want[path].dtype if norm else cfg.dtype), path
        np.testing.assert_array_equal(
            np.asarray(leaf, np.float32),
            np.asarray(want[path].astype(leaf.dtype), np.float32), err_msg=path)


def test_gpt2_places_its_products_leaves_in_the_compute_dtype(params):
    engine = _build(params, T.decode_model(CFG))
    _assert_gpt2_serving_tree(engine.params, params, CFG)
    norms = sum(tree_bytes(leaf) for path, leaf in _leaves(params).items()
                if any(n in path for n in NORMS))
    assert engine.param_bytes_given == tree_bytes(params)
    assert engine.param_bytes == tree_bytes(engine.params)
    assert engine.param_bytes == (engine.param_bytes_given + norms) // 2
    assert engine.param_bytes < 0.52 * engine.param_bytes_given
    registry = M.MetricsRegistry()
    ContinuousBatcher(engine, registry=registry)
    assert registry.gauge("serve_param_bytes").value == engine.param_bytes


def test_a_float32_config_reads_the_same_values(params):
    """At a float32 compute dtype nothing is cast: the tree only holds the
    table as the head reads it, and no byte is saved."""
    import dataclasses

    cfg = dataclasses.replace(CFG, dtype=jnp.float32)
    placed = T.serving_params(params, cfg)
    _assert_gpt2_serving_tree(placed, params, cfg)
    assert tree_bytes(placed) == tree_bytes(params)


def _bf16(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), tree)


def _assert_placed_as_given(engine, given):
    placed, want = _leaves(engine.params), _leaves(given)
    assert placed.keys() == want.keys()
    for path, leaf in placed.items():
        assert (leaf.shape, leaf.dtype) == (want[path].shape, want[path].dtype), path
    assert engine.param_bytes == engine.param_bytes_given == tree_bytes(given)


def test_evabyte_is_placed_as_given():
    from autodist_tpu.models import evabyte as E

    cfg = E.EvaByteConfig(vocab_size=64, hidden_size=32, intermediate_size=48,
                          num_hidden_layers=2, num_attention_heads=4,
                          max_position_embeddings=256, window_size=64,
                          chunk_size=16, num_pred_heads=2, prefill_chunk=32)
    given = _bf16(E.init_params(jax.random.PRNGKey(1), cfg))
    dm = E.decode_model(cfg)
    assert dm.serving_params is None
    AutoDist.reset_default()
    try:
        engine = AutoDist(strategy_builder=AllReduce()).build_inference(
            given, decode_model=dm, n_slots=2, max_len=128)
    finally:
        AutoDist.reset_default()
    _assert_placed_as_given(engine, given)


def test_kimi_is_placed_as_given():
    from autodist_tpu.models import kimi_k2 as K

    cfg = K.KimiK2Config(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_hidden_layers=2, num_attention_heads=4,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, n_routed_experts=16,
        num_experts_per_tok=4, experts_held=(4, 4), max_position_embeddings=128,
        dtype=jnp.bfloat16, page_len=8, prefill_chunk=16,
        rope_scaling=dict(K._yarn_defaults(), factor=4,
                          original_max_position_embeddings=32))
    given = _bf16(K.init_params(jax.random.PRNGKey(2), cfg))
    dm = K.decode_model(cfg)
    assert dm.serving_params is None
    AutoDist.reset_default()
    try:
        engine = AutoDist(strategy_builder=AllReduce()).build_inference(
            given, decode_model=dm, n_slots=2, max_len=64)
    finally:
        AutoDist.reset_default()
    _assert_placed_as_given(engine, given)


def test_paged_programs_read_either_tree_alike(params):
    """The chunk and the decode step give the same tokens, the same pages
    and the same logits from the float32 tree and from the tree the engine
    places: the cast moved, the products did not. (The logits agree to the
    float32 rounding that XLA's excess precision leaves out on one side,
    far under bfloat16's own step.)"""
    placed = T.serving_params(params, CFG)
    prompt = jnp.asarray([[5, 17, 3, 88, 41, 9, 60, 2]], jnp.int32)
    table = jnp.asarray([1, 2, 0, 0, 0, 0, 0, 0], jnp.int32)

    @jax.jit
    def serve(p):
        cache = T.init_paged_kv_cache(CFG, 9, 8)
        first, cache = T.forward_paged_prefill_chunk(
            p, prompt, jnp.int32(0), jnp.int32(8), cache, table, CFG)
        tokens = jnp.concatenate([first, jnp.asarray([7, 7, 7], jnp.int32)])
        tables = jnp.stack([table, jnp.zeros_like(table), jnp.zeros_like(table),
                            jnp.zeros_like(table)])
        positions = jnp.asarray([8, 0, 0, 0], jnp.int32)
        nxt, logits, cache = T.forward_paged_decode_step(
            p, tokens, positions, cache, tables, CFG, return_logits=True)
        return first, nxt, logits, cache

    (first, nxt, logits, cache), (first0, nxt0, logits0, cache0) = \
        serve(placed), serve(params)
    assert int(first[0]) == int(first0[0]) and list(nxt) == list(nxt0)
    np.testing.assert_allclose(logits, logits0, rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(cache), jax.tree_util.tree_leaves(cache0)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


@pytest.mark.parametrize("shape", [(2, 8), (8, 48)], ids=["columns", "gather"])
def test_one_shot_forward_reads_the_placed_tree(params, shape):
    """An engine asked for both surfaces places one tree, and the one-shot
    ``forward`` reads it as it reads the same values held the caller's way
    (the table ``[V, D]`` under ``embed``): a few tokens a column each,
    more through one gather, to a step of bfloat16 (the products' result
    type; the head's contraction is ordered otherwise)."""
    engine = _build(params, T.decode_model(CFG),
                    apply_fn=lambda p, tokens: T.forward(p, tokens, CFG))
    held = {k: v for k, v in engine.params.items() if k != "head"}
    held["embed"] = {"embedding": params["embed"]["embedding"].astype(CFG.dtype)}
    tokens = (np.arange(np.prod(shape)).reshape(shape) * 7 % CFG.vocab_size).astype(np.int32)
    np.testing.assert_allclose(np.asarray(engine.infer(tokens)),
                               np.asarray(T.forward(held, tokens, CFG)),
                               rtol=0, atol=1e-2)


def test_checkpoint_restore_places_the_serving_tree(tmp_path, params):
    from autodist_tpu.checkpoint.saver import Saver

    Saver(str(tmp_path)).save(params, step=1)
    restored = _build(jax.eval_shape(lambda: params), T.decode_model(CFG),
                      checkpoint=str(tmp_path))
    _assert_gpt2_serving_tree(restored.params, params, CFG)
    prompt = np.asarray([8, 6, 4, 1], np.int32)
    assert restored.generate(prompt, 6) == \
        _build(params, T.decode_model(CFG)).generate(prompt, 6)


def test_the_speculative_draft_is_placed_as_its_model_states(params):
    draft = T.init_params(jax.random.PRNGKey(3), DRAFT)
    engine = _build(params, T.decode_model(CFG), draft_params=draft,
                    draft_decode_model=T.decode_model(DRAFT), spec_k=2)
    _assert_gpt2_serving_tree(engine.params, params, CFG)
    _assert_gpt2_serving_tree(engine.draft_params, draft, DRAFT)
    prompt = np.asarray([3, 1, 4, 1, 5], np.int32)
    assert engine.generate(prompt, 8) == \
        _build(params, T.decode_model(CFG)).generate(prompt, 8)
