"""The GPT-2 layer has one rendering (``transformer._attn_part`` +
``_mlp_part``) and serving one engine: every program runs the one body once
a layer, the serving adapter has the paged surface only, and the MoE block
takes its norms' epsilon from the configuration like the dense model."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from autodist_tpu.models import layers as L
from autodist_tpu.models import moe
from autodist_tpu.models import transformer as T

CFG = T.TransformerConfig(
    vocab_size=97, num_layers=3, d_model=32, num_heads=2, d_ff=64,
    max_seq_len=32, causal=True, dtype=jnp.float32,
)
MOE_CFG = moe.MoEConfig(
    vocab_size=97, num_layers=3, d_model=32, num_heads=2, d_ff=64,
    max_seq_len=32, num_experts=4, dtype=jnp.float32,
)
ROWS, PAGES, PAGE_LEN, TABLE = 4, 9, 8, 4


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _trace_dense(fn, *args):
    params = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), CFG))
    jax.eval_shape(lambda p, *a: fn(p, *a, CFG), params, *args)


def _trace_paged(fn, *index_args):
    """A paged program's arguments: ``(tokens, positions..., cache,
    table(s))``, the cache slotted in before the last of ``index_args``."""
    cache = jax.eval_shape(lambda: T.init_paged_kv_cache(CFG, PAGES, PAGE_LEN))
    _trace_dense(fn, *index_args[:-1], cache, index_args[-1])


def _trace_moe():
    params = jax.eval_shape(
        lambda: moe.init_params(jax.random.PRNGKey(0), MOE_CFG))
    jax.eval_shape(lambda p, t: moe.forward(p, t, MOE_CFG), params, _i32(2, 16))


TRACE = {
    "forward": lambda: _trace_dense(T.forward, _i32(2, 16)),
    "prefill-chunk": lambda: _trace_paged(
        T.forward_paged_prefill_chunk, _i32(1, 8), _i32(), _i32(), _i32(TABLE)),
    "decode-step": lambda: _trace_paged(
        T.forward_paged_decode_step, _i32(ROWS), _i32(ROWS), _i32(ROWS, TABLE)),
    "verify": lambda: _trace_paged(
        T.forward_paged_verify, _i32(ROWS, 3), _i32(ROWS), _i32(ROWS, TABLE)),
    "moe-forward": _trace_moe,
}


@pytest.mark.parametrize("program", sorted(TRACE))
def test_every_program_runs_the_one_layer_body(monkeypatch, program):
    """Traced once, a program enters ``_attn_part`` exactly ``num_layers``
    times, and every LayerNorm it applies is one of the body's two a layer
    or the final one: a program that grew a copy of the layer of its own
    would norm outside the body."""
    calls = {"attn": 0, "norm": 0}

    def counted(name, fn):
        def wrapper(*args, **kwargs):
            calls[name] += 1
            return fn(*args, **kwargs)
        return wrapper

    attn_part = counted("attn", T._attn_part)
    monkeypatch.setattr(T, "_attn_part", attn_part)
    monkeypatch.setattr(moe, "_attn_part", attn_part)
    monkeypatch.setattr(L, "layernorm", counted("norm", L.layernorm))
    TRACE[program]()
    assert calls == {"attn": CFG.num_layers, "norm": 2 * CFG.num_layers + 1}


def test_one_serving_engine_and_a_paged_adapter_only():
    import autodist_tpu.serve as serve
    from autodist_tpu.serve.engine import DecodeModel

    fields = {f.name for f in dataclasses.fields(DecodeModel)}
    assert fields == {"init_paged_cache", "prefill_chunk", "decode_paged",
                      "verify_paged", "eos_id", "max_len", "cache_layout",
                      "step_facts", "steps_fact", "serving_params", "slot_state"}
    assert not fields & {"init_cache", "prefill", "decode_step"}
    gone = "Bucketed" + "InferenceEngine"
    assert not hasattr(serve, gone) and gone not in serve.__all__
    assert T.decode_model(CFG).verify_paged is not None


def test_moe_honours_a_stated_layer_norm_eps():
    """The MoE block passes ``cfg.layer_norm_eps`` like the dense model
    (its default is the 1e-6 the block used to hard-code)."""
    params = moe.init_params(jax.random.PRNGKey(0), MOE_CFG)
    tokens = jnp.arange(32, dtype=jnp.int32).reshape(2, 16) % MOE_CFG.vocab_size
    default, _ = moe.forward(params, tokens, MOE_CFG)
    explicit, _ = moe.forward(
        params, tokens, dataclasses.replace(MOE_CFG, layer_norm_eps=1e-6))
    stated, _ = moe.forward(
        params, tokens, dataclasses.replace(MOE_CFG, layer_norm_eps=1e-2))
    assert jnp.array_equal(default, explicit)
    assert not jnp.allclose(default, stated, atol=1e-5)
