"""Mixture-of-Experts transformer — the expert-parallel workload.

TPU-native extension beyond the reference (no expert parallelism anywhere in
``/root/reference/autodist/`` — SURVEY.md §2.2): a Switch-style top-1 routed
FFN in the Mesh-TensorFlow/Switch-Transformer einsum formulation (arXiv
2101.03961), which is what maps onto XLA: dispatch and combine are dense
einsums over a static capacity dim (no dynamic shapes), expert kernels carry
a leading ``[E, ...]`` dim that the strategy lowers onto the mesh "expert"
axis, and GSPMD inserts the token all_to_alls implied by the shardings.

Routing maths (per token t, expert e, capacity slot c):
  gates[t,e]       = softmax(x @ router)        — fp32
  keep top-1 expert per token, positions within an expert ranked by arrival;
  dispatch[t,e,c]  = 1 if token t sits in slot c of expert e (capacity-
                     dropped tokens pass through the residual unchanged)
  expert_in[e,c,d] = dispatch^T @ x             — the EP all_to_all boundary
  expert_out       = ffn_e(expert_in)           — batched over E
  y[t,d]           = (dispatch * gate)[t,e,c] @ expert_out[e,c,d]

An auxiliary load-balance loss (mean fraction·prob product, Switch eq. 4)
is returned through the model's aux metrics.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Dict

import jax
import jax.numpy as jnp

from autodist_tpu.models import layers as L
from autodist_tpu.models.spec import ModelSpec, register_model
from autodist_tpu.models.transformer import (
    TransformerConfig,
    _attn_part,
    _train_attend,
)


@dataclass
class MoEConfig(TransformerConfig):
    num_experts: int = 8
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01


# ---------------------------------------------------------------------- params
def init_params(rng, cfg: MoEConfig) -> Dict[str, Any]:
    keys = jax.random.split(rng, cfg.num_layers + 2)
    params: Dict[str, Any] = {
        "embed": L.embedding_init(keys[0], cfg.vocab_size, cfg.d_model),
        "pos_embed": L.embedding_init(keys[1], cfg.max_seq_len, cfg.d_model),
        "ln_f": L.layernorm_init(cfg.d_model),
    }
    for i in range(cfg.num_layers):
        k = jax.random.split(keys[i + 2], 8)
        params[f"layers_{i}"] = {
            "ln1": L.layernorm_init(cfg.d_model),
            "attn": {
                "wq": L.dense_init(k[0], cfg.d_model, cfg.d_model),
                "wk": L.dense_init(k[1], cfg.d_model, cfg.d_model),
                "wv": L.dense_init(k[2], cfg.d_model, cfg.d_model),
                "wo": L.dense_init(k[3], cfg.d_model, cfg.d_model),
            },
            "ln2": L.layernorm_init(cfg.d_model),
            "moe": {
                "router": {"kernel": L.normal(k[4], (cfg.d_model, cfg.num_experts))},
                # Expert kernels: leading E dim — the expert-axis shard dim.
                "expert_wi": L.normal(
                    k[5], (cfg.num_experts, cfg.d_model, cfg.d_ff), stddev=0.02
                ),
                "expert_wo": L.normal(
                    k[6], (cfg.num_experts, cfg.d_ff, cfg.d_model), stddev=0.02
                ),
            },
        }
    return params


# ----------------------------------------------------------------------- layer
def moe_ffn(p, x, cfg: MoEConfig):
    """Switch FFN on [T, d] tokens. Returns (y, aux_loss)."""
    tokens, d = x.shape
    e = cfg.num_experts
    capacity = max(1, int(cfg.capacity_factor * tokens / e))

    gates = jax.nn.softmax(
        (x.astype(jnp.float32) @ p["router"]["kernel"].astype(jnp.float32)), axis=-1
    )                                                   # [T, E] fp32
    expert_idx = jnp.argmax(gates, axis=-1)             # [T]
    gate = jnp.max(gates, axis=-1)                      # [T]
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)   # [T, E]

    # Position of each token within its expert's queue (arrival order).
    position = jnp.cumsum(onehot, axis=0) * onehot - 1.0         # [T, E]
    in_capacity = (position >= 0) & (position < capacity)
    dispatch = onehot * in_capacity                              # [T, E]
    # [T, E, C]: one-hot over the capacity slot (-1 → all-zero row, which
    # is exactly the capacity-dropped mask).
    slot = jax.nn.one_hot(position.astype(jnp.int32), capacity, dtype=jnp.float32)
    dispatch_tec = dispatch[..., None] * slot                    # [T, E, C]
    combine_tec = dispatch_tec * gate[:, None, None]

    # Dispatch → per-expert batches (the EP boundary: with expert_wi/wo
    # sharded on the expert axis, GSPMD turns this einsum pair into
    # all_to_alls over ICI).
    xin = jnp.einsum("tec,td->ecd", dispatch_tec.astype(cfg.dtype), x)   # [E, C, d]
    h = jax.nn.relu(jnp.einsum("ecd,edf->ecf", xin, p["expert_wi"].astype(cfg.dtype)))
    out = jnp.einsum("ecf,efd->ecd", h, p["expert_wo"].astype(cfg.dtype))  # [E, C, d]
    y = jnp.einsum("tec,ecd->td", combine_tec.astype(cfg.dtype), out)      # [T, d]

    # Switch load-balance aux loss: E * sum_e fraction_e * prob_e.
    fraction = onehot.mean(axis=0)                      # tokens routed to e
    prob = gates.mean(axis=0)                           # mean router prob
    aux = e * jnp.sum(fraction * prob)
    return y, aux


def _block(bp, x, cfg: MoEConfig):
    """The dense model's attention half (``transformer._attn_part``, with
    training's ``attend``) and the Switch FFN in place of its MLP half."""
    b, s, d = x.shape
    x, _ = _attn_part(bp, x, _train_attend(cfg), cfg)
    h = L.layernorm(bp["ln2"], x, cfg.layer_norm_eps)
    y, aux = moe_ffn(bp["moe"], h.reshape(b * s, d), cfg)
    return x + y.reshape(b, s, d).astype(x.dtype), aux


def forward(params, tokens, cfg: MoEConfig):
    b, s = tokens.shape
    x = (L.embedding_lookup(params["embed"], tokens)
         + L.embedding_lookup(params["pos_embed"], jnp.arange(s))[None]).astype(cfg.dtype)
    aux_total = 0.0
    for i in range(cfg.num_layers):
        block = jax.checkpoint(_block) if cfg.remat else _block
        x, aux = block(params[f"layers_{i}"], x, cfg)
        aux_total = aux_total + aux
    x = L.layernorm(params["ln_f"], x, cfg.layer_norm_eps)
    logits = jnp.einsum(
        "bsd,vd->bsv", x, params["embed"]["embedding"].astype(cfg.dtype)
    ).astype(jnp.float32)
    return logits, aux_total / cfg.num_layers


@register_model("moe_transformer")
def moe_transformer(**overrides) -> ModelSpec:
    cfg = MoEConfig(
        vocab_size=8192, num_layers=4, d_model=512, num_heads=8, d_ff=1024,
        max_seq_len=128, num_experts=8,
    )
    cfg = replace(cfg, **overrides)

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        logits, aux = forward(params, tokens[:, :-1], cfg)
        lm = L.softmax_xent(logits, tokens[:, 1:])
        return lm + cfg.aux_loss_weight * aux

    def example_batch(batch_size: int):
        import numpy as np

        rng = np.random.default_rng(0)
        return {
            "tokens": rng.integers(
                0, cfg.vocab_size, (batch_size, cfg.max_seq_len)
            ).astype(np.int32)
        }

    return ModelSpec(
        name=f"moe_transformer_{cfg.num_layers}x{cfg.num_experts}e",
        init=lambda rng: init_params(rng, cfg),
        loss_fn=loss_fn,
        example_batch=example_batch,
        apply=lambda p, tokens: forward(p, tokens, cfg)[0],
        sparse_names=("embed/embedding",),
        expert_names=("expert_",),
        config=cfg,
    )
