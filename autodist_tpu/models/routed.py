"""The routed expert layer that Kimi K2 and Nemotron-H share: sigmoid
scores over every routed expert, the ``K`` largest of ``sigma + b`` chosen,
weights from ``sigma`` alone (DeepSeek-V3's ``noaux_tc``, arXiv 2412.19437),
and a chip that holds a share of the experts.

A configuration hands in what it states: ``held`` (``(first, count)`` of
the routed experts this chip holds), ``num_experts_per_tok``,
``norm_topk_prob``, ``routed_scaling_factor``, ``dtype`` (the products'
compute type) and ``expert_act``, the expert's form:

- ``silu_gated``: ``E(u) = W_down (silu(W_gate u) * W_up u)`` (Kimi K2);
- ``relu2``: ``E(u) = W_down relu(W_up u)^2``, no gate (Nemotron-H).

The shared expert has the routed experts' form. No capacity, no token
dropped: a chip routes over all experts, normalises over all chosen, and
adds the terms of the chosen experts it holds plus the shared expert. The
held experts' products are one grouped matrix product a projection
(``ops/grouped_matmul.py``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from autodist_tpu.models import layers as L
from autodist_tpu.ops import grouped_matmul as gm

EXPERT_ACTS = ("silu_gated", "relu2")


def resolve(choice: str, off_chip: str) -> str:
    """``auto`` is the Mosaic kernel on a TPU and plain ``jnp`` off it."""
    if choice != "auto":
        return choice
    return "kernel" if jax.default_backend() == "tpu" else off_chip


def route(router_p, u, cfg):
    """``u [T, D]`` (float32) -> ``(experts [T, K], weights [T, K])``: scores
    in float32 at the highest precision, the ``K`` largest of ``sigma + b``,
    weighted by ``sigma`` over their sum times the scaling factor."""
    scores = jax.nn.sigmoid(jnp.matmul(
        u.astype(jnp.float32), router_p["kernel"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, experts = jax.lax.top_k(
        scores + router_p["bias"].astype(jnp.float32), cfg.num_experts_per_tok)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg.norm_topk_prob:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), weights * cfg.routed_scaling_factor


def _act(cfg):
    if cfg.expert_act not in EXPERT_ACTS:
        raise ValueError(f"expert_act {cfg.expert_act!r}; one of {EXPERT_ACTS}")
    return cfg.expert_act


def relu2(x):
    r = jax.nn.relu(x)
    return r * r


def mlp(p, u, cfg):
    """The shared expert (or a dense layer of the expert's form) on ``u``."""
    if _act(cfg) == "silu_gated":
        return L.gated_mlp(p, u, compute_dtype=cfg.dtype)
    mid = relu2(L.dense(p["up"], u, compute_dtype=cfg.dtype))
    return L.dense(p["down"], mid, compute_dtype=cfg.dtype)


def expert_ffn(layer_p, u, cfg, live=None, impl: str = "auto"):
    """The expert layer on ``u [T, D]`` (float32): the shared expert plus
    the held experts' share of the routed sum. ``live [T]`` (bool) leaves
    tokens that are padding out of the routing. ``impl`` is the grouped
    product's: ``kernel``, ``reference``, or ``auto`` as the programs call
    it. Returns ``(out [T, D] float32, pairs on held experts, held experts
    hit)``."""
    first, count = cfg.held
    experts, weights = route(layer_p["router"], u, cfg)
    if live is not None:
        experts = jnp.where(live[:, None], experts, -1)
    groups = gm.group_rows(experts, first, count)
    impl = resolve(impl, "reference")
    e = layer_p["experts"]
    rows = gm.gather_rows(u.astype(cfg.dtype), groups)
    if _act(cfg) == "silu_gated":
        mid = (jax.nn.silu(gm.grouped_matmul(rows, e["gate"], groups, impl=impl))
               * gm.grouped_matmul(rows, e["up"], groups, impl=impl))
    else:
        mid = relu2(gm.grouped_matmul(rows, e["up"], groups, impl=impl))
    routed = gm.combine_rows(
        gm.grouped_matmul(mid, e["down"], groups, impl=impl), groups, weights)
    shared = mlp(layer_p["shared"], u, cfg)
    return routed + shared.astype(jnp.float32), groups.n_pairs, groups.n_hit
