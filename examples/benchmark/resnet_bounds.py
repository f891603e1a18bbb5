"""ResNet-50 bound experiments: isolate remaining non-MXU cost.

Usage (on the bench chip)::

    python examples/benchmark/resnet_bounds.py base 128 20
    python examples/benchmark/resnet_bounds.py nostats 128 20
    python examples/benchmark/resnet_bounds.py avgstem 128 20

Each variant prints ms/step, img/s and MFU for a windowed run with the
batch pinned in HBM (docs/performance.md "compute" methodology). The
bounds quantify how much of the remaining step time the BN statistics
reductions and the maxpool backward (SelectAndScatter) account for —
the per-op evidence behind the conv-net ceiling discussion in
docs/performance.md.

Variants (current repo BN = one-pass forward + hand-written vjp backward):
  base          — repo as-is
  autodiffbn    — BN backward via autodiff through the moments (the r2
                  formulation): A/B for the r3 custom-vjp backward
  nostats       — BN without batch statistics (scale/bias only): bounds the
                  cost of the stats reductions
  avgstem       — stem max_pool replaced by avg_pool: bounds the
                  SelectAndScatter (maxpool backward) cost
  bf16feed      — batch pinned in HBM as bf16 (halves image read traffic)
  nchw          — convs declared NCHW instead of NHWC: layout-assignment
                  A/B (XLA re-lays-out either way; the declared order can
                  steer which fusion layouts it picks)
  dotstats      — BN statistics (fwd moments AND bwd sums) expressed as
                  [1,M]@[M,C] matmul reductions instead of cross-NHW
                  reduces. Hypothesis from the r3 op profile: the reduces
                  make layout assignment put BATCH on the 128-lane minor
                  dim of conv inputs ({0,3,2,1}) while conv outputs are
                  channel-minor — mismatched layouts inside every conv
                  kernel. A dot-shaped reduction prefers channel-minor,
                  which may let convs run layout-matched.
"""
import os
import sys
import time

# Allow `python examples/benchmark/resnet_bounds.py` straight from a repo
# checkout (script dir, not the repo root, lands on sys.path).
sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..")))

import jax
import jax.numpy as jnp
import optax
from jax import lax

from autodist_tpu.kernel.lowering import DistributedTrainStep, GraphTransformer
from autodist_tpu.kernel.mesh import build_mesh
from autodist_tpu.model_item import ModelItem, OptimizerSpec
from autodist_tpu.models import get_model
from autodist_tpu.models import layers as L
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.strategy.all_reduce_strategy import AllReduce
from autodist_tpu.strategy.base import StrategyCompiler

VARIANT = sys.argv[1] if len(sys.argv) > 1 else "base"
BATCH = int(sys.argv[2]) if len(sys.argv) > 2 else 128
WINDOW = int(sys.argv[3]) if len(sys.argv) > 3 else 20
PEAK = 197e12


def bn_nostats(p, x, eps=1e-5):
    return x * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


if VARIANT == "nostats":
    L.batchnorm = bn_nostats
elif VARIANT == "autodiffbn":
    L.batchnorm = L._batchnorm_autodiff
elif VARIANT == "avgstem":
    orig_max_pool = L.max_pool
    L.max_pool = lambda x, w, s, padding="SAME": L.avg_pool(x, w, s, padding)
elif VARIANT == "dotstats":
    import functools

    import numpy as np

    def _colsum(m2d):
        """Per-column sum via a dot against a runtime ones vector (iota-
        derived so the algebraic simplifier cannot rewrite it back into the
        cross-lane reduce this variant exists to avoid)."""
        n = m2d.shape[0]
        ones = (jax.lax.iota(jnp.float32, n) * 0.0 + 1.0)[None, :]
        return jax.lax.dot_general(
            ones, m2d, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)[0]

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def _bn_dot(scale, bias, x, eps):
        return L._batchnorm_autodiff({"scale": scale, "bias": bias}, x, eps)

    def _bn_dot_fwd(scale, bias, x, eps):
        c = x.shape[-1]
        x2d = x.astype(jnp.float32).reshape(-1, c)
        n = x2d.shape[0]
        mean = _colsum(x2d) / n
        var_raw = _colsum(x2d * x2d) / n - mean * mean
        var = jnp.maximum(var_raw, 0.0)
        inv = jax.lax.rsqrt(var + eps)
        y = (((x.astype(jnp.float32) - mean) * (scale * inv)) + bias).astype(x.dtype)
        return y, (x, mean, inv, scale, var_raw > 0.0)

    def _bn_dot_bwd(eps, res, dy):
        x, mean, inv, scale, var_live = res
        c = x.shape[-1]
        n = float(np.prod(x.shape[:-1]))
        dy32 = dy.astype(jnp.float32)
        x_hat = (x.astype(jnp.float32) - mean) * inv
        sum_dy = _colsum(dy32.reshape(-1, c))
        sum_dy_xhat = _colsum((dy32 * x_hat).reshape(-1, c))
        var_term = jnp.where(var_live, sum_dy_xhat / n, 0.0)
        dx = (scale * inv) * (dy32 - sum_dy / n - x_hat * var_term)
        return sum_dy_xhat, sum_dy, dx.astype(x.dtype)

    _bn_dot.defvjp(_bn_dot_fwd, _bn_dot_bwd)
    L.batchnorm = lambda p, x, eps=1e-5: _bn_dot(p["scale"], p["bias"], x, eps)
elif VARIANT == "nchw":
    _orig_conv = L.conv

    def _conv_nchw(p, x, stride=1, padding="SAME", *, compute_dtype=None):
        k = p["kernel"]
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
            k = k.astype(compute_dtype)
        y = lax.conv_general_dilated(
            x.transpose(0, 3, 1, 2), k,
            window_strides=(stride, stride), padding=padding,
            dimension_numbers=("NCHW", "HWIO", "NCHW"),
        )
        return y.transpose(0, 2, 3, 1)

    L.conv = _conv_nchw

spec = get_model("resnet")
params = spec.init(jax.random.PRNGKey(0))
batch = spec.example_batch(BATCH)
if VARIANT == "bf16feed":
    batch = {"images": batch["images"].astype(jnp.bfloat16),
             "labels": batch["labels"]}

rs = ResourceSpec.from_local_devices()
mi = ModelItem.from_params(
    params, optimizer_spec=OptimizerSpec("sgd", {"learning_rate": 0.1}),
    loss_fn=spec.loss_fn, example_batch=batch)
strategy = StrategyCompiler(mi).compile(AllReduce().build(mi, rs))
plan = GraphTransformer(strategy, mi, build_mesh(rs, axes=("data",))).transform()
step = DistributedTrainStep(plan, spec.loss_fn, optax.sgd(0.1))
state = step.init(params)
batch = jax.device_put(batch, step.plan.batch_shardings(batch))
jax.block_until_ready(batch)

state, m = step.run(state, batch, WINDOW)
float(m["loss"][-1])
# Each trial: 4 windows back-to-back, one trailing fetch — the programs
# pipeline on the device, so it never idles on a host round trip between
# windows (docs/performance.md pipelined methodology).
best = None
for _ in range(2):
    t0 = time.perf_counter()
    for _ in range(4):
        state, m = step.run(state, batch, WINDOW)
    float(m["loss"][-1])
    dt = (time.perf_counter() - t0) / (4 * WINDOW)
    best = dt if best is None else min(best, dt)
img_s = BATCH / best
flops = spec.flops_per_example * BATCH / best
print(f"VARIANT {VARIANT} b{BATCH} w{WINDOW}: {best*1e3:.2f} ms/step  "
      f"{img_s:.0f} img/s  {flops/1e12:.1f} TFLOP/s  MFU={flops/PEAK:.3f}")
