"""Host-offload A/B on a real device: PS params in HBM vs pinned host.

VERDICT r4 weak #4: the ``host_offload=True`` path had only ever been
validated at plan level (sharding `pinned_host` plumbing) because the
lowering gate disables in-jit host streaming off-TPU. This experiment
executes both variants on the actual chip in one process, one after the
other (the second build starts after the first state is freed):

  A. PS strategy, everything HBM-resident           (host_offload=False)
  B. PS strategy, params+slots in pinned host memory (host_offload=True)

and checks (1) B actually engaged (offloaded plan count > 0), (2) the
loss trajectories agree step-for-step (same math, different residency),
and (3) the streaming cost, reported as B/A step-time ratio.

Reference placement semantics: ps_strategy.py:38-55 (params live on the
PS host, workers pull per step). Artifact: docs/measured/host_offload_ab.json.

On a non-TPU backend the gate disables offload with a warning; the script
still runs (A == B trivially) and marks ``offload_engaged: false`` — that
is the CPU smoke mode, not a measurement.
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..")))

import autodist_tpu as ad
from autodist_tpu.models import get_model

OUT = os.path.join(
    os.path.dirname(__file__), "..", "..", "docs", "measured", "host_offload_ab.json"
)

# Env-overridable so the 1-core CPU smoke can shrink the config; the TPU
# queue job runs the defaults.
MODEL = os.environ.get("HOAB_MODEL", "lstm_lm")
STEPS = int(os.environ.get("HOAB_STEPS", "24"))
WINDOW = int(os.environ.get("HOAB_WINDOW", "8"))
BATCH = int(os.environ.get("HOAB_BATCH", "64"))


def run_variant(tag, step, state, batch, n_windows: int):
    """Warm window (compile) + timed windows; returns (losses, mean_window_s)."""
    state, metrics = step.run(state, batch, WINDOW)
    losses = [float(x) for x in np.asarray(metrics["loss"])]
    print(f"[{tag}] warm window done (loss {losses[-1]:.4f})", flush=True)
    times = []
    for i in range(n_windows):
        t0 = time.perf_counter()
        state, metrics = step.run(state, batch, WINDOW)
        losses.extend(float(x) for x in np.asarray(metrics["loss"]))
        times.append(time.perf_counter() - t0)
        print(f"[{tag}] window {i + 1}/{n_windows}: {times[-1]:.2f}s", flush=True)
    return losses, float(np.mean(times))


def main():
    model = get_model(MODEL)
    params = model.init(jax.random.PRNGKey(0))
    example = model.example_batch(BATCH)

    autodist = ad.AutoDist(strategy_builder=ad.strategy.from_name("PS"))
    n_windows = STEPS // WINDOW

    results = {}
    for tag, offload in (("hbm", False), ("pinned_host", True)):
        step = autodist.build(
            model.loss_fn, params, example, sparse_names=model.sparse_names,
            host_offload=offload,
        )
        n_off = sum(1 for p in step.plan.var_plans.values() if p.offload)
        state = step.init(params)
        batch = jax.device_put(example, step.plan.batch_shardings(example))
        jax.block_until_ready(batch)
        losses, mean_window_s = run_variant(tag, step, state, batch, n_windows)
        results[tag] = {
            "losses": [round(x, 6) for x in losses],
            "mean_window_s": round(mean_window_s, 5),
            "mean_step_s": round(mean_window_s / WINDOW, 6),
            "offloaded_vars": n_off,
        }
        del step, state, batch

    a, b = results["hbm"], results["pinned_host"]
    engaged = b["offloaded_vars"] > 0
    # Same update math either side; bitwise layout may differ, so compare
    # loosely. A drift here means offload changed numerics — a bug.
    la, lb = np.array(a["losses"]), np.array(b["losses"])
    match = bool(np.allclose(la, lb, rtol=2e-3, atol=2e-3))
    artifact = {
        "experiment": "host_offload_ab",
        "model": MODEL,
        "batch": BATCH,
        "steps": STEPS,
        "platform": jax.devices()[0].platform,
        "offload_engaged": engaged,
        "losses_match": match,
        "stream_cost_ratio": round(b["mean_step_s"] / max(a["mean_step_s"], 1e-9), 3),
        "hbm": a,
        "pinned_host": b,
        "at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    os.makedirs(os.path.dirname(os.path.abspath(OUT)), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(artifact, f, indent=2)
    print(json.dumps({
        "metric": "host_offload_stream_cost_ratio",
        "value": artifact["stream_cost_ratio"],
        "unit": "x_vs_hbm",
        "offload_engaged": engaged,
        "losses_match": match,
    }))
    if engaged and not match:
        sys.exit(2)


if __name__ == "__main__":
    main()
