"""Convert measured artifacts into a roofline verdict (VERDICT r4 #3).

Pure CPU artifact math — no TPU needed. Combines:

- ``docs/measured/membw.json``   — platform-achieved HBM bandwidth
  (examples/benchmark/membw.py, runs on the chip);
- ``docs/measured/resnet_op_profile.json`` / ``bert_op_profile.json`` —
  measured ms/step at a known batch (profile_ops.py, runs on the chip);
- the training step's OWN jaxpr — FLOP count and HBM-traffic envelopes
  (autodist_tpu.utils.roofline: lower bound = perfect fusion with MXU
  outputs materializing; upper = zero fusion)

into ``docs/measured/roofline.json``: per model, the measured step time
against ``t_roofline = max(flops/peak, lower_bytes/measured_bw)`` and
the achieved fraction of that ceiling. A fraction ≳ 0.8 means the step
is AT the hardware bound (the "ceiling proven" outcome); lower means
unexplained overhead with the gap quantified.

Exits 0 with a "pending" note when the device artifacts are missing, so
the TPU queue can run it unconditionally after the profile jobs.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..")))

MEASURED = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "docs", "measured"))

def _peak_flops_for(device_kind: str) -> float:
    """Per-chip peak bf16 FLOPs/s from the package's one peak table, keyed
    on the device kind membw.json recorded — a hardcoded v5e constant would
    silently fake the verdict on any other chip generation, and so would a
    default for a kind the table does not list (it raises)."""
    from autodist_tpu.obs.profiler import peak_flops_for_kind

    return peak_flops_for_kind(device_kind)

PROFILES = {
    # model key -> (zoo name, kwargs, profile artifact)
    "resnet50": ("resnet", {}, "resnet_op_profile.json"),
    "bert_base": ("bert_base", {"max_seq_len": 128}, "bert_op_profile.json"),
}


def _load(name):
    path = os.path.join(MEASURED, name)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def step_bounds(zoo_name, kwargs, batch):
    """Traffic/FLOP envelopes for ONE full train step (fwd+bwd+sgd).

    The arithmetic side prefers the zoo's vetted ``flops_per_example``
    (the same number MFU reporting uses — keeps the fractions mutually
    consistent); the jaxpr count stands in when a model doesn't declare
    one (it over-counts gradient convs, see utils/roofline.py).
    """
    import jax
    import optax

    from autodist_tpu.models import get_model
    from autodist_tpu.utils.roofline import traffic_bounds

    model = get_model(zoo_name, **kwargs)
    params = model.init(jax.random.PRNGKey(0))
    example = model.example_batch(batch)
    tx = optax.sgd(0.1)
    opt_state = tx.init(params)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(model.loss_fn)(params, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    bounds = traffic_bounds(train_step, params, opt_state, example)
    if getattr(model, "flops_per_example", None):
        bounds["flops_jaxpr"] = bounds["flops"]
        bounds["flops"] = float(model.flops_per_example) * batch
        bounds["flops_source"] = "model.flops_per_example"
    else:
        bounds["flops_source"] = "jaxpr"
    return bounds


def main() -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")  # tracing only — never dispatch

    membw = _load("membw.json")
    if membw is None:
        # Non-zero so the queue driver RETRIES instead of marking the job
        # done with the verdict never computed (the upstream membw job may
        # simply not have run yet this window).
        print(json.dumps({"metric": "roofline", "value": 0, "unit": "pending",
                          "note": "membw.json not measured yet"}))
        return 3
    from autodist_tpu.resource_spec import HBM_BY_ACCELERATOR, hbm_spec_for_kind

    kind = str(membw.get("device", ""))
    spec_gb_s = hbm_spec_for_kind(kind)[1]
    spec_known = any(k in kind.lower() for k in HBM_BY_ACCELERATOR)
    if membw.get("suspect") or (spec_known
                                and membw["best_gb_s"] > 1.2 * spec_gb_s):
        # A bandwidth "measurement" above physics means the microbenchmark
        # was optimized away (the scan-collapse failure mode membw.py now
        # self-flags). A verdict priced against it would be fiction.
        why = (f"{membw['best_gb_s']:.0f} GB/s > {spec_gb_s:.0f} GB/s spec"
               if membw["best_gb_s"] > 1.2 * spec_gb_s
               else "artifact self-flagged suspect")
        print(json.dumps({"metric": "roofline", "value": 0, "unit": "pending",
                          "note": f"membw.json implausible ({why}); "
                                  f"re-run examples/benchmark/membw.py"}))
        return 3
    bw = membw["best_gb_s"] * 1e9
    peak_flops = _peak_flops_for(str(membw.get("device", "")))

    from autodist_tpu.utils.roofline import roofline_times

    report = {"bw_gb_s": membw["best_gb_s"], "peak_tflops": peak_flops / 1e12,
              "device": membw.get("device", ""), "models": {}}
    for key, (zoo, kwargs, profile_name) in PROFILES.items():
        prof = _load(profile_name)
        if prof is None:
            report["models"][key] = {"note": f"{profile_name} pending"}
            continue
        batch = int(prof["batch"])
        measured_s = float(prof["total_ms_per_step"]) / 1e3
        bounds = step_bounds(zoo, kwargs, batch)
        times = roofline_times(bounds, peak_flops, bw)
        frac = times["t_roofline_s"] / measured_s if measured_s else float("nan")
        report["models"][key] = {
            "batch": batch,
            "measured_ms_per_step": round(measured_s * 1e3, 3),
            "t_mxu_ms": round(times["t_mxu_s"] * 1e3, 3),
            "t_hbm_lower_ms": round(times["t_hbm_lower_s"] * 1e3, 3),
            "t_hbm_upper_ms": round(times["t_hbm_upper_s"] * 1e3, 3),
            "t_roofline_ms": round(times["t_roofline_s"] * 1e3, 3),
            "roofline_fraction": round(frac, 3),
            "binding_side": ("mxu" if times["t_mxu_s"] >= times["t_hbm_lower_s"]
                             else "hbm"),
            "flops_per_step_g": round(bounds["flops"] / 1e9, 2),
            "flops_source": bounds["flops_source"],
            "lower_traffic_gb": round(bounds["lower_bytes"] / 1e9, 3),
            "upper_traffic_gb": round(bounds["upper_bytes"] / 1e9, 3),
            "verdict": ("at hardware ceiling" if frac >= 0.8 else
                        f"unexplained gap: step is {1 / frac:.2f}x the "
                        f"roofline bound" if frac > 0 else "n/a"),
        }
        print(f"[{key}] measured {measured_s * 1e3:.2f} ms vs roofline "
              f"{times['t_roofline_s'] * 1e3:.2f} ms "
              f"({report['models'][key]['binding_side']}-bound, "
              f"fraction {frac:.2f})")

    out = os.path.join(MEASURED, "roofline.json")
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    done = [m for m in report["models"].values() if "roofline_fraction" in m]
    print(json.dumps({
        "metric": "roofline_fraction_min",
        "value": min((m["roofline_fraction"] for m in done), default=0),
        "unit": "fraction_of_hw_bound",
        "models_analyzed": len(done),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
