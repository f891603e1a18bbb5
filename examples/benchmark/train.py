"""Universal benchmark runner: any zoo model × any strategy × any cluster.

TPU-native replacement for the reference's per-model benchmark drivers
(``/root/reference/examples/benchmark/{imagenet,bert,ncf}.py``) which each
vendored an official-models trainer behind an ``--autodist_strategy`` flag.
One runner covers the same matrix:

    python examples/benchmark/train.py --model resnet50 --strategy AllReduce \
        --batch-size 256 --steps 50
    python examples/benchmark/train.py --model bert_base --strategy PartitionedPS
    python examples/benchmark/train.py --model lm1b --strategy Parallax
    python examples/benchmark/train.py --model ncf --strategy PSLoadBalancing

Data is synthetic (shape-identical to the real datasets), streamed through
the native prefetching DataLoader; timing comes from StepTimer with compile
steps excluded; ``--trace`` writes a TensorBoard profile of one step.
Prints one JSON line compatible with bench.py's schema.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import jax
import numpy as np

# Allow `python examples/benchmark/train.py` straight from a repo checkout
# (script dir, not the repo root, lands on sys.path in that invocation).
sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..")))

import autodist_tpu as ad
from autodist_tpu.data import DataLoader
from autodist_tpu.models import get_model
from autodist_tpu.obs import StepTimer, recorder as obs_recorder, spans as obs_spans
from autodist_tpu.utils.compile_cache import enable_compile_cache

# model key -> (zoo name, factory kwargs, items metric)
MODELS = {
    "resnet50": ("resnet", {"depth": 50, "image_size": 224}, "images"),
    "resnet101": ("resnet", {"depth": 101, "image_size": 224}, "images"),
    "vgg16": ("vgg", {"depth": 16, "image_size": 224}, "images"),
    "densenet121": ("densenet", {"depth": 121, "image_size": 224}, "images"),
    "inceptionv3": ("inception", {"image_size": 299}, "images"),
    "bert_base": ("bert_base", {}, "tokens"),
    "bert_large": ("bert_large", {}, "tokens"),
    "transformer": ("transformer", {}, "tokens"),
    "lm1b": ("lstm_lm", {}, "tokens"),
    "ncf": ("ncf", {}, "examples"),
    "moe": ("moe_transformer", {}, "tokens"),
}


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", choices=sorted(MODELS), default="resnet50")
    p.add_argument("--strategy", default="AllReduce",
                   help=f"one of {sorted(ad.strategy.BUILTIN_BUILDERS)}")
    p.add_argument("--resource-spec", default="", help="cluster yml (default: local devices)")
    p.add_argument("--batch-size", type=int, default=0, help="global batch (0 = 8/device)")
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--window", type=int, default=10,
                   help="steps per device-side scan window (1 = per-step dispatch)")
    p.add_argument("--accum", type=int, default=1,
                   help="gradient accumulation microbatches per step")
    p.add_argument("--compute-dtype", default="",
                   help="mixed-precision policy, e.g. bfloat16 (bf16 "
                        "compute, fp32 master weights); empty = model "
                        "default")
    p.add_argument("--remat", default="", type=str.lower,
                   help="rematerialization: 'true' (save nothing), "
                        "'false'/'off'/'' (disabled), or a "
                        "jax.checkpoint_policies name like dots_saveable")
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--data-dir", default="",
                   help="stream batches from a sharded on-disk dataset "
                        "(autodist_tpu.data.write_dataset layout, feature "
                        "names matching the model's batch dict) instead of "
                        "synthetic in-memory data")
    p.add_argument("--pin", action="store_true",
                   help="pin ONE batch in HBM and reuse it every window: "
                        "measures the steady-state device rate (the 'compute' "
                        "methodology in docs/performance.md) instead of "
                        "paying a host upload per window ('fed')")
    p.add_argument("--trace", action="store_true", help="profile one step to TensorBoard")
    p.add_argument("--profile-dir", default="",
                   help="capture a jax.profiler trace of ONE windowed run "
                        "into this dir (created if missing, via "
                        "utils.tracing.trace) — attributable afterwards "
                        "with examples/benchmark/profile_ops.py --parse or "
                        "the obs/attrib.py measured-wire join "
                        "(docs/observability.md § attribution)")
    p.add_argument("--trace-out", default="",
                   help="write a chrome-trace/Perfetto JSON of the run's "
                        "host-side spans (warmup/timed windows, compiles) "
                        "to this path (docs/observability.md)")
    p.add_argument("--model-kwargs", default="",
                   help='JSON overrides for the model factory, e.g. \'{"num_layers": 2}\'')
    return p.parse_args()


def main():
    args = parse_args()
    enable_compile_cache()
    zoo_name, kwargs, item_kind = MODELS[args.model]
    if args.model_kwargs:
        kwargs = {**kwargs, **json.loads(args.model_kwargs)}
    model = get_model(zoo_name, **kwargs)

    autodist = ad.AutoDist(
        resource_spec_file=args.resource_spec or None,
        strategy_builder=ad.strategy.from_name(args.strategy),
    )
    n_dev = int(np.prod(autodist.mesh.devices.shape))
    batch_size = args.batch_size or 8 * n_dev

    params = model.init(jax.random.PRNGKey(0))
    example = model.example_batch(batch_size)
    step = autodist.build(
        model.loss_fn, params, example, sparse_names=model.sparse_names,
        grad_accum_steps=args.accum,
        compute_dtype=args.compute_dtype or None,
        # 'true' -> True, false-likes -> off, anything else is a policy
        # name that build() validates against jax.checkpoint_policies.
        remat=(True if args.remat == "true"
               else False if args.remat in ("", "false", "off")
               else args.remat),
    )
    state = step.init(params)

    # Synthetic epoch streamed through the native loader (batch dict only —
    # tuple-structured batches fall back to repeating the example batch).
    # --pin skips the loader entirely: one batch lives in HBM and the host
    # stays idle during the timed windows.
    # Loaders take the LOCAL batch: each process feeds its
    # global/process_count slice and the plan assembles the global batch
    # (the remapper feed contract). Single-process: local == global.
    n_proc = jax.process_count()
    if batch_size % n_proc:
        raise SystemExit(
            f"--batch-size {batch_size} must divide the {n_proc}-process fleet")
    local_bs = batch_size // n_proc
    if args.pin:
        pinned = jax.device_put(example, step.plan.batch_shardings(example))
        jax.block_until_ready(pinned)
        next_batch = lambda: pinned  # noqa: E731
    elif args.data_dir:
        # Larger-than-RAM path: mmap'd shards gathered by the native
        # engine; process_slice gives each host a disjoint row range of
        # the shared dataset.
        loader = iter(DataLoader.from_files(
            args.data_dir, batch_size=local_bs, epochs=-1, plan=step.plan,
            shuffle=False, process_slice=True,
        ))
        next_batch = lambda: next(loader)  # noqa: E731
    elif isinstance(example, dict):
        data = {
            k: np.tile(np.asarray(v), (4,) + (1,) * (np.asarray(v).ndim - 1))
            for k, v in example.items()
        }
        loader = iter(DataLoader(
            data, batch_size=local_bs, epochs=-1, plan=step.plan, shuffle=False
        ))
        next_batch = lambda: next(loader)  # noqa: E731
    else:
        next_batch = lambda: example  # noqa: E731

    items_per_step = batch_size
    if item_kind == "tokens":
        tok = example["tokens"] if isinstance(example, dict) and "tokens" in example else None
        if tok is not None:
            items_per_step = int(np.prod(np.asarray(tok).shape))

    # Steps run in device-side windows (``step.run`` = one dispatch per
    # window, the hot loop stays on device). Window 1 doubles as
    # warmup/compile.
    window = max(1, min(args.steps // 2, args.window))
    # Warmup: at least one window (covers compile) plus whatever --warmup
    # asks for, rounded up to whole windows; timed windows fill the rest of
    # --steps, rounded DOWN so the run never overshoots the requested count.
    # >= 2 windows (1 warmup + 1 timed); the floor only overshoots --steps
    # in the degenerate --steps 1 case.
    total_windows = max(2, args.steps // window)
    warm_windows = min(max(1, -(-args.warmup // window)), total_windows - 1)
    timed_windows = total_windows - warm_windows
    with obs_spans.span("bench.warmup", window=window):
        state, metrics = step.run(state, next_batch(), window)
        first_loss = float(metrics["loss"][0])
    # The timed loop fetches loss[-1]; fetch it here too so its getitem
    # executable compiles during warmup, not inside the first timed window.
    float(metrics["loss"][-1])
    for _ in range(warm_windows - 1):
        state, metrics = step.run(state, next_batch(), window)
        float(metrics["loss"][-1])
    steps_per_lap = window * timed_windows if args.pin else window
    timer = StepTimer(items_per_step=items_per_step * steps_per_lap, warmup=0)
    pin_laps = 3 if args.pin else 0
    if args.pin:
        # Pinned batch: nothing to feed between windows, so every timed
        # window dispatches back-to-back (run() returns immediately; the
        # programs queue and pipeline on the device) and ONE trailing loss
        # fetch barriers each lap, so the device never idles on a
        # device->host round trip between windows. The lap repeats 3x and
        # the MEDIAN lap is reported: a single-sample lap would commit any
        # transient host hiccup straight into the published row (bench.py
        # takes the median of 3 trials for the same reason).
        for _ in range(pin_laps):
            with obs_spans.span("bench.lap", windows=timed_windows,
                                window=window), timer:
                for _ in range(timed_windows):
                    state, metrics = step.run(state, next_batch(), window)
                float(metrics["loss"][-1])  # single end barrier per lap
    else:
        for _ in range(timed_windows):
            # The feed upload stays outside the timed span: "fed" rows time
            # the window program, the loader is measured by data_feed.py.
            b = next_batch()
            with obs_spans.span("bench.window", window=window), timer:
                state, metrics = step.run(state, b, window)
                float(metrics["loss"][-1])  # host fetch of the last loss = barrier
    last_loss = float(metrics["loss"][-1])
    steps_executed = (warm_windows + timed_windows * max(1, pin_laps)) * window

    if args.trace:
        (_, _), trace_dir = step.trace_step(state, next_batch())
        print(f"trace -> {trace_dir}")
    if args.profile_dir:
        # One more window under the profiler, into the user's dir (the
        # window program is warm by now, so the capture sees steady-state
        # execution, not a compile). The sidecar makes the trace
        # self-describing for `profile_ops.py --parse` / obs attrib.
        from autodist_tpu.obs import attrib as obs_attrib
        from autodist_tpu.utils import tracing

        with tracing.trace("train", trace_dir=args.profile_dir) as td:
            state, metrics = step.run(state, next_batch(), window)
            float(metrics["loss"][-1])
        obs_attrib.write_capture_meta(td, model=args.model,
                                      batch=batch_size, window=window)
        print(f"profile trace -> {td} (parse: python "
              f"examples/benchmark/profile_ops.py --parse {td})")
    if args.trace_out:
        # Host-side span timeline (chrome-trace JSON): warmup/timed windows
        # plus any library spans recorded during the run.
        print(f"trace-out -> {obs_spans.export(args.trace_out)}")

    s = timer.summary()
    if args.pin:
        # Median lap, not mean: p50_s over the 3 laps (warmup=0, so every
        # lap is measured). items_per_sec/mean_step_s recompute from it.
        lap_s = s["p50_s"]
        s["items_per_sec"] = items_per_step * steps_per_lap / lap_s
        s["mean_s"] = lap_s
    result = {
        "metric": f"{args.model}_{item_kind}_per_sec"
                  + ("_pinned" if args.pin else ""),
        "value": round(s.get("items_per_sec", 0.0), 2),
        "unit": f"{item_kind}/s",
        "strategy": args.strategy,
        "global_batch": batch_size,
        "n_devices": n_dev,
        "mean_step_s": round(s.get("mean_s", float("nan")) / steps_per_lap, 5),
        "window": window,
        "steps_executed": steps_executed,
        # 6 decimals: slow-start workloads (big-vocab LM, NCF at ln2) move
        # in the 5th decimal over a short run and 4 would display as frozen.
        "first_loss_to_last": [round(first_loss, 6), round(last_loss, 6)],
    }
    # Record non-default build knobs so A/B runs are distinguishable in
    # the emitted line (the --pin suffix already marks the feed mode).
    if args.pin:
        result["pin_laps"] = pin_laps  # value = median lap of these
    if args.compute_dtype:
        result["compute_dtype"] = args.compute_dtype
    if args.remat not in ("", "false", "off"):
        result["remat"] = args.remat
    if args.accum > 1:
        result["grad_accum_steps"] = args.accum
    if model.flops_per_example:
        # flops_per_example is per EXAMPLE (per sequence for token models,
        # bench.py:305 convention) while items_per_sec counts tokens for
        # item_kind == "tokens" — convert back via tokens-per-example or the
        # achieved rate over-reports by seq_len.
        examples_per_sec = (s.get("items_per_sec", 0.0) * batch_size
                            / max(items_per_step, 1))
        result["model_tflops_per_sec"] = round(
            model.flops_per_example * examples_per_sec / 1e12, 2
        )
    # Black-box the result (no-op unless a flight recorder is active —
    # AUTODIST_FT_DIR / AUTODIST_FLIGHT_DIR): a later hang in the same
    # fleet still leaves this run's measured rate in the postmortem trail.
    obs_recorder.record_event("bench_result", critical=False, **result)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
