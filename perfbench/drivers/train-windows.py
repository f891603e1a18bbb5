"""Driver ``train-windows``: the compiled train step fed by the loader.

``AutoDist(resource_spec, strategy_builder).build(loss_fn, params, ...)``
gives the step; every window is one call of
``DistributedTrainStep.run(state, window, 1, stacked=True)`` on the next
batch from ``data/loader.py``, dispatched back to back with two calls in
flight, the time ending at a host fetch of the last loss. A window is one
step, so that the state after the first step can be read: set-up drives
the same step object through its first ``check_steps`` windows (which also
compile it) and hands it on to the measured window.

The traffic file is the job: ``strategy`` (name and arguments of a
builder), ``optimizer``, ``batch_per_chip``, ``seq_len``, ``remat``
(false, or "block" for the model's per-block checkpoint), ``rows``.
Everything that is the model's (weights from the seed and their shardings,
the loss handed to the program, the vocabulary, the reference that follows
Adam) is the configuration's family's (``perfbench/families/<family>.py``).
"""
from __future__ import annotations

import gc
import time
from collections import deque

import numpy as np

from perfbench.harness import compare, device, runtime, traffic


def build_step(fam, model, mix, params, rows, ctx):
    from autodist_tpu import strategy as S
    from autodist_tpu.model_item import OptimizerSpec

    chips = ctx["cell"].chips
    builder = getattr(S, mix["strategy"]["name"])(**mix["strategy"].get("kwargs", {}))
    autodist = runtime.make_autodist(builder, chips)
    loss_fn = ctx["hooks"].get("loss_fn", lambda f: f)(fam.loss_fn(model, mix))
    batch = mix["batch_per_chip"] * chips
    opt = mix["optimizer"]
    step = autodist.build(
        loss_fn, params, {"tokens": rows[:batch]},
        optimizer=OptimizerSpec(opt["name"], {"learning_rate": opt["learning_rate"]}))
    return step, batch


def _adam_mu(opt_state):
    import jax

    found = [s for s in jax.tree.leaves(opt_state, is_leaf=lambda x: hasattr(x, "mu"))
             if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError("expected one Adam state holding `mu` in the "
                           f"optimizer state, found {len(found)}")
    return found[0].mu


def reference_numbers(fam, model, mix, seed, rows, batch, n_steps,
                      precision="float32", rows_used=slice(None), shardings=None):
    params = fam.make_params(model, seed, shardings)
    if rows_used == "half":
        rows_used = slice(0, batch // 2)
    batches = [rows[i * batch:(i + 1) * batch] for i in range(n_steps)]
    opt = mix["optimizer"]
    losses, grads, change = fam.adam_reference(
        params, batches, model, learning_rate=opt["learning_rate"],
        block_rows=int(mix.get("reference_block_rows", 2)),
        precision=precision, rows_used=rows_used, shardings=shardings)
    return {"losses": losses, "grad_norms": grads, "change_norms": change}


def run(ctx):
    import jax
    import jax.numpy as jnp
    from autodist_tpu.api import AutoDist
    from autodist_tpu.data import DataLoader

    cell, say, hooks = ctx["cell"], ctx["say"], ctx["hooks"]
    fam, model, mix, seed, chips = (cell.family(), cell.model, cell.traffic,
                                    ctx["seed"], cell.chips)
    rows = traffic.train_rows(mix, fam.vocab_size(model), seed)
    n_check = int(mix.get("check_steps", 3))
    b1 = 0.9

    shardings = fam.row_shardings(model, ctx["devices"])
    params = fam.make_params(model, seed, shardings)
    t_build = time.perf_counter()
    step, batch = build_step(fam, model, mix, params, rows, ctx)
    plan_build_s = time.perf_counter() - t_build
    state = step.init(params)
    del params
    step = hooks.get("step", lambda s: s)(step)
    loader = DataLoader({"tokens": rows}, batch_size=batch, shuffle=False,
                        epochs=-1, drop_remainder=True)
    feed = iter(loader.host_batches())
    wait = [0.0]

    def next_window():
        t = time.perf_counter()
        b = next(feed)
        w = step.plan.window_from_local({"tokens": b["tokens"][None]})
        wait[0] += time.perf_counter() - t
        return w

    leaf_norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2))
                                    for x in jax.tree.leaves(t)])
    prog = {"losses": [], "grad_norms": None, "change_norms": None}
    for i in range(n_check):
        state, m = step.run(state, next_window(), 1, stacked=True)
        prog["losses"].append(float(np.asarray(m["loss"])[0]))
        if i == 0:
            mu = step.plan.unpad_params(_adam_mu(state.opt_state))
            prog["grad_norms"] = [float(x) / (1 - b1) for x in leaf_norms(mu)]
            del mu
    prog["change_norms"] = fam.change_norms(model, seed, step.logical_params(state))
    say(f"followed steps: losses {prog['losses']}")

    session = runtime.ProfilerSession(ctx["root"]) if ctx["trace"] else None
    trace_seconds = min(ctx["seconds"], float(mix.get("trace_seconds", 6.0)))
    compile_setup = ctx["meter"].snapshot()
    wait[0] = 0.0
    if session:
        session.start()
    pending, done_at, losses, paused = deque(), [], [], 0.0
    setup_s = time.time() - ctx["t0"]
    t0 = time.monotonic()
    n = 0
    while time.monotonic() - t0 < ctx["seconds"]:
        state, m = step.run(state, next_window(), 1, stacked=True)
        pending.append(m["loss"])
        n += 1
        if len(pending) > 2:
            losses.append(float(np.asarray(pending.popleft())[0]))
            done_at.append(time.monotonic())
        if session and session.t_stop is None and time.monotonic() - t0 >= trace_seconds:
            # Drain, stop the profiler, and keep the seconds that takes out
            # of the measured time: the traced run's rate is of its work.
            while pending:
                losses.append(float(np.asarray(pending.popleft())[0]))
                done_at.append(time.monotonic())
            t_pause = time.monotonic()
            session.stop()
            paused = time.monotonic() - t_pause
            done_at.append(time.monotonic())
    while pending:
        losses.append(float(np.asarray(pending.popleft())[0]))
        done_at.append(time.monotonic())
    elapsed = time.monotonic() - t0 - paused
    if session and session.t_stop is None:
        session.stop()
    compile_window = ctx["meter"].snapshot()
    seq = int(mix["seq_len"])
    tokens = n * batch * seq
    peak = device.memory_peak_bytes(ctx["devices"])
    window_times = [b - a for a, b in zip([t0] + done_at, done_at)]
    if paused:
        window_times = [x for x in window_times if x < paused]
    say(f"window {elapsed:.3f} s: {n} steps of {batch} x {seq} tokens, "
        f"{tokens / elapsed / chips:.1f} tokens/s/chip; loss first {losses[0]:.4f} "
        f"last {losses[-1]:.4f}; loader {loader.engine}, waited {wait[0]:.3f} s")
    say("per-window ms: " + " ".join(f"{1e3 * x:.0f}" for x in window_times[:60]))
    trace = session.read() if session else None
    nonfinite = sum(not np.isfinite(x) for x in losses)

    del state, step, feed, loader
    AutoDist.reset_default()
    gc.collect()

    t_ref = time.perf_counter()
    ref = reference_numbers(fam, model, mix, seed, rows, batch, n_check,
                            shardings=shardings)
    numbers = compare.train_numbers(prog, ref)
    numbers["nonfinite"] = float(nonfinite)
    say(f"numbers against the reference: {numbers}")
    say(f"reference followed {n_check} steps in {time.perf_counter() - t_ref:.1f} s: "
        f"losses {ref['losses']}")
    for name, kw in hooks.get("controls", {}).items():
        jax.clear_caches()      # unload the last variant's programs first
        gc.collect()
        alt = reference_numbers(fam, model, mix, seed, rows, batch, n_check,
                                shardings=shardings, **kw)
        for k, v in compare.train_numbers(alt, ref).items():
            numbers[f"control.{name}.{k}"] = v
        say(f"control {name}: {compare.train_numbers(alt, ref)}")

    return {
        "end_to_end": {"setup_s": setup_s,
                       "train_tok_s_chip": tokens / elapsed / chips},
        "numbers": numbers, "attempted": n, "failed": nonfinite,
        "memory_peak_bytes": peak, "trace": trace,
        "trace_window_s": session.window_s if session else 0.0,
        "data": {
            "kind": "train", "window_s": elapsed, "tokens": tokens, "steps": n,
            "batch": batch, "seq": seq, "chips": chips,
            "window_times": window_times, "input_wait_s": wait[0],
            "compile_s": compile_setup[0], "plan_build_s": plan_build_s,
            "compiles_in_window": compile_window[1] - compile_setup[1],
        },
    }
