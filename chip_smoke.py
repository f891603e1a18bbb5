#!/usr/bin/env python3
"""One command that proves today's code trains and serves on the TPU.

    python chip_smoke.py                  # the chip check
    python chip_smoke.py --rehearse-cpu   # tiny widths on whatever backend
                                          # jax has; can never be a chip pass

It drives the zoo ``transformer`` at its registered defaults (12 layers,
d_model 768, 12 heads, d_ff 3072, vocab 32,000, seq 512, bf16) through the
entry points a user calls, one process on the chip at a time:

1. a **device child** (this file, ``--leg device``) states the backend, the
   device and the versions, refuses anything that is not a TPU the peak
   table knows, then runs in one process
   - *train*: ``AutoDist(AllReduce).build`` -> ``step.init`` -> two per-step
     calls fed by the ``DataLoader`` -> two ``step.run(state, batch, 10)``
     windows, 8 sequences per chip; loss finite, started near ln(vocab),
     ended lower on the fixed batch;
   - *kernels*: every pallas entry point lowered at product shapes must
     carry a ``tpu_custom_call`` and agree with its float32 reference;
   - *paged-vs-forward*: chunked paged prefill + paged decode logits against
     the training ``forward`` on the same tokens (full width, depth cut);
   - with more than one device: shardings span every device, every device
     reports memory in use, the compiled step carries the plan's
     collective, the mesh is ``create_device_mesh``'s arrangement, and one
     step of a ``{data: n/2, model: 2}`` TensorParallel plan runs;
2. the **server** (``python -m autodist_tpu.serve --model transformer``)
   starts once the device child has exited; this process — which never
   imports jax while a child may need the chip — is its HTTP client:
   ``/healthz`` turns 200, concurrent ``POST /generate`` of mixed prompt
   lengths return the right token counts, a repeated greedy prompt repeats
   its stream, ``/metrics`` parses, SIGTERM exits 0.

Any failed check fails the run. The last stdout line of a chip pass is
``{"ok": true, "device": {...}}``; with no TPU the device child exits
non-zero before any leg and no result is printed. Compile seconds, step and
request times, peak HBM and the loader engine are printed as information.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
from typing import NoReturn

HERE = os.path.dirname(os.path.abspath(__file__))

# The whole run must end inside the chip check's 1200 s; every wait below is
# cut to what is left of this.
DEADLINE_S = 1140.0

FULL = {
    "model_args": {},                      # the registered defaults
    "vocab_size": 32000,                   # what those defaults register
    "flash_seqs": (1024, 2048, 4096),
    "paged_batch": 8,
    "paged_table_pages": (64, 128),
    "prompt_lens": (5, 9, 12, 16, 23, 40, 70, 100),
    "max_new": 32,
}
REHEARSAL = {
    "model_args": {"vocab_size": 512, "num_layers": 2, "d_model": 64,
                   "num_heads": 4, "d_ff": 128, "max_seq_len": 128},
    "vocab_size": 512,
    "flash_seqs": (128, 256),
    "paged_batch": 2,
    "paged_table_pages": (4, 8),
    "prompt_lens": (3, 5, 9, 12, 17, 20, 33, 40),
    "max_new": 8,
}
BATCH_PER_CHIP = 8
WINDOW = 10
PAGE_LEN = 16
PROBE_LAYERS = 2                           # depth cut for paged-vs-forward
# max |kernel - float32 reference| over max |reference|: bf16 inputs and a
# bf16 probability matrix in the forward bound it near 1e-2.
KERNEL_TOL = 3e-2
# max |paged-path logit - forward logit| at bf16 activations.
PAGED_LOGIT_TOL = 0.1


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def fail(msg: str, code: int = 1) -> NoReturn:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ============================================================ device child
class _CompileMeter:
    """Seconds jax spent compiling (or loading from the persistent cache)
    and how many cache entries were hit / written, from jax.monitoring."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.hits = 0
        self.writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration_secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def snapshot(self):
        return (self.compile_s, self.hits, self.writes)

    def since(self, snap):
        return {"compile_s": round(self.compile_s - snap[0], 2),
                "cache_hits": self.hits - snap[1],
                "cache_writes": self.writes - snap[2]}


def _peak_bytes():
    """Per-device ``peak_bytes_in_use`` (None where the backend has no
    memory stats, i.e. the CPU rehearsal)."""
    import jax

    out = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        out.append(None if not stats else int(stats["peak_bytes_in_use"]))
    return out


def _rel_err(got, ref) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(got.shape == ref.shape, f"shape {got.shape} != {ref.shape}")
    check(bool(np.isfinite(got).all()), "non-finite kernel output")
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-6))


def _assert_mosaic(fn, args, n_calls: int, on_tpu: bool, label: str) -> None:
    """The lowered program must carry the Mosaic custom call(s) — not the
    interpreter's expansion and not the jnp reference."""
    import jax

    if not on_tpu:
        return
    text = jax.jit(fn).lower(*args).as_text()
    got = text.count("tpu_custom_call")
    check(got == n_calls,
          f"{label}: lowered program carries {got} tpu_custom_call(s), "
          f"expected {n_calls}")


def _train_leg(meter, spec, builder, n_dev: int, on_tpu: bool, *,
               resource_spec=None, per_step: int, windows: int,
               wire: tuple, partitioned: bool = False, label: str) -> dict:
    import jax
    import numpy as np

    from autodist_tpu.api import AutoDist
    from autodist_tpu.analysis.inventory import assert_hlo_wire, compiled_hlo
    from autodist_tpu.data import DataLoader
    from autodist_tpu.model_item import OptimizerSpec

    snap = meter.snapshot()
    AutoDist.reset_default()
    autodist = AutoDist(strategy_builder=builder, resource_spec=resource_spec)
    params = spec.init(jax.random.PRNGKey(0))
    batch = spec.example_batch(BATCH_PER_CHIP * n_dev)
    step = autodist.build(
        spec.loss_fn, params, batch,
        optimizer=OptimizerSpec("adam", {"learning_rate": 3e-4}))
    state = step.init(params)
    del params

    # The fixed batch, fed the way a training script feeds it: one batch per
    # epoch through the loader (which reports the engine that ran).
    loader = DataLoader({k: np.asarray(v) for k, v in batch.items()},
                        batch_size=BATCH_PER_CHIP * n_dev, epochs=-1,
                        plan=step.plan, shuffle=False)
    feed = iter(loader)
    losses, step_s = [], []
    for _ in range(per_step):
        b = next(feed)
        t0 = time.perf_counter()
        state, metrics = step(state, b)
        losses.append(float(metrics["loss"]))       # host fetch = barrier
        step_s.append(time.perf_counter() - t0)
    pinned = jax.device_put(batch, step.plan.batch_shardings(batch))
    window_s = []
    for _ in range(windows):
        t0 = time.perf_counter()
        state, metrics = step.run(state, pinned, WINDOW)
        w = np.asarray(metrics["loss"])              # host fetch = barrier
        window_s.append(time.perf_counter() - t0)
        check(w.shape == (WINDOW,), f"{label}: window loss shape {w.shape}")
        losses.extend(float(x) for x in w)
    check(all(math.isfinite(x) for x in losses),
          f"{label}: non-finite loss in {losses}")
    vocab = spec.config.vocab_size
    check(abs(losses[0] - math.log(vocab)) < 1.0,
          f"{label}: first loss {losses[0]:.3f} is not near ln(vocab)="
          f"{math.log(vocab):.3f} for random weights")
    if len(losses) > 1:
        check(losses[-1] < losses[0],
              f"{label}: loss did not fall on a fixed batch: {losses}")

    out = {"leg": label, **meter.since(snap),
           "loss_first": round(losses[0], 4), "loss_last": round(losses[-1], 4),
           "steps": len(losses), "loader_engine": loader.engine,
           "step_s_after_first": [round(x, 4) for x in step_s[1:]],
           "window_s_after_first": [round(x, 4) for x in window_s[1:]],
           "first_call_s": {e["program"]: round(e["first_call_s"], 2)
                            for e in step.compile_log}}
    if n_dev > 1:
        mesh_shape = dict(zip(step.plan.mesh.axis_names,
                              step.plan.mesh.devices.shape))
        for name, tree in (("params", state.params),
                           ("optimizer state", state.opt_state),
                           ("batch", pinned)):
            for leaf in jax.tree.leaves(tree):
                check(len(leaf.sharding.device_set) == n_dev,
                      f"{label}: a {name} leaf of shape {leaf.shape} lives on "
                      f"{len(leaf.sharding.device_set)} of {n_dev} devices")
        rows = next(iter(jax.tree.leaves(pinned)))
        check(rows.addressable_shards[0].data.shape[0] * mesh_shape["data"]
              == rows.shape[0], f"{label}: batch not split over data")
        n_split = sum(leaf.addressable_shards[0].data.shape != leaf.shape
                      for leaf in jax.tree.leaves(state.params))
        check(n_split > 0 or not partitioned,
              f"{label}: no parameter is partitioned over the mesh")
        hlo = compiled_hlo(step, state, pinned)
        promised = {op for w in step.plan.promised_wire().values()
                    for op in w.require}
        assert_hlo_wire(hlo, present=tuple(sorted(promised | set(wire))),
                        label=label)
        if on_tpu:
            from jax.experimental import mesh_utils

            want = mesh_utils.create_device_mesh(
                list(step.plan.mesh.devices.shape), devices=jax.devices())
            check([d.id for d in step.plan.mesh.devices.flat]
                  == [d.id for d in want.flat],
                  f"{label}: mesh is not create_device_mesh's arrangement")
        out.update(mesh=mesh_shape, wire=sorted(promised | set(wire)),
                   partitioned_params=n_split)
    AutoDist.reset_default()
    return out


def _flash_leg(meter, cfg, seqs, on_tpu: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from autodist_tpu.ops.flash_attention import flash_attention, mha_reference

    snap = meter.snapshot()
    heads, dim = cfg.num_heads, cfg.head_dim
    errs = {}
    for seq in seqs:
        q, k, v = (jax.random.normal(key, (1, seq, heads, dim), jnp.bfloat16)
                   for key in jax.random.split(jax.random.PRNGKey(seq), 3))

        def fwd(q, k, v):
            return flash_attention(q, k, v, True)

        def loss(attn, q, k, v):
            # A non-uniform cotangent so dq/dk/dv exercise every block.
            w = jnp.cos(jnp.arange(dim, dtype=jnp.float32))
            return (attn(q, k, v).astype(jnp.float32) * w).sum()

        grad = jax.grad(lambda q, k, v: loss(fwd, q, k, v), argnums=(0, 1, 2))
        _assert_mosaic(fwd, (q, k, v), 1, on_tpu, f"flash fwd s={seq}")
        _assert_mosaic(grad, (q, k, v), 3, on_tpu, f"flash bwd s={seq}")
        out = jax.jit(fwd)(q, k, v)
        dq, dk, dv = jax.jit(grad)(q, k, v)
        q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda q, k, v: mha_reference(q, k, v, True))(
                q32, k32, v32)
            rq, rk, rv = jax.jit(jax.grad(
                lambda q, k, v: loss(
                    lambda *a: mha_reference(*a, True), q, k, v),
                argnums=(0, 1, 2)))(q32, k32, v32)
        errs[seq] = {"fwd": _rel_err(out, ref), "dq": _rel_err(dq, rq),
                     "dk": _rel_err(dk, rk), "dv": _rel_err(dv, rv)}
        worst = max(errs[seq].values())
        check(worst <= KERNEL_TOL,
              f"flash s={seq}: error {errs[seq]} above {KERNEL_TOL}")
    return {"leg": "flash_attention", **meter.since(snap),
            "shape": f"causal bf16 H{heads} D{dim}",
            "rel_err": {s: {k: round(v, 5) for k, v in e.items()}
                        for s, e in errs.items()}}


def _paged_leg(meter, cfg, batch: int, table_pages, on_tpu: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from autodist_tpu.ops import paged_attention as pa

    snap = meter.snapshot()
    heads, dim = cfg.num_heads, cfg.head_dim
    k1, chunk = 5, PAGE_LEN
    errs, decode_us = {}, {}
    for width in table_pages:
        rng = np.random.default_rng(width)
        n_pages = batch * width + 1
        kf, vf = (jnp.asarray(
            rng.standard_normal((n_pages, PAGE_LEN, heads, dim)), jnp.float32)
            for _ in range(2))
        # Distinct pages per row, out of pool order; page 0 is scratch.
        tables = jnp.asarray(
            1 + rng.permutation(n_pages - 1).reshape(batch, width), jnp.int32)
        timeline = width * PAGE_LEN
        pos = jnp.asarray(rng.integers(0, timeline - k1, batch), jnp.int32)
        pos = pos.at[0].set(0).at[-1].set(timeline - k1)
        rows_pos = pos[:, None] + jnp.arange(k1)[None, :]
        start = timeline - chunk
        cpos = start + jnp.arange(chunk)
        qd = jnp.asarray(rng.standard_normal((batch, heads, dim)), jnp.bfloat16)
        qv = jnp.asarray(
            rng.standard_normal((batch, k1, heads, dim)), jnp.bfloat16)
        qp = jnp.asarray(rng.standard_normal((chunk, heads, dim)), jnp.bfloat16)
        for quant in (False, True):
            if quant:
                kp, ks = pa.quantize_kv(kf)
                vp, vs = pa.quantize_kv(vf)
                kr, vr = pa.dequantize_kv(kp, ks), pa.dequantize_kv(vp, vs)
            else:
                kp, vp, ks, vs = (kf.astype(jnp.bfloat16),
                                  vf.astype(jnp.bfloat16), None, None)
                kr, vr = kp.astype(jnp.float32), vp.astype(jnp.float32)
            # Pages as the pool holds them: heads x head_dim on one axis.
            kp, vp, kr, vr = (x.reshape(n_pages, PAGE_LEN, heads * dim)
                              for x in (kp, vp, kr, vr))
            for name, fn, q, tab, qpos in (
                    ("decode", pa.paged_decode_attention, qd, tables, pos),
                    ("verify", pa.paged_verify_attention, qv, tables, rows_pos),
                    ("prefill", pa.paged_prefill_attention, qp, tables[0],
                     cpos)):
                def kernel(q, kp, vp, tab, qpos, *scales, fn=fn):
                    s = dict(zip(("k_scale", "v_scale"), scales))
                    return fn(q, kp, vp, tab, qpos, impl="kernel", **s)

                args = (q, kp, vp, tab, qpos) + ((ks, vs) if quant else ())
                label = (f"paged {name} P={width} "
                         f"{'int8' if quant else 'bf16'}")
                _assert_mosaic(kernel, args, 1, on_tpu, label)
                got = jax.jit(kernel)(*args)
                with jax.default_matmul_precision("highest"):
                    ref = jax.jit(lambda q, k, v, t, p, fn=fn: fn(
                        q, k, v, t, p, impl="gather"))(
                        q.astype(jnp.float32), kr, vr, tab, qpos)
                errs[label] = _rel_err(got, ref)
                check(errs[label] <= KERNEL_TOL,
                      f"{label}: error {errs[label]:.4f} above {KERNEL_TOL}")
                if name == "decode" and not quant:
                    # Information for ROADMAP S1: the decode call as the
                    # engine would issue it, kernel and gather.
                    for impl in ("kernel", "gather"):
                        call = jax.jit(lambda q, k, v, t, p, impl=impl: fn(
                            q, k, v, t, p, impl=impl))
                        jax.block_until_ready(call(*args))
                        t0 = time.perf_counter()
                        for _ in range(20):
                            out = call(*args)
                        jax.block_until_ready(out)
                        decode_us[f"P={width} {impl}"] = round(
                            (time.perf_counter() - t0) / 20 * 1e6, 1)
    return {"leg": "paged_attention", **meter.since(snap),
            "shape": f"B{batch} page_len{PAGE_LEN} H{heads} D{dim}",
            "rel_err": {k: round(v, 5) for k, v in errs.items()},
            "decode_call_us": decode_us}


def _paged_vs_forward_leg(meter, spec) -> dict:
    """The serving programs' math against the training forward: a prompt
    prefilled chunk by chunk through the page table, then teacher-forced
    paged decode steps, must give the logits ``forward`` gives for the same
    tokens. Full width; depth cut to ``PROBE_LAYERS``."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from autodist_tpu.models import transformer as T

    snap = meter.snapshot()
    cfg = dataclasses.replace(spec.config, num_layers=PROBE_LAYERS)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    n_prompt, n_decode, chunk = 40, 4, PAGE_LEN
    total = n_prompt + n_decode
    tokens = rng.integers(1, cfg.vocab_size, total).astype(np.int32)
    width = -(-total // PAGE_LEN) + 1
    table = jnp.asarray(np.arange(1, width + 1)[::-1].copy(), jnp.int32)
    cache = T.init_paged_kv_cache(cfg, width + 1, PAGE_LEN)

    prefill = jax.jit(lambda p, t, s, n, c, tab: T.forward_paged_prefill_chunk(
        p, t, s, n, c, tab, cfg), donate_argnums=(4,))
    decode = jax.jit(lambda p, t, pos, c, tabs: T.forward_paged_decode_step(
        p, t, pos, c, tabs, cfg, return_logits=True), donate_argnums=(3,))
    padded = np.zeros(-(-n_prompt // chunk) * chunk, np.int32)
    padded[:n_prompt] = tokens[:n_prompt]
    for start in range(0, len(padded), chunk):
        _, cache = prefill(params, jnp.asarray(padded[None, start:start + chunk]),
                           start, n_prompt, cache, table)
    full = np.asarray(jax.jit(lambda p, t: T.forward(p, t, cfg))(
        params, jnp.asarray(tokens[None, :])))[0]
    drift = 0.0
    for i in range(n_decode):
        pos = n_prompt + i
        _, logits, cache = decode(
            params, jnp.asarray(tokens[pos:pos + 1]),
            jnp.asarray([pos], jnp.int32), cache, table[None, :])
        logits = np.asarray(logits)[0]
        check(bool(np.isfinite(logits).all()), "non-finite paged logits")
        drift = max(drift, float(np.abs(logits - full[pos]).max()))
    check(drift <= PAGED_LOGIT_TOL,
          f"paged decode logits drift {drift:.4f} from forward "
          f"(bound {PAGED_LOGIT_TOL})")
    return {"leg": "paged_vs_forward", **meter.since(snap),
            "layers": PROBE_LAYERS, "max_abs_logit_drift": round(drift, 5)}


def leg_device(rehearsal: bool, out_path: str) -> None:
    import importlib.metadata as md

    import jax

    backend = jax.default_backend()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    versions = {p: md.version(p) for p in ("jax", "jaxlib", "libtpu")}
    say(f"backend={backend} device_kind={dev.device_kind!r} "
        f"device_count={device['count']} "
        + " ".join(f"{k}={v}" for k, v in versions.items()))
    on_tpu = backend == "tpu"
    if not rehearsal:
        if not on_tpu:
            fail(f"jax backend is {backend!r}, not 'tpu'; no leg was run", 3)
        from autodist_tpu.obs.profiler import peak_flops_for_kind

        try:
            peak_flops_for_kind(dev.device_kind)
        except ValueError as e:
            fail(f"{e}; no leg was run", 3)

    import autodist_tpu.strategy as S
    from autodist_tpu.models import get_model
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.utils.compile_cache import enable_compile_cache

    sizes = REHEARSAL if rehearsal else FULL
    cache_dir = enable_compile_cache()
    n_before = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    meter = _CompileMeter()
    n_dev = device["count"]
    spec = get_model("transformer", **sizes["model_args"])
    cfg = spec.config
    check(cfg.vocab_size == sizes["vocab_size"],
          f"the HTTP client draws prompts from {sizes['vocab_size']} ids but "
          f"the model registers {cfg.vocab_size}")
    say(f"model transformer L{cfg.num_layers} d{cfg.d_model} H{cfg.num_heads} "
        f"ff{cfg.d_ff} V{cfg.vocab_size} seq{cfg.max_seq_len}; "
        f"compile cache {cache_dir} ({n_before} entries)")

    legs = []

    def run(leg_fn, *a, **kw):
        t0 = time.perf_counter()
        rec = leg_fn(*a, **kw)
        rec["wall_s"] = round(time.perf_counter() - t0, 1)
        rec["peak_bytes_in_use"] = _peak_bytes()
        legs.append(rec)
        say(json.dumps(rec))

    run(_train_leg, meter, spec, S.AllReduce(), n_dev, on_tpu,
        per_step=2, windows=2, wire=("all-reduce",) if n_dev > 1 else (),
        label="train_allreduce")
    run(_flash_leg, meter, cfg, sizes["flash_seqs"], on_tpu)
    run(_paged_leg, meter, cfg, sizes["paged_batch"],
        sizes["paged_table_pages"], on_tpu)
    run(_paged_vs_forward_leg, meter, spec)
    if n_dev > 1:
        check(n_dev % 2 == 0, f"mixed plan needs an even device count: {n_dev}")
        mixed = ResourceSpec(resource_dict={
            "nodes": [{"address": "localhost", "chips": n_dev, "chief": True}],
            **({"tpu": {"accelerator": dev.device_kind}} if on_tpu else {}),
            "mesh": {"data": n_dev // 2, "model": 2}})
        run(_train_leg, meter, spec, S.TensorParallel(), n_dev, on_tpu,
            resource_spec=mixed, per_step=1, windows=0,
            wire=("all-reduce",), partitioned=True,
            label="train_tensor_parallel")
        peaks = _peak_bytes()
        if on_tpu:
            check(all(p for p in peaks),
                  f"a device reports no memory in use: {peaks}")
    n_after = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump({"device": device, "backend": backend, "versions": versions,
                   "legs": legs, "compile_cache": {
                       "dir": cache_dir, "entries_before": n_before,
                       "entries_after": n_after}}, f)


# ================================================================= server
def _http(method: str, url: str, body: dict = None, timeout: float = 30.0):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def _free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def leg_serve(sizes: dict, run_dir: str, deadline: float) -> dict:
    """Start the server, be its client, stop it. Returns the leg record and
    the ``/metrics`` text (parsed by the caller once no child is left)."""
    import random

    port = _free_port()
    base = f"http://127.0.0.1:{port}"
    cmd = [sys.executable, "-m", "autodist_tpu.serve", "--model", "transformer",
           "--port", str(port)]
    for k, v in sizes["model_args"].items():
        cmd += ["--model-arg", f"{k}={v}"]
    log_path = os.path.join(run_dir, "server.log")
    vocab = sizes["vocab_size"]
    rnd = random.Random(0)
    prompts = [[rnd.randrange(1, vocab) for _ in range(n)]
               for n in sizes["prompt_lens"]]
    max_new = sizes["max_new"]
    rec = {"leg": "serve_http"}
    t_start = time.perf_counter()
    with open(log_path, "w", encoding="utf-8") as log:
        server = subprocess.Popen(cmd, cwd=HERE, stdout=log, stderr=log)
    try:
        # The listener binds once the engine is built (params placed, page
        # pool allocated); until then the connection is refused.
        while True:
            check(server.poll() is None,
                  f"server exited rc={server.returncode} before /healthz")
            check(time.monotonic() < deadline, "server not healthy in time")
            try:
                status, body = _http("GET", f"{base}/healthz", timeout=5.0)
                if status == 200 and json.loads(body)["ok"]:
                    break
            except (urllib.error.URLError, OSError):
                pass
            time.sleep(0.5)
        rec["healthz_200_after_s"] = round(time.perf_counter() - t_start, 1)

        def generate(prompt):
            t0 = time.perf_counter()
            status, body = _http(
                "POST", f"{base}/generate",
                {"tokens": prompt, "max_new_tokens": max_new},
                timeout=max(5.0, deadline - time.monotonic()))
            return status, body, time.perf_counter() - t0

        def generate_all():
            """Every prompt at once, one client thread each."""
            results = [None] * len(prompts)

            def client(i):
                results[i] = generate(prompts[i])

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(prompts))]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join(max(1.0, deadline - time.monotonic()))
                check(not t.is_alive(), "a /generate request did not return")
            return results, round(time.perf_counter() - t0, 2)

        # The first batch pays the two serving programs' compiles.
        results, rec["first_batch_s"] = generate_all()
        streams = []
        for i, (status, body, _) in enumerate(results):
            check(status == 200, f"request {i}: HTTP {status}: {body[:200]}")
            doc = json.loads(body)
            check(doc["state"] == "done", f"request {i}: state {doc['state']}")
            check(len(doc["tokens"]) == max_new,
                  f"request {i}: {len(doc['tokens'])} tokens, not {max_new}")
            check(all(isinstance(t, int) and 0 <= t < vocab
                      for t in doc["tokens"]),
                  f"request {i}: token outside the vocabulary")
            streams.append(doc["tokens"])
        # The same greedy prompts again, compiled programs warm: request
        # times without compile, and each stream must repeat itself.
        results, rec["warm_batch_s"] = generate_all()
        rec["warm_request_s"] = [round(r[2], 3) for r in results]
        for i, (status, body, _) in enumerate(results):
            check(status == 200, f"repeat {i}: HTTP {status}: {body[:200]}")
            check(json.loads(body)["tokens"] == streams[i],
                  f"request {i}: the same greedy prompt gave another stream")
        # ...and one of them alone, against its stream from the full batch.
        status, body, dt = generate(prompts[-1])
        check(status == 200 and json.loads(body)["tokens"] == streams[-1],
              "a prompt served alone gave another stream than in a batch")
        rec["solo_request_s"] = round(dt, 3)

        status, metrics_text = _http("GET", f"{base}/metrics")
        check(status == 200, f"/metrics: HTTP {status}")
        status, body = _http("GET", f"{base}/healthz")
        check(status == 200, f"/healthz after load: HTTP {status}")
        rec["healthz"] = json.loads(body)

        server.send_signal(signal.SIGTERM)
        try:
            rc = server.wait(timeout=60)
        except subprocess.TimeoutExpired:
            raise AssertionError("server still running 60 s after SIGTERM")
        check(rc == 0, f"server exited rc={rc} on SIGTERM")
        rec["sigterm_rc"] = rc
    except BaseException:
        with open(log_path, encoding="utf-8", errors="replace") as f:
            sys.stderr.write("--- server log (tail) ---\n" + f.read()[-4000:])
        raise
    finally:
        if server.poll() is None:
            server.kill()
            server.wait()
    rec["n_requests"] = 2 * len(prompts) + 1
    rec["prompt_lens"] = list(sizes["prompt_lens"])
    rec["max_new"] = max_new
    rec["wall_s"] = round(time.perf_counter() - t_start, 1)
    return rec, metrics_text


def check_metrics(metrics_text: str, n_requests: int) -> dict:
    """Parse the exposition with the repo's own scraper-grade parser. Called
    only after every child has exited: it imports the package (and so jax),
    which this process must not do while a child may need the chip."""
    sys.path.insert(0, HERE)
    from autodist_tpu.obs.exporter import parse_openmetrics

    samples = parse_openmetrics(metrics_text)
    names = {name for name, _ in samples}
    done = samples.get(("serve_requests_completed_total", ""))
    check(done is not None and done >= n_requests,
          f"/metrics: serve_requests_completed_total={done}, expected "
          f">= {n_requests}; series: {sorted(names)[:40]}")
    return {"samples": len(samples), "requests_completed": done}


# ================================================================== parent
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny widths on whatever backend jax has; the "
                         "summary says it was a rehearsal and is never a "
                         "chip pass")
    ap.add_argument("--report", default="",
                    help="also write the full report as JSON to this path")
    ap.add_argument("--leg", choices=("device",), help=argparse.SUPPRESS)
    ap.add_argument("--out", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.leg == "device":
        sys.path.insert(0, HERE)
        leg_device(args.rehearse_cpu, args.out)
        return 0

    deadline = time.monotonic() + DEADLINE_S
    sizes = REHEARSAL if args.rehearse_cpu else FULL
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as run_dir:
        out_path = os.path.join(run_dir, "device.json")
        cmd = [sys.executable, os.path.abspath(__file__), "--leg", "device",
               "--out", out_path]
        if args.rehearse_cpu:
            cmd.append("--rehearse-cpu")
        try:
            rc = subprocess.run(
                cmd, cwd=HERE, timeout=deadline - time.monotonic()).returncode
        except subprocess.TimeoutExpired:
            fail("the device legs did not finish in time")
        if rc != 0:
            fail(f"the device legs exited rc={rc}", rc)
        with open(out_path, encoding="utf-8") as f:
            report = json.load(f)
        # The device child has exited: the chip is free for the server.
        try:
            serve_rec, metrics_text = leg_serve(sizes, run_dir, deadline)
            serve_rec["metrics"] = check_metrics(
                metrics_text, serve_rec["n_requests"])
        except AssertionError as e:
            fail(f"serve leg: {e}")
        say(json.dumps(serve_rec))
        report["legs"].append(serve_rec)

    report["rehearsal"] = bool(args.rehearse_cpu)
    report["compile_s_total"] = round(
        sum(leg.get("compile_s", 0.0) for leg in report["legs"]), 2)
    say(f"compile seconds (device legs) {report['compile_s_total']}, cache "
        f"entries {report['compile_cache']['entries_before']} -> "
        f"{report['compile_cache']['entries_after']}")
    if args.report:
        os.makedirs(os.path.dirname(os.path.abspath(args.report)),
                    exist_ok=True)
        with open(args.report, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=1)
    if args.rehearse_cpu:
        # No "ok" key: a rehearsal is never readable as a chip pass.
        print(json.dumps({"rehearsal": True, "rehearsal_legs_passed": True,
                          "device": report["device"]}), flush=True)
        return 0
    print(json.dumps({"ok": True, "device": report["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
