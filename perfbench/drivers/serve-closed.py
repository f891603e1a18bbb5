"""Driver ``serve-closed``: a closed loop of clients on the continuous
batcher (``AutoDist.build_inference`` -> ``ContinuousBatcher.submit``).

Each client sends its next request when its last one ends. Everything the
clients do happens in the batcher's own ``on_tick`` hook, on the scheduler
thread: one process, no client threads. The window opens and closes at a
tick's end, so tokens are counted exactly between two ticks, at emission,
whether or not their request finished.

The traffic file gives ``clients``, the lengths (see ``harness/traffic.py``),
``check_requests``, how many finished requests the reference follows, and,
where the cell reports the gap's tail, ``tail_percentile``.
The configuration file's ``serving`` group gives what a deployment states
(``n_slots``, ``max_len``); page size, pool size, prefill chunk and the
attention paths are the program's own choice. Everything that is the
model's (weights from the seed, the decode model handed to the program, the
vocabulary, the plain reference) is the configuration's family's
(``perfbench/families/<family>.py``).
"""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

from perfbench.harness import device, runtime, traffic

TOKENS_COUNTER = "serve_tokens_generated_total"
POOL_GAUGE = "serve_page_pool_utilization"
# ``serve_tpot_tail_s`` is the gap at the percentile the traffic file states
# (``tail_percentile``). A window's gaps fall into a few classes of tick (one
# that only decodes; one whose chunk rode behind the decode step; ones whose
# chunk could not, or that carry two), and a percentile that lies where a
# class ends jumps by the step between two of them from run to run: each
# cell states one that lies well inside a class (PERF.md section 6, PR 33).
# The ladder and the mean of the band from p90 to p99 are said in every run,
# so that whoever moves the classes' shares sees where their edges lie.
LADDER = (50, 90, 95, 98, 99, 99.5)


class Observer:
    """The closed-loop clients and every measurement, driven by ``on_tick``."""

    def __init__(self, gen, registry, engine):
        self.gen = gen
        self.registry = registry
        self.engine = engine
        self.batcher = None
        self.staggering = set(range(gen.clients)) if gen.mix.get("stagger") else set()
        self.live = {}             # client -> [request, tokens seen, last emission]
        self.warm = threading.Event()
        self.opened = threading.Event()
        self.closed = threading.Event()
        self.want_open = self.want_close = False
        self.is_open = self.was_open = False
        self.t_open = self.t_close = self.wall_open = self.wall_close = None
        self.tokens_open = self.tokens_close = None
        self.gaps, self.first = [], []       # seconds; (seconds, prompt tokens)
        self.ticks = []                      # (seconds, carried prefill, slots in use)
        self.per_second = {}
        self.finished, self.failed, self.sent = [], 0, 0
        self.pool_peak = 0.0
        self.errors = []

    # -- clients
    def start(self, batcher):
        self.batcher = batcher
        for c in range(self.gen.clients):
            self._send(c, stagger=c in self.staggering)
        if not self.staggering:
            self.warm.set()

    def _send(self, client, stagger=False):
        prompt, n_new = (self.gen.stagger_request(client) if stagger
                         else self.gen.next_request(client))
        req = self.batcher.submit(prompt, max_new_tokens=n_new)
        self.live[client] = [req, 0, None, n_new]
        if self.is_open:
            self.sent += 1

    # -- the hook
    def on_tick(self, dt):
        try:
            self._on_tick(dt)
        except Exception as e:  # the batcher would swallow it: keep it
            self.errors.append(repr(e))
            self.closed.set()
            self.warm.set()
            self.opened.set()

    def _on_tick(self, dt):
        now = time.monotonic()
        prefilled = self.engine.prefilling_slots > 0
        in_use = self.engine.active_slots
        for client, st in list(self.live.items()):
            req, seen, last, n_new = st
            n = len(req.tokens)
            if n > seen:
                if seen == 0:
                    prefilled = True
                    if self.is_open:
                        self.first.append((req.t_first_token - req.t_submit,
                                           len(req.prompt)))
                    last = req.t_first_token
                    seen = 1
                if n > seen:
                    if self.is_open:
                        self.gaps.append(now - last)
                        self.gaps.extend([0.0] * (n - seen - 1))
                    last = now
                if self.is_open:
                    sec = int(now - self.t_open)
                    self.per_second[sec] = self.per_second.get(sec, 0) + n - st[1]
                st[1], st[2] = n, last
            if req.done:
                in_use += 1          # held a slot during this tick
                del self.live[client]
                stagger = client in self.staggering
                self.staggering.discard(client)
                if self.is_open and not stagger:
                    ok = (req.state.value == "done" and len(req.tokens) == n_new)
                    self.failed += 0 if ok else 1
                    self.finished.append(
                        {"prompt": np.asarray(req.prompt), "tokens": list(req.tokens),
                         "max_new": n_new, "ok": ok,
                         "ttft_s": req.t_first_token - req.t_submit
                         if req.t_first_token else None})
                if not self.want_close:
                    self._send(client)
        if not self.staggering:
            self.warm.set()
        if self.is_open:
            self.ticks.append((dt, prefilled, min(in_use, self.engine.n_slots)))
            self.pool_peak = max(self.pool_peak,
                                 self.registry.gauge(POOL_GAUGE).value)
        if self.want_open and not self.was_open:
            self.is_open = self.was_open = True
            self.t_open, self.wall_open = now, time.time()
            self.tokens_open = self.registry.counter(TOKENS_COUNTER).value
            self.opened.set()
        elif self.want_close and self.is_open:
            self.is_open = False
            self.t_close, self.wall_close = now, time.time()
            self.tokens_close = self.registry.counter(TOKENS_COUNTER).value
            self.closed.set()


def build_engine(fam, model, params, ctx):
    """The program, entered as a deployment would: ``build_inference``
    with the family's decode model, what the configuration's ``serving``
    group states and nothing else."""
    from autodist_tpu.strategy import AllReduce

    autodist = runtime.make_autodist(AllReduce(), ctx["cell"].chips)
    serving = model["serving"]
    return autodist.build_inference(
        params, decode_model=fam.decode_model(model),
        n_slots=serving["n_slots"], max_len=serving["max_len"])


def check_served(fam, finished, model, seed, n_check, precisions=("float32",),
                 pad_to=None):
    """Widest gap by which a served token's reference logit lies below the
    reference's best, over a sample of the finished requests drawn from
    the seed, the longest always in it. With more ``precisions`` also the
    same gap for the token each lower precision puts first (the control)."""
    import jax.numpy as jnp

    params, vocab = fam.reference_params(model, seed), fam.vocab_size(model)
    order = np.random.default_rng(int(seed) % (2 ** 32)).permutation(len(finished))
    longest = max(range(len(finished)), key=lambda i: (
        len(finished[i]["prompt"]) + len(finished[i]["tokens"])))
    picks = [longest] + [int(i) for i in order if i != longest]
    picks = picks[:n_check]
    # One padded length per cell, so that the reference is one program.
    pad = max(len(finished[i]["prompt"]) + len(finished[i]["tokens"]) for i in picks)
    pad = -(-max(pad, pad_to or 0) // 64) * 64
    worst = {p: 0.0 for p in precisions}
    n_tokens, bad_vocab = 0, 0
    for i in picks:
        r = finished[i]
        served = np.asarray(r["tokens"], np.int32)
        bad_vocab += int(((served < 0) | (served >= vocab)).sum())
        served = np.clip(served, 0, vocab - 1)
        seq = np.zeros(pad, np.int32)
        n_p, n_t = len(r["prompt"]), len(served)
        seq[:n_p] = r["prompt"]
        seq[n_p:n_p + n_t] = served
        pos = np.arange(n_p - 1, n_p + n_t - 1)
        best, _, table = fam.next_token_logits(
            params, jnp.asarray(seq), model, "float32")
        picked = np.zeros(pad, np.int32)
        picked[pos] = served
        gap = np.asarray(fam.logit_gaps(best, table, jnp.asarray(picked)))[pos]
        worst["float32"] = max(worst["float32"], float(gap.max()))
        n_tokens += n_t
        for prec in precisions:
            if prec == "float32":
                continue
            _, top, _ = fam.next_token_logits(
                params, jnp.asarray(seq), model, prec)
            gap = np.asarray(fam.logit_gaps(best, table, top))[pos]
            worst[prec] = max(worst[prec], float(gap.max()))
    return worst, n_tokens, bad_vocab, len(picks)


def run(ctx):
    import jax
    from autodist_tpu import metrics as M
    from autodist_tpu.api import AutoDist
    from autodist_tpu.obs import spans as obs_spans
    from autodist_tpu.serve.batcher import ContinuousBatcher

    cell, say, hooks = ctx["cell"], ctx["say"], ctx["hooks"]
    fam, model, mix, seed = cell.family(), cell.model, cell.traffic, ctx["seed"]
    gen = traffic.ServeTraffic(mix, fam.vocab_size(model), seed)

    params = fam.make_params(model, seed)
    t_build = time.perf_counter()
    engine = build_engine(fam, model, params, ctx)
    plan_build_s = time.perf_counter() - t_build
    del params
    if "engine" in hooks:
        hooks["engine"](engine)
    registry = M.MetricsRegistry()
    obs = Observer(gen, registry, engine)
    obs_spans.get_tracer().set_capacity(1 << 16)
    batcher = ContinuousBatcher(engine, registry=registry, on_tick=obs.on_tick)
    batcher.start()
    session = runtime.ProfilerSession(ctx["root"]) if ctx["trace"] else None
    try:
        obs.start(batcher)
        if not obs.warm.wait(timeout=900):
            raise RuntimeError("warm-up did not finish: " + "; ".join(obs.errors))
        compile_setup = ctx["meter"].snapshot()
        if session:
            session.start()
        obs.want_open = True
        if not obs.opened.wait(timeout=120):
            raise RuntimeError("the window never opened")
        setup_s = obs.wall_open - ctx["t0"]
        deadline = obs.t_open + ctx["seconds"]
        if session:
            time.sleep(max(0.0, min(ctx["seconds"], float(mix.get("trace_seconds", 6.0)))))
            session.stop()
        time.sleep(max(0.0, deadline - time.monotonic()))
        obs.want_close = True
        if not obs.closed.wait(timeout=120):
            raise RuntimeError("the window never closed")
    finally:
        batcher.stop(drain=False, timeout_s=60.0)
    if obs.errors:
        raise RuntimeError("observer failed: " + "; ".join(obs.errors))
    compile_window = ctx["meter"].snapshot()
    window = obs.t_close - obs.t_open
    spans = [s for s in obs_spans.get_tracer().spans()
             if obs.wall_open <= s.t_start_s <= obs.wall_close]
    peak = device.memory_peak_bytes(ctx["devices"])
    n_slots = engine.n_slots
    engine_facts = {"page_len": engine.page_len, "prefill_chunk": engine.prefill_chunk,
                    "n_pages": engine.pool.usable_pages, "n_slots": n_slots,
                    "max_len": engine.max_len}
    trace = session.read() if session else None

    # Free the program before the reference runs: its weights, its pool.
    del batcher, engine, obs.engine, obs.batcher
    AutoDist.reset_default()
    gc.collect()

    tokens = obs.tokens_close - obs.tokens_open
    say(f"window {window:.3f} s: {tokens:.0f} tokens, {tokens / window:.2f} tokens/s; "
        f"requests sent {obs.sent} finished {len(obs.finished)} failed {obs.failed}; "
        f"ticks {len(obs.ticks)}; engine {engine_facts}")
    say("tokens per second of the window: " + " ".join(
        str(obs.per_second.get(s, 0)) for s in range(int(window) + 1)))

    numbers = {"requests_failed": float(obs.failed), "tokens_missing": 0.0,
               "out_of_vocab": 0.0, "logit_gap": None}
    finished = [r for r in obs.finished if r["tokens"]]
    numbers["tokens_missing"] = float(sum(
        abs(r["max_new"] - len(r["tokens"])) for r in obs.finished))
    if finished:
        precisions = ("float32",) + tuple(hooks.get("control_precisions", ()))
        t_ref = time.perf_counter()
        worst, n_tok, bad, n_req = check_served(
            fam, finished, model, seed, int(mix.get("check_requests", 8)), precisions,
            pad_to=gen.longest_timeline())
        numbers["logit_gap"] = worst["float32"]
        numbers["out_of_vocab"] = float(bad)
        say(f"reference followed {n_req} requests, {n_tok} served tokens in "
            f"{time.perf_counter() - t_ref:.1f} s; widest gap {worst}")
        for prec, value in worst.items():
            if prec != "float32":
                numbers["control." + prec + ".logit_gap"] = value

    gaps, first = obs.gaps, obs.first
    if gaps:
        say(f"gaps {len(gaps)}: " + " ".join(
            f"p{q:g} {runtime.percentile(gaps, q):.6f}" for q in LADDER)
            + f"; mean of p90-p99 {runtime.band_mean(gaps):.6f} s")
    end_to_end = {
        "setup_s": setup_s,
        "serve_tok_s": tokens / window,
        "serve_tpot_tail_s": runtime.percentile(gaps, mix["tail_percentile"])
        if gaps and "tail_percentile" in mix else None,
        "serve_ttft_per_ktok_s": (1000.0 * sum(t for t, _ in first)
                                  / sum(n for _, n in first)) if first else None,
    }
    return {
        "end_to_end": end_to_end, "numbers": numbers,
        "attempted": len(obs.finished), "failed": obs.failed,
        "memory_peak_bytes": peak, "trace": trace,
        "trace_window_s": session.window_s if session else 0.0,
        "data": {
            "kind": "serve", "window_s": window, "tokens": tokens,
            "gaps": gaps, "first": first, "ticks": obs.ticks,
            "finished": [(len(r["prompt"]), len(r["tokens"])) for r in obs.finished],
            "pool_peak": obs.pool_peak, "spans": spans, "engine": engine_facts,
            "compile_s": compile_setup[0], "plan_build_s": plan_build_s,
            "compiles_in_window": compile_window[1] - compile_setup[1],
        },
    }
