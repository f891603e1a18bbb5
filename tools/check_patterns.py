#!/usr/bin/env python3
"""Banned-pattern lint: codebase-specific rules ruff can't express.

Runs in CI's lint job (``.github/workflows/ci.yml``) before any test tier;
exits 1 listing ``file:line`` offenders. Rules:

1. **one spelling of shard_map** — ``jax.experimental.shard_map`` (the
   pre-0.6 home of the API, with the complementary ``auto=`` / ``check_rep``
   spelling) is imported nowhere: every call site goes through
   ``autodist_tpu.utils.compat.shard_map``, which calls ``jax.shard_map``.

2. **no wall-clock in timed bench windows** — ``time.time()`` is banned in
   ``bench.py`` and ``examples/benchmark/``: it steps with NTP/suspend, so
   a timed window that uses it can silently mis-measure. Timed windows use
   ``time.perf_counter()``; wall stamps for traces belong to ``obs/``.

3. **grad-sync collectives live in the bucketing helper** — emitting
   ``lax.psum(`` / ``lax.psum_scatter(`` in ``autodist_tpu/kernel/``
   outside ``kernel/bucketing.py`` (and the compressor wire,
   ``kernel/compressor.py``) is banned: the bucketed backward-overlap
   emission (dryrun family #12) is only sound if EVERY gradient collective
   goes through the one helper the bucket assignment, the cost model's
   overlap pricing and the analyzer's attribution share — a direct psum in
   the lowering would silently reintroduce the monolithic post-backward
   sync path this rule exists to keep dead.

4. **ONE flight-record writer** — touching the flight-record dir
   (``open(`` on a flight path, or the ``flight-`` segment-name prefix)
   anywhere in ``autodist_tpu/`` outside ``obs/recorder.py`` is banned:
   the crash-safety story (fsync cadence, segment rotation, torn-line
   tolerance) only holds because every writer AND reader goes through the
   recorder module (docs/observability.md § flight recorder). Components
   record via ``obs.recorder.record_event/record_step``; postmortems read
   via ``obs.recorder.read_records``.

5. **ONE xplane reader** — importing the xplane proto (``xplane_pb2``) or
   globbing ``xplane.pb`` anywhere in ``autodist_tpu/``, ``examples/``,
   ``tests/`` or ``bench.py`` outside ``obs/attrib.py`` is banned: the
   measured-wire attribution (docs/observability.md § attribution) is
   only trustworthy because the example CLI, the tests and the join all
   read a device profile through the one parser with the
   container/async-copy double-count guard. (``tools/`` is exempt: the
   golden-trace generator builds a synthetic xplane on purpose.)

6. **ONE retry/backoff home** — ``time.sleep(`` anywhere in
   ``autodist_tpu/`` outside ``utils/retry.py`` is banned: ad-hoc
   sleep-retry/poll loops are exactly the drift the chaos soak harness
   exists to flush out (unjittered restarts storm in lockstep; uncapped
   polls hang; see docs/chaos.md § retry). Retry through
   ``retry_call``/``Backoff``; poll through ``wait_until``. ``bench.py``
   and ``examples/`` are outside the scanned root on purpose (the bench
   probe ladder and queue-driver grace periods are driver-side deadline
   machinery, not package retry loops); the heartbeat escalation
   scheduler needs no exemption — it paces itself on ``Event.wait``
   deadlines, which the rule never matches.

7. **ONE HLO parser home** — calling ``.as_text()`` on a lowered/compiled
   program anywhere in ``autodist_tpu/``, ``tests/``, ``examples/``,
   ``bench.py`` or ``__graft_entry__.py`` outside
   ``autodist_tpu/analysis/`` is banned (same single-reader policy as
   rules 4–6): ``analysis/inventory.py`` and ``analysis/graph.py`` are
   the ONE place HLO text is produced and parsed, and the compiled-text
   cache there is what keeps ``--lint``/``--attrib``/plan-cache
   validation from re-lowering the same program three times per run. Get
   text via ``analysis.compiled_hlo / compiled_artifacts /
   compiled_window`` (or ``step.lower_text`` for the StableHLO debug
   surface). Exempt: ``utils/tracing.py`` (the HLO dump-file writer — it
   writes artifacts, never parses them) and ``kernel/lowering.py`` (the
   ``lower_text`` debug surface itself).

8. **ONE page-table/pool allocator home** — constructing a KV page pool
   or page table anywhere outside ``autodist_tpu/serve/pages.py`` is
   banned (same single-home policy as rules 3 and 6): the paged serving
   engine's admission math, the analyzer's static pool accounting, the
   obs utilization/fragmentation gauges and the chaos page-exhaustion
   injector are only mutually consistent because every page is accounted
   by the one allocator. Build pools via ``serve.pages.build_pool``;
   tables only ever come out of ``PagePool.alloc`` (docs/serving.md).

9. **ONE radix-tree home** — constructing a prefix cache or radix node
   (``PrefixCache(`` / ``_RadixNode(``) anywhere outside
   ``autodist_tpu/serve/prefix.py`` is banned (same single-home policy
   as rule 8): the COW sharing contract — refcounted leases, at-most-one
   frontier copy, eviction that never touches a live request's pages —
   only holds because every engine (plain and speculative), the router's
   affinity tiebreak and the chaos eviction-storm injector share the one
   tree implementation. Build caches via
   ``serve.prefix.build_prefix_cache`` (or ``prefix_cache=True`` on the
   engine); hash blocks via ``serve.prefix.block_hashes``
   (docs/serving.md § prefix sharing).

10. **ONE sampling/RNG home for serving** — drawing serving randomness
    (``jax.random.categorical`` / ``gumbel`` / ``fold_in`` /
    ``bernoulli``) anywhere in ``autodist_tpu/serve/`` or
    ``autodist_tpu/models/`` outside ``serve/sampling.py`` is banned
    (same single-home policy as rules 8–9): the replayable-stream
    contract — every draw a pure function of ``(request_id, seed,
    position)`` — only holds because the counter-based key derivation
    and the temperature/top-k/top-p transform live in exactly one
    place. A second sampler would silently fork the failover
    bit-identity story (docs/serving.md § stochastic sampling).
    ``models/layers.py``'s ``jax.random.uniform/normal`` parameter init
    is untouched by design: the rule bans the *sampling* draw family,
    not weight init.

11. **ONE actuator over plan/serve knobs** — constructing the autopilot's
    deployed-state or decision-journal writers (``PilotState(`` /
    ``PilotStateStore(`` / ``DecisionJournal(``) anywhere in
    ``autodist_tpu/`` outside ``pilot/`` is banned (same single-home
    policy as rules 8–10): the closed-loop retuning story — episode
    gating, cooldown/rate limits, write-ahead journal, canary/rollback,
    crash recovery to old-or-new-never-mixed — only holds because every
    knob deploy flows through the one controller. A second actuator
    writing ``plan``/``serve`` knobs would race the canary window and
    corrupt the recovery contract (docs/autopilot.md). Read-side access
    (``pilot_dir()`` / ``read_decisions``) is open to everyone — the
    doctor stitches the journal into its timeline that way.

12. **ONE paged-attention math home** — spelling paged attention math
    (the per-layer page gather ``_paged_gather(`` or the paged timeline
    einsum contractions ``bthd->bht`` / ``bthd->bhqt`` / ``thd->hct``)
    anywhere in ``autodist_tpu/models/`` or ``autodist_tpu/serve/``
    outside ``ops/paged_attention.py`` is banned (same single-home
    policy as rules 8–11): the kernel-vs-gather bit-identity bar, the
    int8 dequantize-in-kernel contract and the measured crossover are
    only sound because every forward path — decode, prefill-chunk, spec
    verify — calls the one ops module; a re-inlined gather/einsum would
    silently fork streams the moment the impl flips
    (docs/serving.md § paged-attention kernel). Call
    ``ops.paged_attention.paged_{decode,prefill,verify}_attention``.

Pure stdlib, no third-party deps — runs anywhere Python runs.
"""
from __future__ import annotations

import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SHARD_MAP_RE = re.compile(
    r"^\s*(from\s+jax\.experimental(\.shard_map)?\s+import\s+.*shard_map"
    r"|.*\bjax\.experimental\.shard_map\b(?!`))")
TIME_TIME_RE = re.compile(r"\btime\.time\(\)")
PSUM_CALL_RE = re.compile(r"\blax\.psum(_scatter)?\s*\(")
# Rule 4: an open() whose argument expression mentions a flight path, or
# any use of the segment-name prefix literal, outside obs/recorder.py.
FLIGHT_WRITE_RE = re.compile(r"open\([^)\n]*flight|['\"]flight-")
# Rule 5: the xplane proto import / trace-file glob, outside obs/attrib.py.
XPLANE_RE = re.compile(r"\bxplane_pb2\b|xplane\.pb\b")
# Rule 6: a literal time.sleep call — retry/poll loops go through
# utils/retry.py (passing `time.sleep` as a callable default is fine; the
# rule targets call sites).
TIME_SLEEP_RE = re.compile(r"\btime\.sleep\s*\(")
# Rule 7: HLO text production/parsing outside the analysis parser home.
AS_TEXT_RE = re.compile(r"\.as_text\s*\(")
# Rule 8: page-pool/page-table construction outside serve/pages.py.
PAGES_RE = re.compile(r"\bPagePool\s*\(|\bPageTable\s*\(")
# Rule 9: radix-tree construction outside serve/prefix.py.
PREFIX_RE = re.compile(r"\bPrefixCache\s*\(|\b_RadixNode\s*\(")
# Rule 10: serving-randomness draws outside serve/sampling.py.
SAMPLING_RE = re.compile(
    r"\bjax\.random\.(categorical|gumbel|fold_in|bernoulli)\s*\(")
# Rule 11: pilot actuator construction outside pilot/.
PILOT_RE = re.compile(
    r"\bPilotState\s*\(|\bPilotStateStore\s*\(|\bDecisionJournal\s*\(")
# Rule 12: paged-attention math outside ops/paged_attention.py — the page
# gather helper or any paged timeline einsum contraction.
PAGED_MATH_RE = re.compile(
    r"\b_paged_gather\s*\(|bthd->bht\b|bthd->bhqt\b|thd->hct\b")


def _py_files(*roots):
    for root in roots:
        full = os.path.join(REPO, root)
        if os.path.isfile(full):
            yield root
            continue
        for dirpath, dirnames, filenames in os.walk(full):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in filenames:
                if f.endswith(".py"):
                    yield os.path.relpath(
                        os.path.join(dirpath, f), REPO)


def main() -> int:
    errors = []

    for rel in _py_files("autodist_tpu", "tests", "examples", "bench.py"):
        with open(os.path.join(REPO, rel), "r", encoding="utf-8") as f:
            for i, line in enumerate(f, 1):
                code = line.split("#", 1)[0]
                if SHARD_MAP_RE.search(code):
                    errors.append(
                        f"{rel}:{i}: bare jax.experimental.shard_map import"
                        f" — use autodist_tpu.utils.compat.shard_map")

    for rel in _py_files("bench.py", os.path.join("examples", "benchmark")):
        with open(os.path.join(REPO, rel), "r", encoding="utf-8") as f:
            for i, line in enumerate(f, 1):
                code = line.split("#", 1)[0]
                if TIME_TIME_RE.search(code):
                    errors.append(
                        f"{rel}:{i}: time.time() in a bench file — timed "
                        f"windows must use time.perf_counter()")

    psum_allowed = {
        os.path.join("autodist_tpu", "kernel", "bucketing.py"),
        os.path.join("autodist_tpu", "kernel", "compressor.py"),
    }
    for rel in _py_files(os.path.join("autodist_tpu", "kernel")):
        if rel in psum_allowed:
            continue
        with open(os.path.join(REPO, rel), "r", encoding="utf-8") as f:
            for i, line in enumerate(f, 1):
                code = line.split("#", 1)[0]
                if PSUM_CALL_RE.search(code):
                    errors.append(
                        f"{rel}:{i}: direct lax.psum/psum_scatter for grad "
                        f"sync — emit through kernel/bucketing.py (the one "
                        f"bucketed-emission helper; docs/zero.md)")

    flight_allowed = {os.path.join("autodist_tpu", "obs", "recorder.py")}
    for rel in _py_files("autodist_tpu"):
        if rel in flight_allowed:
            continue
        with open(os.path.join(REPO, rel), "r", encoding="utf-8") as f:
            for i, line in enumerate(f, 1):
                code = line.split("#", 1)[0]
                if FLIGHT_WRITE_RE.search(code):
                    errors.append(
                        f"{rel}:{i}: direct flight-record dir access — go "
                        f"through autodist_tpu/obs/recorder.py (the ONE "
                        f"writer with the fsync/rotation discipline; "
                        f"docs/observability.md)")

    xplane_allowed = {os.path.join("autodist_tpu", "obs", "attrib.py")}
    for rel in _py_files("autodist_tpu", "examples", "tests", "bench.py"):
        if rel in xplane_allowed:
            continue
        with open(os.path.join(REPO, rel), "r", encoding="utf-8") as f:
            for i, line in enumerate(f, 1):
                code = line.split("#", 1)[0]
                if XPLANE_RE.search(code):
                    errors.append(
                        f"{rel}:{i}: xplane parsing outside obs/attrib.py "
                        f"— capture/parse through the attribution library "
                        f"(the ONE trace reader; docs/observability.md)")

    # (ft/heartbeat.py needs no exemption: its escalation scheduler paces
    # itself on Event.wait deadlines, which this regex never matches.)
    sleep_allowed = {
        os.path.join("autodist_tpu", "utils", "retry.py"),
    }
    for rel in _py_files("autodist_tpu"):
        if rel in sleep_allowed:
            continue
        with open(os.path.join(REPO, rel), "r", encoding="utf-8") as f:
            for i, line in enumerate(f, 1):
                code = line.split("#", 1)[0]
                if TIME_SLEEP_RE.search(code):
                    errors.append(
                        f"{rel}:{i}: ad-hoc time.sleep retry/poll loop — "
                        f"go through autodist_tpu/utils/retry.py "
                        f"(retry_call/Backoff/wait_until, the ONE "
                        f"jittered-backoff home; docs/chaos.md)")

    as_text_exempt = {
        # The dump-file writer (writes debug artifacts, parses nothing)
        # and the lower_text StableHLO debug surface itself.
        os.path.join("autodist_tpu", "utils", "tracing.py"),
        os.path.join("autodist_tpu", "kernel", "lowering.py"),
    }
    for rel in _py_files("autodist_tpu", "tests", "examples", "bench.py",
                         "__graft_entry__.py"):
        if rel in as_text_exempt or rel.startswith(
                os.path.join("autodist_tpu", "analysis") + os.sep):
            continue
        with open(os.path.join(REPO, rel), "r", encoding="utf-8") as f:
            for i, line in enumerate(f, 1):
                code = line.split("#", 1)[0]
                if AS_TEXT_RE.search(code):
                    errors.append(
                        f"{rel}:{i}: .as_text() HLO text outside "
                        f"autodist_tpu/analysis/ — go through "
                        f"analysis.compiled_hlo/compiled_artifacts/"
                        f"compiled_window (the ONE parser home with the "
                        f"compiled-text cache; docs/analysis.md)")

    pages_allowed = {os.path.join("autodist_tpu", "serve", "pages.py")}
    for rel in _py_files("autodist_tpu", "tests", "examples", "bench.py"):
        if rel in pages_allowed:
            continue
        with open(os.path.join(REPO, rel), "r", encoding="utf-8") as f:
            for i, line in enumerate(f, 1):
                code = line.split("#", 1)[0]
                if PAGES_RE.search(code):
                    errors.append(
                        f"{rel}:{i}: page-pool/page-table construction "
                        f"outside autodist_tpu/serve/pages.py — build "
                        f"pools via serve.pages.build_pool and get tables "
                        f"from PagePool.alloc (the ONE allocator home; "
                        f"docs/serving.md)")

    prefix_allowed = {os.path.join("autodist_tpu", "serve", "prefix.py")}
    for rel in _py_files("autodist_tpu", "tests", "examples", "bench.py"):
        if rel in prefix_allowed:
            continue
        with open(os.path.join(REPO, rel), "r", encoding="utf-8") as f:
            for i, line in enumerate(f, 1):
                code = line.split("#", 1)[0]
                if PREFIX_RE.search(code):
                    errors.append(
                        f"{rel}:{i}: radix-tree construction outside "
                        f"autodist_tpu/serve/prefix.py — build via "
                        f"serve.prefix.build_prefix_cache (the ONE COW "
                        f"prefix-sharing home; docs/serving.md § prefix "
                        f"sharing)")

    sampling_allowed = {os.path.join("autodist_tpu", "serve", "sampling.py")}
    for rel in _py_files(os.path.join("autodist_tpu", "serve"),
                         os.path.join("autodist_tpu", "models")):
        if rel in sampling_allowed:
            continue
        with open(os.path.join(REPO, rel), "r", encoding="utf-8") as f:
            for i, line in enumerate(f, 1):
                code = line.split("#", 1)[0]
                if SAMPLING_RE.search(code):
                    errors.append(
                        f"{rel}:{i}: serving randomness drawn outside "
                        f"autodist_tpu/serve/sampling.py — sample through "
                        f"sampling.sample_tokens / request_key (the ONE "
                        f"counter-based RNG home; a second sampler forks "
                        f"the replay bit-identity contract; "
                        f"docs/serving.md § stochastic sampling)")

    # The chaos soak harness provisions a scratch controller in order to
    # ATTACK it (poisoned_calibration) — a driver, not a second actuator.
    pilot_allowed = {os.path.join("autodist_tpu", "chaos", "harness.py")}
    for rel in _py_files("autodist_tpu"):
        if rel in pilot_allowed or rel.startswith(
                os.path.join("autodist_tpu", "pilot") + os.sep):
            continue
        with open(os.path.join(REPO, rel), "r", encoding="utf-8") as f:
            for i, line in enumerate(f, 1):
                code = line.split("#", 1)[0]
                if PILOT_RE.search(code):
                    errors.append(
                        f"{rel}:{i}: pilot state/journal construction "
                        f"outside autodist_tpu/pilot/ — the autopilot is "
                        f"the ONE actuator over plan/serve knobs; deploy "
                        f"through its Controller, read via "
                        f"pilot.read_decisions (docs/autopilot.md)")

    for rel in _py_files(os.path.join("autodist_tpu", "models"),
                         os.path.join("autodist_tpu", "serve")):
        with open(os.path.join(REPO, rel), "r", encoding="utf-8") as f:
            for i, line in enumerate(f, 1):
                code = line.split("#", 1)[0]
                if PAGED_MATH_RE.search(code):
                    errors.append(
                        f"{rel}:{i}: paged-attention math outside "
                        f"autodist_tpu/ops/paged_attention.py — call "
                        f"ops.paged_attention.paged_*_attention (the ONE "
                        f"home the kernel-vs-gather bit-identity and the "
                        f"int8 dequantize-in-kernel contract hold over; "
                        f"docs/serving.md § paged-attention kernel)")

    if errors:
        print("banned-pattern lint FAILED:", file=sys.stderr)
        for e in errors:
            print("  " + e, file=sys.stderr)
        return 1
    print("banned-pattern lint ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
