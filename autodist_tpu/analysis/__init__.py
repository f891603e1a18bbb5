"""shardlint: static sharding/collective/memory analysis of lowered programs.

AutoDist's premise is that the strategy compiler — not the user — is
accountable for what the transformed graph actually does; GSPMD
(arXiv 2105.04663) inserts resharding collectives silently wherever
annotations are inconsistent, so "the strategy said reduce-scatter" and
"the program carries reduce-scatter" are different claims. This subsystem
checks the second claim statically: take (Strategy, ShardingPlan,
ResourceSpec, compiled HLO text) and produce a structured findings report
with no device execution — it runs on CPU under ``JAX_PLATFORMS=cpu``.

Surfaces:

- :func:`analyze_plan` — plan-only passes (degradation drift, static HBM
  budget, optional strategy screen): what ``plan/cache.py`` runs before
  trusting a cached winner;
- :func:`analyze_program` — the above plus wire conformance, alias
  hazards, and the SCHEDULE passes (``analysis/sched.py``: per-gradsync-
  bucket scheduled overlap, scheduled-liveness peak with donation
  folding) against a compiled program's
  :class:`~autodist_tpu.analysis.inventory.CollectiveInventory` and
  :class:`~autodist_tpu.analysis.graph.ProgramGraph`: what
  ``strategy/explain.py --lint``, ``bench.py --lint`` and the tier-1 wire
  pins ride;
- :func:`channel_cycle_hazards` — cross-program channel-ordering cycle
  detection (SLH004), the MPMD groundwork sibling of
  :func:`rendezvous_hazards`;
- ``python -m autodist_tpu.analysis --selftest`` — the CPU proof: every
  dryrun family's pinned wire re-derived with zero findings (schedule
  passes active), plus seeded defects that MUST trip each pass
  (docs/analysis.md).
"""
from __future__ import annotations

from typing import Optional

from autodist_tpu.analysis.inventory import (
    COLLECTIVE_KINDS,
    COLLECTIVE_OPS,
    Collective,
    CollectiveInventory,
    assert_hlo_wire,
    collective_sizes,
    compiled_artifacts,
    compiled_hlo,
    compiled_text,
    compiled_window,
    hlo_contains,
)
from autodist_tpu.analysis.graph import (
    HloComputation,
    HloInstr,
    ProgramGraph,
)
from autodist_tpu.analysis.report import (
    FINDING_CODES,
    AnalysisError,
    AnalysisReport,
    Finding,
    report_to_text,
)
from autodist_tpu.analysis.passes import (
    DEFAULT_HEADROOM,
    alias_hazards,
    batch_element_count,
    degradation_check,
    hbm_budget,
    measured_wire_check,
    payload_candidates,
    rendezvous_hazards,
    screen_strategy,
    wire_conformance,
)
from autodist_tpu.analysis.sched import (
    channel_cycle_hazards,
    liveness_check,
    overlap_check,
    scheduled_liveness,
    scheduled_overlap,
    screen_schedule,
)


def analyze_plan(
    plan,
    strategy=None,
    resource_spec=None,
    optimizer: str = "",
    headroom: float = DEFAULT_HEADROOM,
    temp_bytes: float = 0.0,
    serve_pool_bytes: float = 0.0,
    serve_shared_fraction: float = 0.0,
    serve_quant_capacity_x: float = 1.0,
    program: str = "",
    model_item=None,
) -> AnalysisReport:
    """Static passes over a lowered :class:`ShardingPlan` (no program text
    needed): degradation drift vs the shared predicate, and — when a
    ``resource_spec`` is given — the per-chip HBM budget
    (``serve_pool_bytes`` accounts a serving engine's static KV page pool
    as a named tenant, ``InferenceEngine.page_pool_bytes`` per chip;
    ``serve_shared_fraction`` — the engine's ``shared_fraction`` — rides
    the memory summary so the report shows how much of the pool's
    logical footprint COW prefix sharing deduplicates). With
    ``model_item`` (and ``strategy``), the pure-arithmetic schedule screen
    (``sched.screen_schedule``: degenerate bucketing SLO001, bucket
    zero-embed transient SLM003) joins in. This is the validation the
    plan cache runs on every hit."""
    report = AnalysisReport(program=program)
    report.extend(degradation_check(plan, strategy))
    mem_findings, mem_summary = hbm_budget(
        plan, resource_spec=resource_spec, optimizer=optimizer,
        headroom=headroom, temp_bytes=temp_bytes,
        serve_pool_bytes=serve_pool_bytes,
        serve_shared_fraction=serve_shared_fraction,
        serve_quant_capacity_x=serve_quant_capacity_x)
    report.extend(mem_findings)
    report.tables["memory"] = mem_summary
    if strategy is not None and model_item is not None:
        report.extend(screen_schedule(
            strategy, model_item, resource_spec=resource_spec,
            headroom=headroom))
    return report


def analyze_program(
    plan,
    hlo_text: str,
    strategy=None,
    resource_spec=None,
    optimizer: str = "",
    headroom: float = DEFAULT_HEADROOM,
    temp_bytes: float = 0.0,
    serve_pool_bytes: float = 0.0,
    serve_shared_fraction: float = 0.0,
    serve_quant_capacity_x: float = 1.0,
    batch=None,
    batch_elements: Optional[int] = None,
    program: str = "",
    model_item=None,
) -> AnalysisReport:
    """Full analysis of one compiled program: everything
    :func:`analyze_plan` checks plus wire conformance (the program's
    collective inventory diffed against the plan's promised wire) and
    donated-buffer alias hazards. ``batch`` (or ``batch_elements``)
    supplies the activation allowance — pass the training batch whenever
    you have one, or token-scale collectives on tiny models read as
    unplanned."""
    report = analyze_plan(
        plan, strategy=strategy, resource_spec=resource_spec,
        optimizer=optimizer, headroom=headroom, temp_bytes=temp_bytes,
        serve_pool_bytes=serve_pool_bytes,
        serve_shared_fraction=serve_shared_fraction,
        serve_quant_capacity_x=serve_quant_capacity_x,
        program=program, model_item=model_item)
    if batch_elements is None and batch is not None:
        batch_elements = batch_element_count(batch)
    inventory = CollectiveInventory.from_hlo(hlo_text, program=program)
    wire_findings, wire_table = wire_conformance(
        plan, inventory, batch_elements=batch_elements)
    report.extend(wire_findings)
    report.extend(alias_hazards(hlo_text))
    report.tables["wire"] = wire_table
    report.tables["inventory"] = inventory.to_json()
    # Schedule passes (schedlint): post-optimization dumps carry the
    # executor's issue order, so static overlap and scheduled liveness run
    # whenever the dump is scheduled — zero extra compiles.
    graph = ProgramGraph.from_hlo(hlo_text, program=program)
    if graph.is_scheduled and graph.entry is not None:
        ov_findings, ov_table = overlap_check(graph)
        report.extend(ov_findings)
        report.tables["sched_overlap"] = ov_table
        static_ok = not any(
            f.code in ("SLM001", "SLM002") for f in report.findings)
        lv_findings, lv_summary = liveness_check(
            graph, resource_spec=resource_spec, headroom=headroom,
            static_totals_ok=static_ok)
        report.extend(lv_findings)
        report.tables["sched_memory"] = lv_summary
    return report


__all__ = [
    "COLLECTIVE_KINDS",
    "COLLECTIVE_OPS",
    "AnalysisError",
    "AnalysisReport",
    "Collective",
    "CollectiveInventory",
    "DEFAULT_HEADROOM",
    "FINDING_CODES",
    "Finding",
    "HloComputation",
    "HloInstr",
    "ProgramGraph",
    "alias_hazards",
    "analyze_plan",
    "analyze_program",
    "assert_hlo_wire",
    "batch_element_count",
    "channel_cycle_hazards",
    "collective_sizes",
    "compiled_artifacts",
    "compiled_hlo",
    "compiled_text",
    "compiled_window",
    "degradation_check",
    "hbm_budget",
    "hlo_contains",
    "liveness_check",
    "measured_wire_check",
    "overlap_check",
    "payload_candidates",
    "rendezvous_hazards",
    "report_to_text",
    "scheduled_liveness",
    "scheduled_overlap",
    "screen_schedule",
    "screen_strategy",
    "wire_conformance",
]
