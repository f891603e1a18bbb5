"""Explain strategy selection for a model on a cluster, from the CLI.

The reference left strategy choice to the user with only qualitative
guidance ("the best strategy differs per model",
``/root/reference/docs/usage/performance.md:14``). This tool prints what the
:class:`~autodist_tpu.strategy.cost_model.CostModel` predicts for every
builder on a concrete (model × cluster) pair — per-step sync/update/latency
time, per-chip memory vs HBM, feasibility — so the choice is auditable
before any chip time is spent::

    python -m autodist_tpu.strategy.explain --model bert_base
    python -m autodist_tpu.strategy.explain --model lstm_lm \
        --resource-spec spec.yml --batch-size 256

Zoo model names come from ``autodist_tpu.models``; ``--model-kwargs`` passes
factory overrides as ``k=v`` pairs (ints/floats auto-coerced).
"""
from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional, Tuple

from autodist_tpu.model_item import ModelItem
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.strategy.cost_model import CostModel, candidate_slate


def _coerce(v: str):
    for cast in (int, float):
        try:
            return cast(v)
        except ValueError:
            continue
    return v


def explain(
    model_item: ModelItem,
    resource_spec: ResourceSpec,
    candidates: Optional[List[Tuple[str, object]]] = None,
    out=None,
    measured: Optional[dict] = None,
    calibration=None,
) -> List[Tuple[str, object]]:
    """Rank candidate builders for (model × cluster); print a table.

    ``measured`` maps candidate names to measured seconds/step (e.g. from
    ``AutoDist.tune``'s ``last_tune_results`` table or a saved sweep) —
    shown as an extra column. ``calibration`` is a
    :class:`~autodist_tpu.strategy.cost_model.Calibration`; pass ``"auto"``
    to load the default file a prior ``tune()`` wrote. When present, a
    calibrated absolute step-time column appears next to the analytical
    one (VERDICT r1 next #10: the model's predictions carry a measured
    anchor).

    Returns the ranked ``[(name, StrategyCost), ...]`` — the RAW cost
    ranking, best-priced first. This may place a lossy compressed-wire
    candidate (e.g. ``AllReduce+topk`` from the full slate) at index 0;
    the printed ``recommended:`` headline applies the lossless-first
    policy on top, and programmatic callers wanting the same safe default
    must do likewise (classify with
    ``kernel.compressor.is_active_compressor`` over
    ``strategy.ir.iter_synchronizers``) rather than blindly adopting
    ``ranked[0]``.
    """
    from autodist_tpu.strategy.cost_model import Calibration

    out = out if out is not None else sys.stdout
    if calibration == "auto":
        calibration = Calibration.load()
    cm = CostModel(model_item, resource_spec)
    built = []
    # The full slate (tune/Auto's shared candidates + the remaining
    # builders) — explain shows everything, flagged by feasibility.
    for name, builder in candidates or candidate_slate(full=True):
        try:
            built.append((name, builder.build(model_item, resource_spec)))
        except Exception as e:  # noqa: BLE001 - keep explaining the rest
            print(f"{name:22s} failed to build: {e}", file=out)
    ranked = cm.rank(built)
    print(
        f"\n{resource_spec!r}\n"
        f"model: {len(model_item.variables)} vars, "
        f"{len(model_item.sparse_variables)} sparse, "
        f"{model_item.total_bytes / 1e6:.1f} MB params, "
        f"optimizer={model_item.optimizer_spec.name}\n",
        file=out,
    )
    if calibration is not None:
        print(
            f"calibration: measured ≈ {calibration.base_s * 1e3:.3f}ms + "
            f"{calibration.scale:.2f} × predicted "
            f"({calibration.n_points} candidates on "
            f"{calibration.device or 'unknown device'})\n",
            file=out,
        )
    header = (
        f"{'strategy':22s} {'total':>10s} {'comm':>10s} {'update':>9s} "
        f"{'latency':>9s} {'act':>9s} {'gather':>9s} {'mem/chip':>10s} "
        f"{'opt/chip':>10s} {'fits':>5s}"
        + (f" {'calib':>10s}" if calibration is not None else "")
        + (f" {'measured':>10s}" if measured else "")
    )
    print(header, file=out)
    print("-" * len(header), file=out)
    for name, cost in ranked:
        row = (
            f"{name:22s} {cost.total_s * 1e3:8.3f}ms {cost.comm_s * 1e3:8.3f}ms "
            f"{cost.update_s * 1e3:7.3f}ms {cost.latency_s * 1e3:7.3f}ms "
            f"{cost.act_sync_s * 1e3:7.3f}ms {cost.gather_s * 1e3:7.3f}ms "
            f"{cost.per_chip_bytes / 1e9:8.2f}GB "
            f"{cost.opt_bytes / 1e9:8.2f}GB "
            f"{'yes' if cost.feasible else 'NO':>5s}"
        )
        if calibration is not None:
            row += f" {calibration.predict_s(cost) * 1e3:8.3f}ms"
        if measured:
            m = measured.get(name)
            row += f" {m * 1e3:8.3f}ms" if m is not None else f" {'—':>10s}"
        print(row, file=out)
    if ranked and not ranked[0][1].feasible:
        print(
            f"\nWARNING: no candidate fits per-chip HBM "
            f"({ranked[0][1].hbm_bytes / 1e9:.2f} GB usable) — showing the "
            f"least-over-budget candidate; expect OOM without a bigger "
            f"chip, more shards, or host offload.",
            file=out,
        )
    best = ranked[0][0] if ranked else "(none)"
    # Lossy-wire candidates (active gradient compressors) may top the
    # table but are never *recommended*: compression changes numerics, so
    # the user opts in by naming the compressor, not by following a
    # default recommendation.
    from autodist_tpu.kernel.compressor import is_active_compressor
    from autodist_tpu.strategy.ir import iter_synchronizers

    def _lossy(strategy) -> bool:
        # Per-shard (part_config) compressors override node-level ones
        # (ir.py fold contract) — iter_synchronizers walks both levels.
        return any(
            is_active_compressor(getattr(s, "compressor", "") or "")
            for n in strategy.node_config
            for s in iter_synchronizers(n)
        )

    lossy_names = {name for name, s in built if _lossy(s)}
    if best in lossy_names:
        lossless = next((n for n, _ in ranked if n not in lossy_names), None)
        if lossless is not None:
            print(
                f"\nrecommended: {lossless} (fastest priced: {best}, but "
                f"its compressed wire changes numerics — opt in explicitly "
                f"via its compressor knob)",
                file=out,
            )
        else:
            print(
                f"\nrecommended: {best} — NOTE: every ranked candidate "
                f"carries a compressed (lossy) wire; there is no lossless "
                f"default here, so treat this as an explicit opt-in",
                file=out,
            )
        return ranked
    print(f"\nrecommended: {best}", file=out)
    return ranked


def explain_provenance(provenance: dict, out=None) -> None:
    """Render a plan-search provenance record (``autodist_tpu.plan``) —
    candidates visited, the seed table, predicted (and calibrated /
    measured, when recorded) costs, and why the winner won. The record is
    what ``Plan.last_result["provenance"]`` holds and what the plan cache
    persists next to every winner (``provenance.json``)."""
    out = out if out is not None else sys.stdout
    if not provenance:
        print("(empty provenance: cached entry predates search provenance)",
              file=out)
        return
    print(
        f"plan search: {provenance.get('n_visited', '?')} candidates "
        f"visited (beam {provenance.get('beam_width', '?')} × "
        f"{provenance.get('generations', '?')} generations, "
        f"seed {provenance.get('search_seed', '?')})",
        file=out,
    )
    seeds = provenance.get("seeds", {})
    if seeds:
        print(f"\n{'seed':22s} {'predicted':>11s} {'mem/chip':>10s} "
              f"{'fits':>5s}", file=out)
        for name in sorted(seeds, key=lambda n: seeds[n].get(
                "predicted_s", float("inf"))):
            row = seeds[name]
            print(
                f"{name:22s} {row.get('predicted_s', 0.0) * 1e3:9.3f}ms "
                f"{row.get('per_chip_gb', 0.0):8.2f}GB "
                f"{'yes' if row.get('feasible') else 'NO':>5s}",
                file=out,
            )
    w = provenance.get("winner", {})
    print(
        f"\nwinner: {w.get('origin', '?')} — "
        f"predicted {w.get('predicted_s', 0.0) * 1e3:.3f} ms/step "
        f"(comm {w.get('comm_s', 0.0) * 1e3:.3f}, "
        f"update {w.get('update_s', 0.0) * 1e3:.3f}, "
        f"lat {w.get('latency_s', 0.0) * 1e3:.3f}, "
        f"act {w.get('act_sync_s', 0.0) * 1e3:.3f}, "
        f"gather {w.get('gather_s', 0.0) * 1e3:.3f}, "
        f"overlap {w.get('overlap_s', 0.0) * 1e3:.3f}), "
        f"{w.get('per_chip_gb', 0.0):.2f} GB/chip "
        f"(opt {w.get('opt_gb_per_chip', 0.0):.2f}) "
        f"{'ok' if w.get('feasible') else 'OVER'}",
        file=out,
    )
    if w.get("n_shard_update"):
        print(
            f"zero1: {w['n_shard_update']} vars carry shard_update "
            f"(reduce-scatter grads, 1/N-sharded optimizer update, "
            f"all-gather params — docs/zero.md)",
            file=out,
        )
    if w.get("bucket_bytes"):
        print(
            f"bucketed overlap: bucket_bytes={w['bucket_bytes']} — grad "
            f"collectives emitted per bucket inside the backward "
            f"({w.get('overlap_s', 0.0) * 1e3:.3f} ms of wire priced as "
            f"overlappable; kernel/bucketing.py, docs/performance.md)",
            file=out,
        )
    calib = provenance.get("calibration")
    if calib:
        print(
            f"calibrated: {calib.get('predicted_calibrated_s', 0.0) * 1e3:.3f}"
            f" ms/step ({calib.get('n_points', 0)} measured points on "
            f"{calib.get('device') or 'unknown device'}; model error "
            f"{calib.get('mean_abs_rel_err_before', float('nan')) * 100:.1f}%"
            f" -> {calib.get('mean_abs_rel_err_after', float('nan')) * 100:.1f}"
            f"% after fit)",
            file=out,
        )
    if w.get("measured_s"):
        print(f"measured: {w['measured_s'] * 1e3:.3f} ms/step", file=out)
    mesh = provenance.get("mesh")
    if mesh and mesh.get("chosen"):
        print(f"mesh recommendation: {mesh['chosen']} (searched "
              f"{len(mesh.get('candidates', {}))} factorizations)", file=out)
    print(f"\nwhy: {provenance.get('why', '(not recorded)')}", file=out)


def lint(
    model_spec,
    model_item: ModelItem,
    resource_spec: ResourceSpec,
    builder_name: str = "AllReduce",
    batch=None,
    out=None,
) -> int:
    """``--lint``: lower + compile the (model × builder × cluster) step on
    this process's devices and run the static analyzer (shardlint +
    schedlint, ``autodist_tpu.analysis``) over the compiled program —
    findings table, the per-variable planned-vs-actual wire bytes, the
    per-bucket SCHEDULED overlap column (next to what pricing assumed and
    what a trace measures — docs/analysis.md § schedule passes), and the
    scheduled-liveness peak. Falls back to the plan-only passes
    (degradation drift + HBM budget + schedule screen, no wire/schedule
    conformance) when the runtime doesn't have the spec's device count,
    since those need the real compiled program.

    Returns a process exit code: 0 clean, 1 when any error-severity
    finding survives (CI-friendly)."""
    import jax

    from autodist_tpu.analysis import (
        analyze_plan,
        analyze_program,
        report_to_text,
    )
    from autodist_tpu.kernel import (
        DistributedTrainStep,
        GraphTransformer,
        build_mesh,
    )
    from autodist_tpu.strategy import from_name
    from autodist_tpu.strategy.base import StrategyCompiler

    out = out if out is not None else sys.stdout
    builder = from_name(builder_name)
    strategy = StrategyCompiler(model_item).compile(
        builder.build(model_item, resource_spec))
    if jax.device_count() != resource_spec.num_chips:
        print(
            f"lint: runtime has {jax.device_count()} devices, spec wants "
            f"{resource_spec.num_chips} — running plan-only passes (no "
            f"wire conformance, and no HBM budget: shardings realized on "
            f"the local mesh would misprice the spec's per-chip residency; "
            f"run under a matching mesh for the full check)", file=out)
        mesh = build_mesh(ResourceSpec(resource_dict={
            "nodes": [{"address": "localhost",
                       "chips": jax.device_count(), "chief": True}]}))
        plan = GraphTransformer(strategy, model_item, mesh).transform()
        # resource_spec=None: the plan was lowered over the LOCAL mesh, so
        # its shard counts say nothing about the spec's chips — judging
        # un-sharded residency against the remote HBM would emit false
        # SLM001 errors (and a false exit 1) for plans that fit fine.
        report = analyze_plan(
            plan, strategy=strategy, resource_spec=None,
            optimizer=model_item.optimizer_spec.name,
            program=f"{builder_name} (plan-only)", model_item=model_item)
    else:
        mesh = build_mesh(resource_spec)
        plan = GraphTransformer(strategy, model_item, mesh).transform()
        try:
            optimizer = model_item.optimizer_spec.make()
        except TypeError:
            # Default spec with no hyperparameters (lint only needs the
            # program's SHAPE; the learning rate is irrelevant to the wire).
            import optax

            optimizer = optax.sgd(0.1)
        step = DistributedTrainStep(plan, model_spec.loss_fn, optimizer)
        params = model_spec.init(jax.random.PRNGKey(0))
        state = step.init(params)
        # ONE compile serves the HLO text, the memory analysis AND any
        # later analyzer call in this process — compiled_artifacts caches
        # per (step, shapes), and the XLA compile is the dominant cost of
        # lint (analysis/inventory.py).
        from autodist_tpu.analysis import compiled_artifacts

        hlo, temp = compiled_artifacts(step, state, batch)
        report = analyze_program(
            plan, hlo, strategy=strategy, resource_spec=resource_spec,
            optimizer=model_item.optimizer_spec.name, batch=batch,
            temp_bytes=temp, program=builder_name, model_item=model_item)
    print(report_to_text(report), file=out)
    return 0 if report.ok else 1


def wire_measured(
    model_spec,
    model_item: ModelItem,
    resource_spec: ResourceSpec,
    measured_path: str,
    builder_name: str = "AllReduce",
    out=None,
) -> int:
    """``--wire-measured``: the planned → priced → measured table, side by
    side, for one (model × builder × cluster) against a saved
    ``MeasuredWire`` JSON (``obs/attrib.py`` — produced by
    ``StepProfiler.attribute`` / ``bench.py --attrib``). Planned comes
    from the lowered plan's promised wire, priced from the cost model's
    components, measured from the trace attribution; the SLT measured-wire
    findings print below the table (warnings — exit stays 0)."""
    import jax

    from autodist_tpu.analysis.passes import measured_wire_check
    from autodist_tpu.kernel import GraphTransformer, build_mesh
    from autodist_tpu.obs.attrib import MeasuredWire
    from autodist_tpu.strategy import from_name
    from autodist_tpu.strategy.base import StrategyCompiler
    from autodist_tpu.strategy.cost_model import OVERLAP_EXPOSED_FRACTION

    out = out if out is not None else sys.stdout
    builder = from_name(builder_name)
    strategy = StrategyCompiler(model_item).compile(
        builder.build(model_item, resource_spec))
    if jax.device_count() == resource_spec.num_chips:
        mesh = build_mesh(resource_spec)
    else:
        print(
            f"wire-measured: runtime has {jax.device_count()} devices, "
            f"spec wants {resource_spec.num_chips} — lowering the plan on "
            f"the local mesh (promised payloads reflect the local shard "
            f"counts)", file=out)
        mesh = build_mesh(ResourceSpec(resource_dict={
            "nodes": [{"address": "localhost",
                       "chips": jax.device_count(), "chief": True}]}))
    plan = GraphTransformer(strategy, model_item, mesh).transform()
    cost = CostModel(model_item, resource_spec).strategy_cost(strategy)
    measured = MeasuredWire.load(measured_path)
    components = measured.calibration_components()

    print(f"\nmeasured wire: {measured.program or measured_path} "
          f"(window {measured.window}, {measured.n_devices} device "
          f"timeline(s), {measured.device_total_s_per_step * 1e3:.3f} "
          f"ms/step device time"
          + ("" if measured.overlap_measurable
             else ", overlap not measurable on this runtime") + ")",
          file=out)
    print(f"\n{'component':18s} {'priced':>12s} {'measured':>12s}",
          file=out)
    print("-" * 44, file=out)
    rows = [
        ("comm (grad sync)", cost.comm_s, components.get("comm_s")),
        ("gather (zero1 ag)", cost.gather_s, components.get("gather_s")),
        ("overlap (exposed)", OVERLAP_EXPOSED_FRACTION * cost.overlap_s,
         components.get("overlap_s")),
    ]
    for label, priced, meas in rows:
        print(f"{label:18s} {priced * 1e3:10.4f}ms "
              + (f"{meas * 1e3:10.4f}ms" if meas is not None
                 else f"{'—':>12s}"), file=out)

    if measured.buckets:
        print(f"\n{'bucket':>6s} {'measured':>11s} {'overlap':>8s} "
              f"{'promised':>10s}  vars", file=out)
        print("-" * 72, file=out)
        for b in measured.buckets:
            print(f"{b.bucket:6d} {b.measured_s_per_step * 1e3:9.4f}ms "
                  f"{b.overlap_fraction * 100:7.1f}% "
                  f"{b.promised_bytes / 1e6:8.3f}MB  "
                  f"{','.join(b.vars)[:40]}", file=out)

    wires = plan.promised_wire()
    measured_by_var = {r["var"]: r for r in measured.var_table}
    print(f"\n{'variable':28s} {'rendering':11s} {'planned ops':24s} "
          f"{'promised':>10s} {'measured':>10s} {'bucket':>6s}", file=out)
    print("-" * 96, file=out)
    for name, w in sorted(wires.items()):
        if w.rendering == "nontrainable":
            continue
        m = measured_by_var.get(name, {})
        ms = m.get("measured_s_per_step")
        print(
            f"{name[:28]:28s} {w.rendering:11s} "
            f"{','.join(w.require or w.allow)[:24]:24s} "
            f"{w.storage_bytes / 1e6:8.3f}MB "
            + (f"{ms * 1e3:8.4f}ms" if ms is not None else f"{'—':>10s}")
            + (f" {m['bucket']:>6d}" if m.get("bucket") is not None
               else f" {'—':>6s}"),
            file=out)

    findings = measured_wire_check(plan, measured)
    if findings:
        print("", file=out)
        for f in findings:
            print(f.render(), file=out)
    else:
        print("\nmeasured wire conforms: no SLT findings", file=out)
    return 0


def _load_provenance(path: str) -> dict:
    """Provenance from a file, a cache entry dir, or a cache root (newest
    entry wins)."""
    import glob
    import json as _json

    if os.path.isdir(path):
        direct = os.path.join(path, "provenance.json")
        if os.path.exists(direct):
            path = direct
        else:
            candidates = sorted(
                glob.glob(os.path.join(path, "*", "provenance.json")),
                key=os.path.getmtime, reverse=True)
            if not candidates:
                raise FileNotFoundError(
                    f"no provenance.json under {path!r}")
            path = candidates[0]
    with open(path, "r", encoding="utf-8") as f:
        return _json.load(f)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m autodist_tpu.strategy.explain",
        description="Rank strategy builders for a model on a cluster (cost model).",
    )
    p.add_argument("--model", help="zoo model name (e.g. bert_base, resnet, lstm_lm)")
    p.add_argument("--model-kwargs", default="", help='comma "k=v" factory overrides')
    p.add_argument("--resource-spec", default="", help="cluster yml (default: local devices)")
    p.add_argument("--batch-size", type=int, default=32, help="planning batch size")
    p.add_argument(
        "--measured-file", default="",
        help='JSON {"name": seconds_per_step} from a measured sweep; adds a '
             'measured column',
    )
    p.add_argument(
        "--calibration", default="",
        help='path to a tune()-written calibration.json, or "auto" for the '
             'default location; adds a calibrated step-time column',
    )
    p.add_argument(
        "--plan-provenance", default="",
        help="render a plan-search provenance record instead of the slate "
             "table: a provenance.json path, a plan-cache entry dir, or a "
             "cache root (newest entry). See docs/planner.md.",
    )
    p.add_argument(
        "--platform", default="cpu",
        help="jax platform for the planning traces (default cpu: ranking is "
             "analytical and must not hang on an absent/wedged accelerator; "
             "pass e.g. 'tpu' to derive the default ResourceSpec from the "
             "real local devices instead of a --resource-spec file)",
    )
    p.add_argument(
        "--lint", action="store_true",
        help="run the static sharding analyzer (shardlint, docs/analysis.md) "
             "over the builder's compiled program instead of the ranking "
             "table: findings + per-variable planned-vs-actual wire bytes; "
             "exits 1 on any error finding. Provisions a CPU mesh matching "
             "the spec's chip count when no backend exists yet.",
    )
    p.add_argument(
        "--builder", default="AllReduce",
        help="--lint/--wire-measured: strategy builder to lower "
             "(default AllReduce; any strategy.from_name name)",
    )
    p.add_argument(
        "--wire-measured", default="",
        help="render the planned/priced/measured wire table side by side "
             "against a saved MeasuredWire JSON (obs/attrib.py — from "
             "StepProfiler.attribute or bench.py --attrib); SLT findings "
             "print below (docs/observability.md § attribution)",
    )
    args = p.parse_args(argv)

    if args.plan_provenance:
        try:
            provenance = _load_provenance(args.plan_provenance)
        except (OSError, ValueError) as e:
            p.error(f"--plan-provenance {args.plan_provenance!r}: {e}")
        explain_provenance(provenance)
        return 0
    if not args.model:
        p.error("--model is required (or pass --plan-provenance)")

    import jax

    if args.platform:
        # Before any backend use: shape-only planning runs anywhere, and
        # must not take the chip from a process that is training on it.
        jax.config.update("jax_platforms", args.platform)
    if (args.lint or args.wire_measured) and args.resource_spec \
            and args.platform == "cpu":
        # Wire conformance needs a mesh of the spec's shape; provision the
        # CPU host platform with that many devices while the backend is
        # still uninitialized (the __graft_entry__ recipe). A live backend
        # is used as-is — lint degrades to plan-only passes on mismatch.
        try:
            from jax._src import xla_bridge

            backend_up = bool(xla_bridge._backends)
        except Exception:  # noqa: BLE001 - internal moved: assume up
            backend_up = True
        if not backend_up:
            n = ResourceSpec(args.resource_spec).num_chips
            flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                     if "xla_force_host_platform_device_count" not in f]
            flags.append(f"--xla_force_host_platform_device_count={n}")
            os.environ["XLA_FLAGS"] = " ".join(flags)

    from autodist_tpu.models import get_model

    kwargs = {}
    if args.model_kwargs:
        for pair in args.model_kwargs.split(","):
            k, v = pair.split("=", 1)
            kwargs[k.strip()] = _coerce(v.strip())
    spec = get_model(args.model, **kwargs)

    params = spec.init(jax.random.PRNGKey(0))
    batch = spec.example_batch(args.batch_size)
    # Same capture as build()/the benchmark runner: force-marked sparse and
    # expert names must reach the ranking, not just jaxpr-detected ones.
    item = ModelItem.from_params(
        params, loss_fn=spec.loss_fn, example_batch=batch,
        sparse_names=spec.sparse_names, expert_names=spec.expert_names,
    )
    if args.resource_spec:
        rs = ResourceSpec(args.resource_spec)
    else:
        rs = ResourceSpec.from_local_devices()
        if args.platform == "cpu":
            print(
                "note: cluster derived from the cpu planning platform "
                f"({rs.num_chips} device); pass --resource-spec <yml> for "
                "a real multi-chip topology, or --platform tpu to derive "
                "from the local accelerator",
                file=sys.stderr,
            )
    if args.lint:
        return lint(spec, item, rs, builder_name=args.builder, batch=batch)
    if args.wire_measured:
        return wire_measured(spec, item, rs, args.wire_measured,
                             builder_name=args.builder)
    measured = None
    if args.measured_file:
        import json

        with open(args.measured_file, "r", encoding="utf-8") as f:
            raw = json.load(f)
        # Accept both {"name": seconds} and tune()'s table shape
        # {"name": {"measured_s": ...}}.
        measured = {
            k: (v["measured_s"] if isinstance(v, dict) else float(v))
            for k, v in raw.items()
        }
    calibration = None
    if args.calibration:
        from autodist_tpu.strategy.cost_model import Calibration

        if args.calibration == "auto":
            calibration = "auto"
        else:
            calibration = Calibration.load(args.calibration)
            if calibration is None:
                # An explicit path must not silently degrade — the user
                # would read uncalibrated totals as calibrated ones.
                raise FileNotFoundError(
                    f"--calibration file {args.calibration!r} is missing or "
                    f"unreadable")
    explain(item, rs, measured=measured, calibration=calibration)
    return 0


if __name__ == "__main__":
    sys.exit(main())
