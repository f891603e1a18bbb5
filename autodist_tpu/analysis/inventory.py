"""Collective inventory: ONE parser for the collectives in a lowered program.

Every wire pin in the repo — the dryrun families' ``_hlo_wire`` checks, the
sparse/zero1 payload assertions, the analyzer's conformance pass — must read
a program's collectives the same way, or a dump-format change silently
splits "what tests check" from "what the analyzer reports". This module is
that single reading:

- :func:`hlo_contains` / :func:`assert_hlo_wire` / :func:`collective_sizes`
  are the (promoted) ``tests/helpers`` matchers, byte-compatible with their
  previous behavior; the test helper is now a thin re-export of these.
- :class:`CollectiveInventory` is the richer structured view: every
  collective op in a post-optimization HLO dump parsed into op kind, result
  and operand shapes/dtypes, payload bytes, replica groups (explicit
  ``{{0,1},{2,3}}`` and iota ``[2,4]<=[8]`` forms both expanded), channel
  id, and the named-scope ``op_name`` metadata — the substrate the
  analysis passes (``autodist_tpu.analysis.passes``) diff against the
  plan's promised wire.

HLO spells collectives with hyphens (``all-reduce(``), StableHLO with
underscores (``stablehlo.all_reduce``); named-scope metadata rides along as
``metadata={op_name="..."}`` / ``loc("...")`` attachments that must never
satisfy a presence check (a scope named ``zero1.reduce_scatter`` labels
whatever op a regression replaced the real collective with).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

#: Canonical (hyphenated) collective op kinds in a post-optimization dump.
COLLECTIVE_KINDS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# The payload-size half of wire pinning (the classifier
# tests/test_sparse_wire.py pioneered): op-call spellings with the opening
# paren, the exact needles `collective_sizes` greps.
COLLECTIVE_OPS = tuple(f"{k}(" for k in COLLECTIVE_KINDS)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f32": 4, "s32": 4, "u32": 4,
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "c128": 16,
}


def dtype_bytes(dtype: str) -> int:
    """Bytes per element of an HLO dtype string (unknown kinds read as 4 —
    the conservative f32 default)."""
    return _DTYPE_BYTES.get(dtype, 4)


def _variants(op: str) -> Tuple[str, str]:
    """Both spellings of a collective name: hyphenated (post-optimization
    HLO) and underscored (StableHLO / traced jaxpr)."""
    base = op.strip().rstrip("(")
    return base.replace("_", "-"), base.replace("-", "_")


# jax.named_scope labels ride along as HLO metadata={op_name="..."} and
# StableHLO loc("...") attachments — strip both before matching so a
# present-pin can only be satisfied by an actual op call.
_METADATA_RE = re.compile(r'metadata=\{[^}]*\}|loc\("[^"]*"[^)]*\)')
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_SHAPE_RE = re.compile(r"([a-z][0-9a-z]*)\[([0-9,]*)\]")
_CHANNEL_RE = re.compile(r"channel_id=(\d+)")
_GROUPS_EXPLICIT_RE = re.compile(r"replica_groups=\{(\{[0-9,{} ]*\})\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")


def hlo_contains(text: str, op: str) -> bool:
    """True when ``op`` (a collective like ``"reduce-scatter"``) appears AS
    AN OP CALL in a lowered/compiled program dump — post-optimization HLO
    (``all-gather(``), StableHLO (``stablehlo.all_gather``), or a traced
    jaxpr (``all_gather(``). Named-scope metadata mentioning the op does
    not count."""
    hyphen, underscore = _variants(op)
    needles = (f"{hyphen}(", f"stablehlo.{underscore}", f"{underscore}(")
    for line in text.splitlines():
        line = _METADATA_RE.sub("", line)
        if any(n in line for n in needles):
            return True
    return False


def assert_hlo_wire(text: str, present: Iterable[str] = (),
                    absent: Iterable[str] = (), label: str = "") -> None:
    """Pin a program's collective wire: every op in ``present`` must appear,
    none in ``absent`` may. Raises AssertionError naming the offender."""
    where = f" [{label}]" if label else ""
    for op in present:
        assert hlo_contains(text, op), (
            f"lowered program{where} carries no {op!r} wire")
    for op in absent:
        assert not hlo_contains(text, op), (
            f"lowered program{where} unexpectedly carries a {op!r} wire")


def collective_sizes(hlo_text: str, ops: Iterable[str] = COLLECTIVE_OPS,
                     ) -> List[int]:
    """Element count of every collective's result/operand array(s) in a
    post-optimization HLO dump (every shape on a collective's def line —
    the historical tests/helpers contract, preserved verbatim)."""
    sizes = []
    for line in hlo_text.splitlines():
        if "=" not in line or not any(op in line for op in ops):
            continue
        # Shapes sit after '=', e.g.
        #   %all-reduce.3 = (f32[4096,16]{1,0}, f32[]) all-reduce(...)
        lhs = line.split("=", 1)[1]
        shapes = re.findall(r"[a-z][0-9a-z]*\[([0-9,]*)\]", lhs)
        for s in shapes:
            dims = [int(d) for d in s.split(",") if d]
            n = 1
            for d in dims:
                n *= d
            sizes.append(n)
    return sizes


# ----------------------------------------------- compiled-program cache
# Lowering + XLA compile is the dominant cost of EVERY analyzer call; one
# bench/lint/attrib run used to re-lower the same program up to three
# times (explain --lint, bench --lint, the attribution capture). The text
# is cached per (step identity, arg shapes/dtypes): the same step object
# with the same abstract signature always lowers to the same program, so
# the cache can never serve a stale dump within a process. Keyed weakly —
# a released step releases its dumps.
_COMPILED_CACHE = None  # weakref.WeakKeyDictionary, created lazily


def _arg_signature(*trees) -> str:
    import jax

    parts = []
    for leaf in jax.tree_util.tree_leaves(trees):
        dtype = getattr(leaf, "dtype", type(leaf).__name__)
        shape = getattr(leaf, "shape", ())
        parts.append(f"{dtype}{tuple(shape)}")
    return "|".join(parts)


def _step_cache(step) -> Optional[Dict]:
    """The per-step cache dict, or None when the step can't be weakly
    referenced (caching silently off — correctness never depends on it)."""
    global _COMPILED_CACHE
    if _COMPILED_CACHE is None:
        import weakref

        _COMPILED_CACHE = weakref.WeakKeyDictionary()
    try:
        return _COMPILED_CACHE.setdefault(step, {})
    except TypeError:
        return None


def compiled_artifacts(step, state, batch) -> Tuple[str, float]:
    """(post-optimization HLO text, compiled temp/peak bytes) of a
    DistributedTrainStep's single-step program, cached per (step, arg
    shapes). The temp figure feeds the SLM002 budget; 0.0 when the
    backend doesn't expose ``memory_analysis``."""
    cache = _step_cache(step)
    key = ("step", _arg_signature(state, batch))
    if cache is not None and key in cache:
        return cache[key]
    compiled = step._compile(state, batch).lower(state, batch).compile()
    text = compiled.as_text()
    temp = 0.0
    try:
        mem = compiled.memory_analysis()
        temp = float(getattr(mem, "temp_size_in_bytes", 0))
    except Exception:  # noqa: BLE001 - optional backend API
        pass
    if cache is not None:
        cache[key] = (text, temp)
    return text, temp


def compiled_hlo(step, state, batch) -> str:
    """Post-optimization HLO of a DistributedTrainStep's single-step
    program — the text every wire pin greps, cached per (step, shapes)
    (StableHLO from ``lower_text`` shows collectives only when they are
    explicit in the traced program; GSPMD-inserted ones exist only
    post-compile.)"""
    return compiled_artifacts(step, state, batch)[0]


def compiled_window(step, state, batch, num_steps: int,
                    stacked: bool = False):
    """(compiled window program, its post-optimization HLO text), cached
    per (step, arg shapes, window) — the one-compile contract the
    measured-wire attribution rides (``obs/attrib.py``): the SAME compile
    serves the instruction-name → scope map and the captured execution.
    Lowered on abstract shapes only; nothing executes here."""
    import jax

    cache = _step_cache(step)
    key = ("window", _arg_signature(state, batch), int(num_steps),
           bool(stacked))
    if cache is not None and key in cache:
        return cache[key]
    fn = step._window_program(state, batch, num_steps, stacked, False)
    compiled = fn.lower(jax.eval_shape(lambda: state),
                        jax.eval_shape(lambda: batch)).compile()
    out = (compiled, compiled.as_text())
    if cache is not None:
        cache[key] = out
    return out


def compiled_text(jitted, *args) -> str:
    """Post-optimization HLO text of any jitted callable (a serving
    program, a kernel) for ``args`` — arrays or ``ShapeDtypeStruct``s;
    nothing executes. Its first line names the module as a device trace
    does (``HloModule jit_<function name>``)."""
    return jitted.lower(*args).compile().as_text()


def _expand_iota_groups(num_groups: int, group_size: int,
                        dims: Tuple[int, ...],
                        perm: Optional[Tuple[int, ...]]) -> Tuple[Tuple[int, ...], ...]:
    """Expand HLO's iota replica-group form ``[g,s]<=[dims]T(perm)`` into
    explicit groups (the v2 'iota tile assignment' encoding)."""
    ids = np.arange(int(np.prod(dims))).reshape(dims)
    if perm is not None:
        ids = ids.transpose(perm)
    ids = ids.ravel().reshape(num_groups, group_size)
    return tuple(tuple(int(x) for x in row) for row in ids)


@dataclass(frozen=True)
class Collective:
    """One collective op parsed from a lowered/compiled program."""

    op: str                                   # canonical hyphenated kind
    results: Tuple[Tuple[str, Tuple[int, ...]], ...]   # (dtype, dims)
    operands: Tuple[Tuple[str, Tuple[int, ...]], ...]
    replica_groups: Tuple[Tuple[int, ...], ...] = ()   # expanded groups
    groups_raw: str = ""                      # textual form, "" if absent
    channel_id: Optional[int] = None
    op_name: str = ""                         # metadata op_name scope path
    line: str = ""
    # HLO instruction name from the def line ("reduce-scatter.48", no %):
    # the key device profiles carry per event, so measured-wire attribution
    # (obs/attrib.py) can join a traced op to this inventory entry.
    name: str = ""

    @staticmethod
    def _elems(shapes) -> int:
        total = 0
        for _dt, dims in shapes:
            n = 1
            for d in dims:
                n *= d
            total += n
        return total

    @property
    def result_elements(self) -> int:
        return self._elems(self.results)

    @property
    def operand_elements(self) -> int:
        return self._elems(self.operands)

    @property
    def max_payload_elements(self) -> int:
        """Largest single array this collective touches (result or operand)
        — the figure the payload pins compare against variable sizes."""
        per = [self._elems([s]) for s in self.results + self.operands]
        return max(per) if per else 0

    @property
    def result_bytes(self) -> int:
        return sum(
            self._elems([s]) * dtype_bytes(s[0]) for s in self.results)

    @property
    def group_size(self) -> int:
        return len(self.replica_groups[0]) if self.replica_groups else 0


@dataclass
class CollectiveInventory:
    """Every collective in one program, with per-kind lookups — the
    analyzer's structured view of "what the wire actually is"."""

    collectives: List[Collective] = field(default_factory=list)
    program: str = ""   # label for multi-program (rendezvous) analyses

    @classmethod
    def from_hlo(cls, text: str, program: str = "") -> "CollectiveInventory":
        """Parse a post-optimization HLO dump (``compiled.as_text()``).

        Async pairs (``all-reduce-start``/``-done``) count once, under the
        base kind; named-scope metadata never creates an entry.
        """
        out = []
        for raw in text.splitlines():
            op_name_m = _OP_NAME_RE.search(raw)
            line = _METADATA_RE.sub("", raw).strip()
            if "=" not in line:
                continue
            found = None
            for kind in COLLECTIVE_KINDS:
                for spelled in (f"{kind}(", f"{kind}-start("):
                    idx = line.find(spelled)
                    if idx >= 0:
                        found = (kind, idx)
                        break
                if found:
                    break
            if not found:
                continue
            kind, idx = found
            eq = line.index("=")
            if idx < eq:  # '=' inside the call: not a def line
                continue
            results = tuple(
                (m.group(1), tuple(int(d) for d in m.group(2).split(",") if d))
                for m in _SHAPE_RE.finditer(line[eq + 1:idx])
            )
            operands = tuple(
                (m.group(1), tuple(int(d) for d in m.group(2).split(",") if d))
                for m in _SHAPE_RE.finditer(line[idx:])
            )
            groups: Tuple[Tuple[int, ...], ...] = ()
            groups_raw = ""
            gm = _GROUPS_EXPLICIT_RE.search(line)
            if gm:
                groups_raw = gm.group(0)
                groups = tuple(
                    tuple(int(x) for x in g.split(",") if x.strip())
                    for g in re.findall(r"\{([0-9, ]*)\}", gm.group(1))
                )
            else:
                im = _GROUPS_IOTA_RE.search(line)
                if im:
                    groups_raw = im.group(0)
                    dims = tuple(int(x) for x in im.group(3).split(","))
                    perm = (tuple(int(x) for x in im.group(4).split(","))
                            if im.group(4) else None)
                    groups = _expand_iota_groups(
                        int(im.group(1)), int(im.group(2)), dims, perm)
            cm = _CHANNEL_RE.search(line)
            nm = re.match(r"(?:ROOT\s+)?%?([A-Za-z0-9_.-]+)\s*$",
                          line[:eq].strip())
            out.append(Collective(
                op=kind,
                results=results,
                operands=operands,
                replica_groups=groups,
                groups_raw=groups_raw,
                channel_id=int(cm.group(1)) if cm else None,
                op_name=op_name_m.group(1) if op_name_m else "",
                line=line,
                name=nm.group(1) if nm else "",
            ))
        return cls(collectives=out, program=program)

    # -------------------------------------------------------------- lookups
    def ops(self) -> Tuple[str, ...]:
        """Distinct op kinds present, in :data:`COLLECTIVE_KINDS` order."""
        present = {c.op for c in self.collectives}
        return tuple(k for k in COLLECTIVE_KINDS if k in present)

    def by_op(self, kind: str) -> List[Collective]:
        return [c for c in self.collectives if c.op == kind]

    def has(self, kind: str) -> bool:
        return any(c.op == kind for c in self.collectives)

    def max_payload(self, kind: Optional[str] = None) -> int:
        cs = self.collectives if kind is None else self.by_op(kind)
        return max((c.max_payload_elements for c in cs), default=0)

    def sizes(self, ops: Iterable[str] = COLLECTIVE_KINDS) -> List[int]:
        """Per-array element counts across the selected kinds (results and
        operands, matching the historical :func:`collective_sizes` rule)."""
        kinds = {o.rstrip("(") for o in ops}
        out: List[int] = []
        for c in self.collectives:
            if c.op in kinds:
                out.extend(
                    Collective._elems([s]) for s in c.results + c.operands)
        return out

    def to_json(self) -> List[Dict]:
        return [
            {
                "op": c.op,
                "name": c.name,
                "result_elements": c.result_elements,
                "result_bytes": c.result_bytes,
                "max_payload_elements": c.max_payload_elements,
                "n_groups": len(c.replica_groups),
                "group_size": c.group_size,
                "channel_id": c.channel_id,
                "op_name": c.op_name,
            }
            for c in self.collectives
        ]

    def describe(self) -> str:
        lines = [f"CollectiveInventory({self.program or 'program'}: "
                 f"{len(self.collectives)} collectives)"]
        for c in self.collectives:
            lines.append(
                f"  {c.op:<19s} {c.result_elements:>10d} elems "
                f"{c.result_bytes:>10d} B groups={len(c.replica_groups)}"
                f"x{c.group_size}"
                + (f"  [{c.op_name}]" if c.op_name else "")
            )
        return "\n".join(lines)
