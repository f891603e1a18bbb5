"""Reader of the JAX profiler's ``.xplane.pb``: device busy time, the
device operations that took most time, the longest idle gaps by what the
host was doing, and the time of named kernels and of collectives.

Only ``jax.profiler.ProfileData`` is used. Run as a script on a trace file
to print what it holds (planes, lines, a few events): look at one trace by
hand before writing a reader against it.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _union(intervals):
    """Merged ``[start, end)`` intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _display(name: str) -> str:
    """An operation's name without its instance number, so that all
    executions of the layers' copies of one operation add up."""
    return re.sub(r"[.\d]+$", "", name) or name


class Trace:
    """Events of one profile, times in seconds from the profile's start."""

    def __init__(self, device_ops, device_modules, host_events):
        self.device_ops = device_ops          # {device index: [(name, start, dur)]}
        self.device_modules = device_modules  # {device index: [(name, start, dur)]}
        self.host_events = host_events        # [(name, start, dur)]

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        ops, modules, host = {}, {}, []
        raw = []
        for plane in data.planes:
            m = re.match(r"/device:TPU:(\d+)$", plane.name)
            for line in plane.lines:
                if m and line.name in (OPS_LINE, MODULES_LINE):
                    kind = "op" if line.name == OPS_LINE else "module"
                    for ev in line.events:
                        raw.append((kind, int(m.group(1)), ev.name,
                                    ev.start_ns, ev.duration_ns))
                elif plane.name.startswith("/host:"):
                    for ev in line.events:
                        raw.append(("host", 0, ev.name, ev.start_ns,
                                    ev.duration_ns))
        t_min = min((r[3] for r in raw), default=0)
        for kind, dev, name, start, dur in raw:
            rec_t = (start - t_min) / 1e9
            if kind == "op":
                ops.setdefault(dev, []).append((name, rec_t, dur / 1e9))
            elif kind == "module":
                modules.setdefault(dev, []).append((name, rec_t, dur / 1e9))
            else:
                host.append((name, rec_t, dur / 1e9))
        return cls(ops, modules, host)

    # ------------------------------------------------------------ device
    def busy_intervals(self, dev: int):
        return _union([(s, s + d) for _, s, d in self.device_ops.get(dev, ())])

    def busy_seconds(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.device_ops:
            return 0.0
        per = [sum(e - s for s, e in self.busy_intervals(dev))
               for dev in self.device_ops]
        return sum(per) / len(per)

    def op_seconds(self, dev: int = None):
        """``{display name: seconds}`` on one device (the lowest by default)."""
        if not self.device_ops:
            return {}
        dev = min(self.device_ops) if dev is None else dev
        out = defaultdict(float)
        for name, _, dur in self.device_ops[dev]:
            out[op_label(name)] += dur
        return dict(out)

    def top_ops(self, n: int = 10):
        return [[k, v] for k, v in sorted(
            self.op_seconds().items(), key=lambda kv: -kv[1])[:n]]

    def seconds_matching(self, pattern: str, dev: int = None):
        """``(seconds, calls)`` of the operations whose name matches
        ``pattern`` on one device."""
        if not self.device_ops:
            return 0.0, 0
        dev = min(self.device_ops) if dev is None else dev
        rx = re.compile(pattern)
        hits = [d for name, _, d in self.device_ops[dev] if rx.search(name)]
        return sum(hits), len(hits)

    def collective_seconds(self, dev: int = None) -> float:
        """Device time of collective operations (their union, so that a
        start/done pair is not counted twice)."""
        if not self.device_ops:
            return 0.0
        dev = min(self.device_ops) if dev is None else dev
        spans = [(s, s + d) for name, s, d in self.device_ops[dev]
                 if op_label(name).startswith(COLLECTIVES)]
        return sum(e - s for s, e in _union(spans))

    def module_runs(self, dev: int = None):
        """``{module name: [(start, end), ...]}`` on one device."""
        if not self.device_modules:
            return {}
        dev = min(self.device_modules) if dev is None else dev
        out = defaultdict(list)
        for name, start, dur in self.device_modules[dev]:
            out[name].append((start, start + dur))
        return dict(out)

    def ops_within(self, runs, dev: int = None):
        """The operations of one device that start inside any of ``runs``."""
        import bisect

        if not self.device_ops:
            return []
        dev = min(self.device_ops) if dev is None else dev
        runs = sorted(runs)
        starts = [r[0] for r in runs]
        out = []
        for op in self.device_ops[dev]:
            i = bisect.bisect_right(starts, op[1]) - 1
            if i >= 0 and op[1] < runs[i][1]:
                out.append(op)
        return out

    def module_seconds(self, dev: int = None):
        """``{module name: (seconds, runs)}`` on one device."""
        if not self.device_modules:
            return {}
        dev = min(self.device_modules) if dev is None else dev
        out = defaultdict(lambda: [0.0, 0])
        for name, _, dur in self.device_modules[dev]:
            out[name][0] += dur
            out[name][1] += 1
        return {k: tuple(v) for k, v in out.items()}

    # ------------------------------------------------------------- gaps
    def idle_gaps(self, n: int = 10, dev: int = None):
        """The idle time of one device by what the host was doing: every
        gap between busy intervals goes to the innermost host event that
        covers its middle; the ``n`` names with most idle seconds."""
        if not self.device_ops:
            return []
        dev = min(self.device_ops) if dev is None else dev
        busy = self.busy_intervals(dev)
        gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:2000]
        host = sorted(self.host_events, key=lambda e: e[1])
        starts = [e[1] for e in host]
        import bisect

        out = defaultdict(float)
        for s, e in gaps:
            mid = (s + e) / 2
            i = bisect.bisect_right(starts, mid)
            best = None
            for name, hs, hd in reversed(host[max(0, i - 400):i]):
                if hs <= mid <= hs + hd and (best is None or hd < best[1]):
                    best = (name, hd)
            out[best[0] if best else "no_host_event"] += e - s
        return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])[:n]]


_HLO = re.compile(r"^%(\S+) = (\(?[a-z0-9]+\[[^ ]*)")


def op_label(name: str) -> str:
    """A short name that adds up the layers' copies of one operation. On
    the TPU an event's name is its HLO text (``%fusion.12 = bf16[8,1600]{..}
    fusion(...)``): the label is the instruction's name without its number
    and its (first) result shape, which tells a whole-pool copy from a
    row's. Any other name only loses its trailing number."""
    m = _HLO.match(name)
    if not m:
        return _display(name)
    shape = re.sub(r"\{[^}]*\}?", "", m.group(2))
    shape = re.sub(r"[^A-Za-z0-9]+", "_", shape).strip("_")
    return f"{re.sub(r'[.]\d+', '', m.group(1))}_{shape}"[:64]


def _dump(path: str) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events))
            for ev in events[:3]:
                print("    ", ev.name, ev.start_ns, ev.duration_ns,
                      [(k, str(v)[:120]) for k, v in ev.stats][:12])
    tr = Trace.from_file(path)
    print("busy_s", tr.busy_seconds())
    print("modules", tr.module_seconds())
    print("top", tr.top_ops(15))
    print("gaps", tr.idle_gaps())


if __name__ == "__main__":
    import sys

    _dump(sys.argv[1] if sys.argv[1].endswith(".pb") else find_xplane(sys.argv[1]))
