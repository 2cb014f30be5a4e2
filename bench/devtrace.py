"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes an XSpace (`*.xplane.pb`). On a TPU each chip is a
plane `/device:TPU:<n>` with a line `XLA Modules` (one event per program
run: `jit_<function>(<hash>)`) and a line `XLA Ops` (one event per HLO op;
a Pallas kernel is a `tpu_custom_call`, with no kernel name of its own).
The host plane `/host:CPU` carries the benchmark's own spans
(`jax.profiler.TraceAnnotation`, named `bench.*`) on the same clock.

Everything here is measured inside the span `bench.window`, which the
drivers put around the traced window:

- busy time: the union of a chip's op intervals, averaged over the chips;
- kernel time: the summed durations of custom calls inside the programs
  whose name starts with a given prefix;
- idle gaps: the complement of the busy union, each attributed to the
  innermost `bench.*` span that covers its midpoint;
- device time by op: the summed durations of the leaf ops (a `while` op
  spans its body's ops, which the trace lists too).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OUTSIDE = "(no bench span)"


@dataclasses.dataclass
class Ev:
    name: str
    start: float    # ns
    end: float      # ns


@dataclasses.dataclass
class Device:
    ops: List[Ev]
    modules: List[Ev]


@dataclasses.dataclass
class Trace:
    devices: Dict[str, Device]
    spans: List[Ev]

    @staticmethod
    def from_json(obj: dict) -> "Trace":
        """From {"devices": {plane: {"ops": [[name, start, end], ...],
        "modules": [...]}}, "spans": [...]} (the recorded test trace)."""
        ev = lambda rows: [Ev(n, float(a), float(b)) for n, a, b in rows]  # noqa: E731
        return Trace({k: Device(ev(d["ops"]), ev(d["modules"]))
                      for k, d in obj["devices"].items()}, ev(obj["spans"]))


def load_xspace(pd) -> Trace:
    """A `jax.profiler.ProfileData` as a Trace."""
    devices: Dict[str, Device] = {}
    spans: List[Ev] = []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            dev = Device([], [])
            for line in plane.lines:
                target = {"XLA Ops": dev.ops,
                          "XLA Modules": dev.modules}.get(line.name)
                if target is not None:
                    target.extend(Ev(e.name, e.start_ns,
                                     e.start_ns + e.duration_ns)
                                  for e in line.events)
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Ev(e.name, e.start_ns,
                                e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(devices, spans)


def load_dir(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return load_xspace(ProfileData.from_file(files[-1]))


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(evs: List[Ev], lo: float, hi: float) -> List[Ev]:
    return [Ev(e.name, max(e.start, lo), min(e.end, hi))
            for e in evs if e.end > lo and e.start < hi]


def leaves(evs: List[Ev]) -> List[Ev]:
    """The events that hold no other event: a `while` op spans every op of
    its body, which the trace also lists, so only leaves are summed."""
    evs = sorted(evs, key=lambda e: (e.start, -e.end))
    parent = [False] * len(evs)
    stack: List[int] = []
    for i, e in enumerate(evs):
        while stack and evs[stack[-1]].end <= e.start:
            stack.pop()
        if stack and e.end <= evs[stack[-1]].end:
            parent[stack[-1]] = True
        stack.append(i)
    return [e for e, p in zip(evs, parent) if not p]


def short_module(name: str) -> str:
    """`jit_fn(1234)` -> `jit_fn`: the program name without its hash."""
    return name.split("(", 1)[0]


def short_op(name: str) -> str:
    """`%fusion.3 = bf16[8,128]{...} fusion(...)` -> `fusion.3 bf16[8,128]`."""
    head, _, rest = name.partition(" = ")
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return head.lstrip("%") + (" " + shape.group(1) if shape else "")


class Reduced:
    """One traced window, reduced."""

    def __init__(self, t: Trace):
        wins = [s for s in t.spans if s.name == WINDOW_SPAN]
        if not wins:
            raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
        if not t.devices:
            raise ValueError("the trace holds no TPU device plane")
        self.lo = min(w.start for w in wins)
        self.hi = max(w.end for w in wins)
        self.window_s = (self.hi - self.lo) * 1e-9
        self.devices = {k: Device(clip(d.ops, self.lo, self.hi),
                                  clip(d.modules, self.lo, self.hi))
                        for k, d in t.devices.items()}
        self.spans = clip([s for s in t.spans if s.name != WINDOW_SPAN],
                          self.lo, self.hi)
        self.busy = {k: union([(e.start, e.end) for e in d.ops])
                     for k, d in self.devices.items()}
        used = [k for k, iv in self.busy.items() if iv] or list(self.busy)
        self.used = used
        self.busy_s = sum(sum(b - a for a, b in self.busy[k])
                          for k in used) * 1e-9 / len(used)

    @property
    def idle_share(self) -> Optional[float]:
        if self.window_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def kernel_seconds(self, module_prefix: str,
                       op_marker: str = "custom-call(") -> float:
        """Summed device time of the custom calls (Pallas kernels) inside
        programs whose name starts with `module_prefix`, over the chips."""
        total = 0.0
        for k in self.used:
            d = self.devices[k]
            mods = sorted((m.start, m.end) for m in d.modules
                          if m.name.startswith(module_prefix))
            if not mods:
                continue
            starts = [a for a, _ in mods]
            for op in d.ops:
                if op_marker not in op.name:
                    continue
                i = bisect.bisect_right(starts, op.start) - 1
                if i >= 0 and op.end <= mods[i][1] + 1.0:
                    total += op.end - op.start
        return total * 1e-9

    def gaps(self) -> List[Tuple[float, float]]:
        """Idle intervals of the first chip used, inside the window."""
        busy = self.busy[self.used[0]]
        out, t = [], self.lo
        for a, b in busy:
            if a > t:
                out.append((t, a))
            t = max(t, b)
        if self.hi > t:
            out.append((t, self.hi))
        return out

    def attribute(self, t_ns: float) -> str:
        """The innermost bench span covering t: of nested spans, the one
        that started last."""
        if not hasattr(self, "_by_start"):
            self._by_start = sorted(self.spans, key=lambda s: s.start)
            self._starts = [s.start for s in self._by_start]
        for i in range(bisect.bisect_right(self._starts, t_ns) - 1, -1, -1):
            if self._by_start[i].end >= t_ns:
                return self._by_start[i].name
        return OUTSIDE

    def breakdown(self, top: int = 10) -> dict:
        by_op: Dict[str, float] = {}
        k = self.used[0]
        d = self.devices[k]
        mods = sorted((m.start, m.end, short_module(m.name))
                      for m in d.modules)
        starts = [m[0] for m in mods]
        for op in leaves(d.ops):
            i = bisect.bisect_right(starts, op.start) - 1
            mod = mods[i][2] if i >= 0 and op.end <= mods[i][1] + 1 else "?"
            key = f"{mod}:{short_op(op.name)}"[:120]
            by_op[key] = by_op.get(key, 0.0) + (op.end - op.start) * 1e-9
        by_gap: Dict[str, float] = {}
        for a, b in self.gaps():
            key = self.attribute((a + b) / 2)
            by_gap[key] = by_gap.get(key, 0.0) + (b - a) * 1e-9
        order = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
        return {"device_ops": [[n, s] for n, s in order(by_op)],
                "idle_gaps": [[n, s] for n, s in order(by_gap)]}


def reduce_dir(trace_dir: str) -> Reduced:
    return Reduced(load_dir(trace_dir))

