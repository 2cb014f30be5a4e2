"""The program's own spans and the device's programs in a profiler trace.

While the profiler records, every `repro.obs.trace.span` the program opens
is a host event on the plane `/host:CPU`, on the device ops' clock, with
its attrs as the event's stats: the serving engine's `serve.wave`,
`serve.prefill` (rows, width, real_tokens, padded_tokens), `serve.step`
(step, active_rows) and the step's phases. `devtrace.py` keeps only the
bench's own `bench.*` spans; this module keeps the program's, with their
stats, and the ops and program runs (`XLA Ops`, `XLA Modules`) of the first
chip used, all clipped to the span `bench.window`.

The trace is read from `out.trace_dir` once per process (`for_outcome`).
A program without these spans or names (an older checkout) leaves the
readers nothing to read: each then returns None.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

import devtrace
from devtrace import Ev, clip, union

WINDOW_SPAN = devtrace.WINDOW_SPAN
SPAN_PREFIXES = ("serve.",)        # the program's spans a reader reads
# the Pallas kernels' `pallas_call` names: the op of each is `%<name>.N`
KERNELS = ("matmul", "flash_attention", "rg_lru")
_KERNEL_OP = re.compile(r"(?:^|\s)%%(?:%s)(?:\.\d+)? = .*custom-call\("
                        % "|".join(KERNELS))

Interval = Tuple[float, float]


@dataclasses.dataclass
class Span:
    name: str
    start: float    # ns
    end: float      # ns
    attrs: Dict[str, object]


@dataclasses.dataclass
class Trace:
    spans: List[Span]       # the program's spans and `bench.window`
    ops: List[Ev]           # the first chip used; none without a chip
    modules: List[Ev]

    @staticmethod
    def from_json(obj: dict) -> "Trace":
        """From {"spans": [[name, start, end, {attrs}], ...], "ops":
        [[name, start, end], ...], "modules": [...]} (the tests' traces)."""
        ev = lambda rows: [Ev(n, float(a), float(b)) for n, a, b in rows]  # noqa: E731
        return Trace([Span(n, float(a), float(b), dict(s))
                      for n, a, b, s in obj["spans"]],
                     ev(obj.get("ops", [])), ev(obj.get("modules", [])))


def load_xspace(pd) -> Trace:
    """A `jax.profiler.ProfileData` as a Trace."""
    spans: List[Span] = []
    chips: Dict[str, Tuple[List[Ev], List[Ev]]] = {}
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            ops: List[Ev] = []
            modules: List[Ev] = []
            for line in plane.lines:
                target = {"XLA Ops": ops,
                          "XLA Modules": modules}.get(line.name)
                if target is not None:
                    target.extend(Ev(e.name, e.start_ns,
                                     e.start_ns + e.duration_ns)
                                  for e in line.events)
            chips[plane.name] = (ops, modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN or e.name.startswith(
                            SPAN_PREFIXES):
                        spans.append(Span(e.name, e.start_ns,
                                          e.start_ns + e.duration_ns,
                                          dict(e.stats)))
    used = sorted(chips, key=lambda k: int(k.rsplit(":", 1)[1]))
    used = [k for k in used if chips[k][0]] or used
    ops, modules = chips[used[0]] if used else ([], [])
    return Trace(spans, ops, modules)


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


class Reduced:
    """One traced window: the program's spans with their stats, and the
    first chip's ops and program runs, clipped to `bench.window`."""

    def __init__(self, t: Trace):
        wins = [s for s in t.spans if s.name == WINDOW_SPAN]
        if not wins:
            raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
        self.lo = min(w.start for w in wins)
        self.hi = max(w.end for w in wins)
        self.spans = [Span(s.name, max(s.start, self.lo), min(s.end, self.hi),
                           s.attrs)
                      for s in t.spans if s.name != WINDOW_SPAN
                      and s.end > self.lo and s.start < self.hi]
        self.ops = clip(t.ops, self.lo, self.hi)
        self.modules = clip(t.modules, self.lo, self.hi)
        self.busy = union([(e.start, e.end) for e in self.ops])

    @property
    def has_device(self) -> bool:
        return bool(self.ops)

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def runs(self, prefix: str) -> List[Ev]:
        """The runs of programs whose name starts with `prefix`."""
        return [m for m in self.modules if m.name.startswith(prefix)]

    def busy_ns(self, within: List[Interval]) -> float:
        """Device busy time (the union of op intervals) inside `within`."""
        return length(intersect(self.busy, union(within)))

    def idle_ns(self, within: List[Interval]) -> float:
        """Device idle time inside `within`."""
        w = union(within)
        return length(w) - length(intersect(self.busy, w))

    def kernel_ns(self, within: List[Interval]) -> float:
        """Device time of the named Pallas kernels inside `within`."""
        kern = union([(e.start, e.end) for e in self.ops
                      if _KERNEL_OP.search(e.name)])
        return length(intersect(kern, union(within)))


@functools.lru_cache(maxsize=4)
def reduce_dir(trace_dir: str) -> Reduced:
    """The newest `*.xplane.pb` under `trace_dir`, as devtrace reads it."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return Reduced(load_xspace(ProfileData.from_file(files[-1])))


def for_outcome(out) -> Optional[Reduced]:
    """The traced window of a run, read once per process; None for a run
    with no trace."""
    return reduce_dir(out.trace_dir) if out.trace_dir else None
