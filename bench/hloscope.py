"""Which named scope each device op of a compiled program ran under.

The ops of a program carry the `jax.named_scope`s of the code that made
them in their HLO metadata, `metadata={op_name="jit(serve_decode)/while/
body/mla/dot_general" ...}`, and the compiler keeps it on the ops it
fuses. `op_scopes` maps each instruction of a compiled module's text
(`jax.stages.Compiled.as_text()`) to that path. The device trace names each
op event by its instruction (`%fusion.3 = bf16[...] fusion(...)`), so
`share` can sum the device time of the ops under a scope.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

import devtrace
import progtrace

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([^\s=]+) = [^\n]*?\bop_name="([^"]*)"',
                    re.M)


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """instruction name -> op_name path, for every instruction that has one."""
    return {m.group(1): m.group(2) for m in _INSTR.finditer(hlo_text)}


def instruction(event_name: str) -> str:
    """`%fusion.3 = bf16[8]{0} fusion(...)` (or `fusion.3 bf16[8]`, as
    `devtrace.short_op` writes it) -> `fusion.3`."""
    return event_name.lstrip().lstrip("%").split(" ", 1)[0]


def under(path: str, scope: str) -> bool:
    """Whether an op_name path lies under `scope` or a scope named
    `<scope>.<part>`."""
    return any(p == scope or p.startswith(scope + ".")
               for p in path.split("/"))


def share(pt: progtrace.Reduced, program: str, scopes: Dict[str, str],
          scope: str) -> Optional[float]:
    """Device time of the leaf ops under `scope` inside the runs of the
    programs named `program`, over the busy time of those runs, in %. None
    where the trace holds no such run or the scopes are unknown."""
    runs = progtrace.union([(r.start, r.end) for r in pt.runs(program)])
    busy = progtrace.length(progtrace.intersect(pt.busy, runs))
    if not scopes or busy <= 0:
        return None
    ops = progtrace.union([
        (e.start, e.end) for e in devtrace.leaves(pt.ops)
        if under(scopes.get(instruction(e.name), ""), scope)])
    return 100.0 * progtrace.length(progtrace.intersect(ops, runs)) / busy
