"""Attribute the device operations of a traced window to the program's
named scopes, from the compiled programs' op metadata.

The program wraps each part of a layer in ``jax.named_scope`` (the Gated
DeltaNet mixer in ``gdn``, gated attention in ``gated_attn``, the expert
layer in ``moe`` with ``moe.route`` and ``moe.experts`` inside it).  XLA
keeps the scope path in each instruction's ``metadata={op_name="..."}``,
and the device trace names each operation it ran after its instruction
(``%fusion.99``).  So the compiled program's HLO text (``as_text()``)
maps operation names to scopes (:func:`scope_table`), and
:func:`scope_seconds` sums, per scope, the device time of the operations
under it inside program runs of the traced steps: the union of their
intervals on each device (an operation inside a loop nests in the loop's
own event), summed over devices.

:func:`scope_roofline` is the kernel roofline of one scope: the least time
of the work in that scope over the traced steps (``run.shapes``'s
``scope_prefill`` for each prefill, ``scope_decode`` for each lane's
decode call) over the device time of its operations.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Mapping, Optional, Sequence, Tuple

from bench import trace_reduce
from bench.trace_reduce import Span, TraceData

SCOPES = ("gdn", "gated_attn", "moe", "moe.route", "moe.experts")
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=.*?\bop_name="([^"]*)"')


def scope_table(hlo_text: str, scopes: Sequence[str] = SCOPES) -> Dict[str, Tuple[str, ...]]:
    """Instruction name -> the scopes on its op_name path, for every
    instruction of ``hlo_text`` under at least one of ``scopes``."""
    table: Dict[str, Tuple[str, ...]] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        parts = m.group(2).split("/")
        hit = tuple(s for s in scopes if s in parts)
        if hit:
            table[m.group(1)] = hit
    return table


def op_key(event_name: str) -> str:
    """``%fusion.99 = ...`` -> ``fusion.99``."""
    return event_name.split(" = ")[0].strip().lstrip("%")


def scope_seconds(td: TraceData, tables: Mapping[str, Mapping[str, Tuple[str, ...]]],
                  spans: Sequence[Span]) -> Dict[str, float]:
    """Device seconds under each scope of the operations of program runs
    (of the programs ``tables`` names) that start inside one of ``spans``."""
    bounds = sorted((s.start_ns, s.end_ns) for s in spans)
    found: Dict[Tuple[str, str], list] = defaultdict(list)
    for dev, mods in td.modules.items():
        runs = []
        for m in mods:
            name = trace_reduce.program_name(m.name)
            if name in tables and any(a <= m.start_ns <= b for a, b in bounds):
                runs.append((m.start_ns, m.end_ns, tables[name]))
        runs.sort(key=lambda r: r[0])
        j = 0
        for op in sorted(td.ops.get(dev, []), key=lambda s: s.start_ns):
            while j < len(runs) and runs[j][1] < op.start_ns:
                j += 1
            if j == len(runs):
                break
            start, end, table = runs[j]
            if op.start_ns < start:
                continue
            for scope in table.get(op_key(op.name), ()):
                found[(dev, scope)].append((op.start_ns, min(op.end_ns, end)))
    out: Dict[str, float] = defaultdict(float)
    for (_, scope), intervals in found.items():
        out[scope] += sum(b - a for a, b in trace_reduce.union(intervals)) * 1e-9
    return dict(out)


def scope_roofline(run, scope: str) -> Optional[float]:
    """Least time of ``scope``'s work in the traced steps over the device
    time of its operations, in percent; None without a trace or ops."""
    device = getattr(run, "scope_s", None) or {}
    if device.get(scope, 0.0) <= 0.0:
        return None
    pairs = run.traced_steps()
    if not pairs:
        return None
    s = run.shapes
    least = 0.0
    for step, _ in pairs:
        least += sum(run.least_seconds(*s.scope_prefill(scope, n)) for n in step.prefills)
        least += len(step.contexts) * run.least_seconds(*s.scope_decode(scope))
    return 100.0 * least / device[scope]
