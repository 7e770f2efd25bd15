"""Device time of the step program by device scope (`tracing.scope`), in ms a
traced step, by ONE path for every cell: the cell's newest trace under
`output/benchmarks/trace/<cell>` (as `program_spans.traced_spans` finds it) and
the step program's compiled text, which the program keeps whenever somebody
lowers the step ahead of time (`tracing.program_text('task.step_call')`: every
runner does, for the step's memory plan, after a traced window).

Beside `device_scopes.reduce_scopes`, whose instruction -> scope mapping this
file uses: a window may hold more programs than the step (the image cells'
loader dispatches its augment program once a step, and its instructions are
called `fusion.3` like the step's), so ops are taken for the step program only
inside the intervals its module has on the device plane's `XLA Modules` line,
and the other programs' time is a row of its own, in no scope. And every number
is ms a traced step, the cover a union of intervals over a union of intervals
(a scope's row is the union of ITS ops' intervals: async copies of one scope may
run under another's ops, so rows can add up to a little more than the cover).

XLA gives a fusion the `op_name` of its root: a row is "the fusions whose root
is under the scope", and a product fused with the next scope's elementwise tail
is booked there. So rows are device time, and no share of a peak. What a row
hides is in the text too: `fused_scopes` gives, for every instruction that calls
a computation, the scopes of the instructions fused into it, and each row says
how much of its time ran in fusions that ALSO hold ops of which other scope (a
LayerNorm fused into the product that reads it, an optimizer update fused into
the weight gradient's product). Nothing is re-booked by that: it is printed.

A record without a trace, a cell or a step count (a hand-written one, an
untraced run), a program without `tracing.program_text` (a parent older than
it), a process in which nobody lowered the step, a text whose module the trace
does not hold and a text without a single declared scope (read from a compile
cache that an older program filled: one plain line says so) all give None;
nothing here raises for those.
"""
from __future__ import annotations

import bisect
import functools
import os
import re

from . import device_scopes, trace
from .manifest import REPO_ROOT
from .program_spans import _tracing
from .swa_lm_readers import declared_scopes      # every name `tracing.SPANS` declares as a device scope

STEP_PROGRAM = 'task.step_call'      # the declared span that dispatches the step program: the key of its kept text
MODULE_LINE = 'XLA Modules'
MODULE = re.compile(r'^HloModule\s+([\w.\-]+)', re.M)
COMPUTATION = re.compile(r'^(?:ENTRY\s+)?%([\w.\-]+)\s+\(.*\)\s+->\s+.*\{\s*$')     # where a computation's body starts
CALLS = re.compile(r'\bcalls=%([\w.\-]+)')
PRINTED = ('img.block',)             # a row of `tools/scope_report.py`'s table and no metric: what no inner scope takes


def module_name(hlo_text: str):
    found = MODULE.search(hlo_text)
    return found.group(1) if found else None


def read_modules(path: str) -> list:
    """(program name, start_ns, end_ns) of every program run on the first device plane, from its module-level
    line, whose events are named `<module>(<fingerprint>)`."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace.DEVICE_PLANE):
            return [(e.name.split('(', 1)[0], e.start_ns, e.start_ns + e.duration_ns)
                    for line in plane.lines if line.name == MODULE_LINE for e in line.events]
    return []


def fused_scopes(hlo_text: str, names) -> dict:
    """instruction name -> the declared scopes of the instructions fused into it: the innermost scopes of every
    instruction of the computation it `calls=`, and of the computations those call."""
    direct, callees, called_by, current = {}, {}, {}, None
    for line in hlo_text.splitlines():
        head = COMPUTATION.match(line)
        if head:
            current = head.group(1)
            direct[current], callees[current] = set(), set()
            continue
        inst = device_scopes.INSTRUCTION.match(line)
        if inst is None or current is None:
            continue
        op = device_scopes.OP_NAME.search(line)
        scope = device_scopes.scope_of(op.group(1), names) if op else None
        if scope:
            direct[current].add(scope)
        callee = CALLS.search(line)
        if callee:
            callees[current].add(callee.group(1))
            called_by[inst.group(1)] = callee.group(1)

    @functools.lru_cache(maxsize=None)
    def held(computation: str) -> frozenset:
        return frozenset(direct.get(computation, ())).union(*(held(c) for c in callees.get(computation, ())))

    return {inst: held(callee) for inst, callee in called_by.items()}


def split_by_program(ops, modules, name: str):
    """(ops that start inside an interval of program `name`, all the others): two programs may call their
    instructions alike, and only the one whose text is read may be booked by instruction name."""
    runs = sorted((s, e) for n, s, e in modules if n == name)
    starts = [s for s, _ in runs]
    mine, others = [], []
    for op in ops:
        i = bisect.bisect_right(starts, op[1]) - 1
        (mine if i >= 0 and op[1] < runs[i][1] else others).append(op)
    return mine, others


def _overlap(merged, starts, s, e) -> int:
    """ns of (s, e) inside the sorted, merged intervals."""
    total, i = 0, max(bisect.bisect_right(starts, s) - 1, 0)
    while i < len(merged) and merged[i][0] < e:
        total += max(0, min(e, merged[i][1]) - max(s, merged[i][0]))
        i += 1
    return total


def reduce(ops, modules, program: str, scopes: dict, window=None, steps: int = 1, fused: dict = None):
    """The whole reduction, on tuples: `ops` and `modules` are (name, start_ns, end_ns) of one device, `program`
    the step program's module name, `scopes` its instruction -> scope, `window` (start_ns, end_ns) clips the ops.
    -> {'scope_ms': scope -> ms a step (union of its ops' intervals), 'busy_ms': the step program's busy time,
    'covered_ms': of it under any scope, 'cover': their ratio in %, 'outside': the ten op families with most time
    under no scope, as [family, ms a step] of what no scoped op covers, 'other_programs_ms': busy time of the ops
    outside the step program's intervals, 'holds': scope -> {another scope: ms a step of the scope's ops whose
    fusion also holds ops of that other scope}, from `fused` (`fused_scopes`), 'families': scope -> {op family: ms
    a step}}; None where the trace holds no op of that program."""
    if window is not None:
        w0, w1 = window
        ops = [(n, max(s, w0), min(e, w1)) for n, s, e in ops if e > w0 and s < w1]
    mine, others = split_by_program(ops, modules, program)
    if not mine or not steps:
        return None
    by_scope, unscoped, holds, families = {}, [], {}, {}
    for name, s, e in mine:
        instruction = device_scopes.instruction_of(name)
        scope = scopes.get(instruction)
        if scope is None:
            unscoped.append((trace.op_family(name), s, e))
            continue
        by_scope.setdefault(scope, []).append((s, e))
        row = families.setdefault(scope, {})
        row[trace.op_family(name)] = row.get(trace.op_family(name), 0) + (e - s) / 1e6 / steps
        for other in (fused or {}).get(instruction, ()):
            if other != scope:
                row = holds.setdefault(scope, {})
                row[other] = row.get(other, 0) + (e - s) / 1e6 / steps
    ms = lambda intervals: sum(e - s for s, e in trace.union(intervals)) / 1e6 / steps  # noqa: E731
    covered = trace.union(iv for v in by_scope.values() for iv in v)
    starts = [s for s, _ in covered]
    outside = {}
    for family, s, e in unscoped:
        outside[family] = outside.get(family, 0) + (e - s) - _overlap(covered, starts, s, e)
    busy_ms, covered_ms = ms((s, e) for _, s, e in mine), ms(covered)
    return {'scope_ms': {k: ms(v) for k, v in by_scope.items()}, 'busy_ms': busy_ms, 'covered_ms': covered_ms,
            'cover': 100.0 * covered_ms / busy_ms,
            'outside': sorted(([k, v / 1e6 / steps] for k, v in outside.items() if v > 0), key=lambda kv: -kv[1])[:trace.TOP],
            'other_programs_ms': ms((s, e) for _, s, e in others), 'holds': holds, 'families': families}


@functools.lru_cache(maxsize=2)
def _reduced(path: str, mtime: float, hlo_text: str, steps: int):
    """`reduce` of one trace file's first device inside `bench.window`; `mtime` only keys the cache."""
    scopes = device_scopes.instruction_scopes(hlo_text, declared_scopes())
    if not scopes:
        print("step scopes: the step program's text holds no declared scope: compiled before them? "
              '(a compile cache that an older program filled keeps its op names)', flush=True)
        return None
    devices, spans = trace.read_planes(path)
    if not devices:
        return None
    marks = [(s, e) for name, s, e in spans if name == 'window']
    window = (min(s for s, _ in marks), max(e for _, e in marks)) if marks else None
    return reduce(next(iter(devices.values())), read_modules(path), module_name(hlo_text), scopes, window, steps,
                  fused_scopes(hlo_text, declared_scopes()))


def scopes_of(run: dict):
    """`reduce` for a run's record: its cell's newest trace and the step program's kept text; None without either."""
    tracing = _tracing()
    steps = (run.get('trace') or {}).get('work')
    if tracing is None or not hasattr(tracing, 'program_text') or not steps or not run.get('cell'):
        return None
    text = tracing.program_text(STEP_PROGRAM)
    if not text:
        return None
    try:
        path = trace.newest_xplane(os.path.join(REPO_ROOT, 'output', 'benchmarks', 'trace', run['cell']))
    except FileNotFoundError:
        return None
    return _reduced(path, os.path.getmtime(path), text, steps)


def scope_ms(run: dict, *scopes):
    """Device ms a traced step under these scopes, their rows added; None where the run's step has none of them."""
    found = scopes_of(run)
    rows = [found['scope_ms'][s] for s in scopes if s in found['scope_ms']] if found else []
    return sum(rows) if rows else None


def cover(run: dict):
    """% of the step program's busy device time that lies under ANY declared scope; never over 100."""
    found = scopes_of(run)
    return found['cover'] if found else None


def table(run: dict) -> list:
    """One line a scope (ms a traced step, % of the step program's busy time, how much of it ran in fusions that
    also hold ops of which other scope, its five largest op families), the cover, the other programs, and the op
    families that took most time outside every scope."""
    found = scopes_of(run)
    if not found:
        return ['step scopes: nothing to read']
    busy = found['busy_ms']
    lines = []
    for k, v in sorted(found['scope_ms'].items(), key=lambda kv: -kv[1]):
        also = ', '.join(f'{o} {t:.3f}' for o, t in sorted(found['holds'].get(k, {}).items(), key=lambda kv: -kv[1]))
        top = sorted(found['families'].get(k, {}).items(), key=lambda kv: -kv[1])[:5]
        lines.append(f'step scope {k}: {v:.3f} ms a step, {100 * v / busy:.2f} % of busy' + (f'; in fusions that also hold {also}' if also else '')
                     + '; by op family: ' + ', '.join(f'{f} {t:.3f}' for f, t in top))
    lines.append(f'step program: {busy:.3f} ms a step busy, {found["covered_ms"]:.3f} under a scope = cover {found["cover"]:.2f} %; '
                 f'other programs {found["other_programs_ms"]:.3f} ms a step')
    lines.append('outside every scope, ms a step: ' + (', '.join(f'{k} {v:.3f}' for k, v in found['outside']) or 'nothing'))
    return lines
