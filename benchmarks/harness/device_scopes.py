"""Device time by the program's device scopes (`tracing.scope`, a
`jax.named_scope`), from a profiler trace and the step program's compiled text.

The trace's `XLA Ops` events carry an instruction's HLO line but not its
metadata, so the scope comes from the compiled module: every instruction's
`op_name` holds the scopes it was traced under, autodiff and remat wrappers
around them (`transpose(jvp(glm.mla.core))`, `checkpoint/glm.moe.route/..`); the
innermost declared scope is the instruction's. XLA's rewrite of
`lax.ragged_dot` into its TPU kernel drops the `op_name` (it reads
`ragged-dot-none`): those instructions are the grouped products, the one user
of `ragged_dot` in the program, and go to `RAGGED_DOT_SCOPE`.

A program without the scopes (a parent older than them) gives empty results;
nothing here raises for that.
"""
from __future__ import annotations

import re

from . import trace

INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+) = ', re.M)          # where an instruction's text starts
OP_NAME = re.compile(r'(?<![\w])metadata=\{[^}]*?op_name="([^"]*)"')
SCOPE_TOKEN = re.compile(r'[a-z]+(?:\.[a-z_]+)+')
RAGGED_DOT_SCOPE = 'glm.moe.experts'
# every device scope the program declares -> the part of `lm_flops.forward_macs` computed under it
SCOPE_PARTS = {'glm.embed': None, 'glm.mla.proj': 'mla_proj', 'glm.mla.core': 'mla_core', 'glm.dense_ffn': 'dense_ffn',
               'glm.moe.route': 'moe_route', 'glm.moe.experts': 'moe_experts', 'glm.moe.shared': 'moe_shared',
               'glm.mtp': 'mtp_proj', 'glm.head_loss': 'head'}


def declared_scopes() -> set:
    """The names `tracing.SPANS` declares as device scopes."""
    try:
        from timm_tpu.utils import tracing
    except ImportError:
        return set()
    return {name for name, (_, what) in tracing.SPANS.items() if what.startswith('device scope')}


def scope_of(op_name: str, names) -> str | None:
    """The innermost of `names` in an instruction's `op_name`."""
    found = [t for t in SCOPE_TOKEN.findall(op_name) if t in names]
    return found[-1] if found else None


def instruction_scopes(hlo_text: str, names) -> dict:
    """instruction name (without %) -> scope, for the instructions that have one."""
    out = {}
    starts = list(INSTRUCTION.finditer(hlo_text))
    for m, following in zip(starts, starts[1:] + [None]):
        # an instruction's text may run over several lines (a Pallas call's attributes hold a multi-line string)
        found = OP_NAME.search(hlo_text, m.end(), following.start() if following else len(hlo_text))
        if found:
            scope = RAGGED_DOT_SCOPE if m.group(1).startswith('ragged-dot') and RAGGED_DOT_SCOPE in names \
                else scope_of(found.group(1), names)
            if scope:
                out[m.group(1)] = scope
    return out


def instruction_of(event_name: str) -> str:
    """`%fusion.18 = (f32[..]) fusion(..)` -> `fusion.18`."""
    return event_name.split(' = ', 1)[0].strip().lstrip('%')


def reduce_scopes(path: str, hlo_text: str, names=None) -> dict:
    """-> {'scope_s': scope -> busy seconds (union of its ops' intervals) inside `bench.window` on the first
    device, 'busy_s': all ops', 'unscoped': the ten op families that took most time outside every scope}."""
    names = declared_scopes() if names is None else names
    devices, spans = trace.read_planes(path)
    if not devices or not names:
        return {'scope_s': {}, 'busy_s': 0.0, 'unscoped': []}
    scopes = instruction_scopes(hlo_text, names)
    marks = [(s, e) for name, s, e in spans if name == 'window']
    ops = next(iter(devices.values()))
    w0, w1 = (min(s for s, _ in marks), max(e for _, e in marks)) if marks else \
        (min(s for _, s, _ in ops), max(e for _, _, e in ops))
    by_scope, outside = {}, {}
    for name, s, e in ops:
        s, e = max(s, w0), min(e, w1)
        if e <= s:
            continue
        scope = scopes.get(instruction_of(name))
        if scope is None:
            family = trace.op_family(name)
            outside[family] = outside.get(family, 0) + (e - s)
        by_scope.setdefault(scope, []).append((s, e))
    seconds = {k: sum(e - s for s, e in trace.union(v)) / 1e9 for k, v in by_scope.items()}
    busy = sum(e - s for s, e in trace.union(iv for v in by_scope.values() for iv in v)) / 1e9
    top = sorted(([k, v / 1e9] for k, v in outside.items()), key=lambda kv: -kv[1])[:trace.TOP]
    return {'scope_s': {k: v for k, v in seconds.items() if k is not None}, 'busy_s': busy, 'unscoped': top}


def scope_ms(run: dict, *prefixes):
    """ms a traced step under the scopes that start with one of `prefixes`; None where the run has none."""
    scopes = (run.get('trace') or {}).get('scopes') or {}
    work = (run.get('trace') or {}).get('work')
    rows = [v for k, v in scopes.get('scope_s', {}).items() if k.startswith(prefixes)]
    if not rows or not work:
        return None
    return sum(rows) / work * 1e3


def scope_share(run: dict, *prefixes):
    """% of the traced window's busy device time under those scopes."""
    scopes = (run.get('trace') or {}).get('scopes') or {}
    rows = [v for k, v in scopes.get('scope_s', {}).items() if k.startswith(prefixes)]
    if not rows or not scopes.get('busy_s'):
        return None
    return 100.0 * sum(rows) / scopes['busy_s']


def counter_mean(run: dict, name: str):
    """Mean over the window's steps of a step counter; None where the run has none."""
    rows = (run.get('counters') or {}).get(name)
    return sum(rows) / len(rows) if rows else None


def part_mfu(run: dict, scope: str, part: str):
    """% of the chip's bfloat16 peak that the needed operations of one part of the step (the record's
    `needed_macs[part]`, which the cell's runner put there from its family's operation table; forward and
    backward) make over the device time under `scope`: the part's roofline share (these parts are matrix products:
    compute-bound). None where the record has no such part or no time under the scope: another family's, an
    image cell's, a parent older than the scopes."""
    from . import lm_flops, peaks
    macs, ms = (run.get('needed_macs') or {}).get(part), scope_ms(run, scope)
    if macs is None or not ms:
        return None
    return 100.0 * lm_flops.train_flops(macs) / (ms / 1e3) / peaks.peak(run['device_kind'])['bf16_flops']


def scope_table(run: dict, parts: dict = None) -> list:
    """One line a scope of `parts` (scope -> its part of the needed operations; the GLM family's where none is
    given): ms a traced step, share of busy time, roofline share of its part; then the cover."""
    parts = SCOPE_PARTS if parts is None else parts
    scopes = (run.get('trace') or {}).get('scopes') or {}
    if not scopes.get('scope_s'):
        return ['device scopes: none in the trace']
    lines = []
    for scope, part in parts.items():
        ms, share, mfu = scope_ms(run, scope), scope_share(run, scope), part_mfu(run, scope, part)
        if ms is not None:
            lines.append(f'device scope {scope}: {ms:.2f} ms a step, {share:.1f} % of busy'
                         + (f', {mfu:.1f} % of peak on its needed operations' if mfu is not None else ''))
    lines.append(f'device scopes cover {scope_share(run, *parts):.1f} % of busy device time; outside them: '
                 + ', '.join(f'{k} {v * 1e3:.1f} ms' for k, v in scopes['unscoped'][:5]))
    return lines
