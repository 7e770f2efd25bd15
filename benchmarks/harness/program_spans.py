"""The program's own spans (`timm_tpu/utils/tracing.py`) reduced for the
per-layer readers, from two places:

  * the program's ring, read in this process after the run: `window(run)` is
    the window's `train.step` roots with everything recorded under them,
    `setup(window)` what ended before the first of them;
  * the profiler's trace of a `--trace 1` run, where the same spans sit in a
    host plane on the device planes' clock: `idle_by_layer(run)` lays the
    device's idle gaps over them.

A program that has no `tracing` module (a parent older than its spans), a
run whose inner step was replaced (no `task.train_step` under the roots) or a
run without a trace gives `None`; nothing here raises for those.
"""
from __future__ import annotations

import functools
import os
import statistics

from . import trace
from .manifest import REPO_ROOT

STEP = ('task.train_step',)
INPUT = ('train.loader_next', 'train.batch_to_device')
LOOP = ('train.step', 'train.bookkeeping', 'train.log_sync')


def _tracing():
    try:
        from timm_tpu.utils import tracing
    except ImportError:
        return None
    return tracing


def wall_ms(span) -> float:
    return (span.end_ns - span.start_ns) / 1e6


def cpu_ms(span) -> float:
    return (span.cpu_end_ns - span.cpu_start_ns) / 1e6


def window(run: dict):
    """-> {'roots': the last `run['steps']` `train.step` spans, 'under': root
    id -> every span recorded under it, 'spans' (the whole ring), 'marks',
    'gauges'} or None."""
    tracing = _tracing()
    if tracing is None or not run.get('steps'):
        return None
    snap = tracing.snapshot()
    roots = [s for s in snap['spans'] if s.name == 'train.step'][-run['steps']:]
    if len(roots) < run['steps']:
        return None
    first = roots[0].id
    root_of = {r.id: r.id for r in roots}
    for s in sorted((s for s in snap['spans'] if s.id > first), key=lambda s: s.id):   # a parent's id is the smaller
        if s.parent in root_of:
            root_of[s.id] = root_of[s.parent]
    under = {r.id: [] for r in roots}
    for s in snap['spans']:
        if s.id in root_of and s.id not in under:
            under[root_of[s.id]].append(s)
    if not all(any(s.name == 'task.train_step' for s in spans) for spans in under.values()):
        return None
    return {'roots': roots, 'under': under, 'spans': snap['spans'], 'marks': snap['marks'], 'gauges': snap['gauges']}


def per_step(w, *names, what=wall_ms):
    """Per step of the window `w`, `what` summed over the spans of these names
    under the step's root; None where `w` is."""
    if w is None:
        return None
    return [sum(what(s) for s in w['under'][r.id] if s.name in names) for r in w['roots']]


def step_ms(run: dict, *names):
    """Median over the window's steps of the wall ms under these names."""
    rows = per_step(window(run), *names)
    return None if rows is None else statistics.median(rows)


def host_cpu_share(run: dict):
    """Thread-CPU over wall, in %, of `task.train_step` less `task.sentinel_poll`
    over the whole window: the share of the call's Python part in which the main
    thread ran, rather than waited for the interpreter or the runtime."""
    w = window(run)
    if w is None:
        return None
    cpu, wall = (sum(per_step(w, 'task.train_step', what=what)) - sum(per_step(w, 'task.sentinel_poll', what=what))
                 for what in (cpu_ms, wall_ms))
    return 100.0 * cpu / wall if wall > 0 else None


def decode_busy_share(run: dict):
    """`loader.decode_busy_ns` added between the marks at the window's ends,
    over the window's length times the loader's worker threads, in %."""
    from .train_runner import loader_workers
    w = window(run)
    if w is None:
        return None
    t0, t1 = w['roots'][0].start_ns, w['roots'][-1].end_ns
    before = [c for t, c in w['marks'] if t <= t0]
    upto = [c for t, c in w['marks'] if t <= t1]
    if not before or not upto or 'loader.decode_busy_ns' not in upto[-1]:
        return None
    busy = upto[-1]['loader.decode_busy_ns'] - before[-1].get('loader.decode_busy_ns', 0)
    return 100.0 * busy / ((t1 - t0) * loader_workers())


def setup(w):
    """Spans that ended before the first root of the window `w` began, oldest
    first; None where `w` is."""
    if w is None:
        return None
    opened = w['roots'][0].start_ns
    return [s for s in w['spans'] if s.end_ns <= opened]


def setup_s(run: dict, name: str, first_only: bool = False):
    """Wall seconds of the set-up's spans of this name (of the first one)."""
    spans = setup(window(run))
    rows = None if spans is None else [wall_ms(s) / 1e3 for s in spans if s.name == name]
    if not rows:
        return None
    return rows[0] if first_only else sum(rows)


# -- the same spans in the profiler's trace -------------------------------------------

@functools.lru_cache(maxsize=2)
def _trace_of(path: str, mtime: float):
    """(idle gaps of the first device inside `bench.window`, program spans by
    name) of one trace file; `mtime` only keys the cache."""
    from jax.profiler import ProfileData
    names = set(_tracing().SPANS)
    devices, bench = trace.read_planes(path)
    marks = [(s, e) for name, s, e in bench if name == 'window']
    window = (min(s for s, _ in marks), max(e for _, e in marks)) if marks else None
    gaps = trace.reduce_device(next(iter(devices.values())), window)['gaps_ns'] if devices else []
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith('/host:'):
            for line in plane.lines:
                for e in line.events:
                    name = e.name.split('#', 1)[0]     # TraceMe writes ids as name#key=value#
                    if name in names:
                        spans.setdefault(name, []).append((e.start_ns, e.start_ns + e.duration_ns))
    return gaps, spans


def traced_spans(run: dict):
    """name -> [(start_ns, end_ns)] of the program's spans in the newest trace
    of the run's cell, with the device's idle gaps; None without either."""
    if _tracing() is None or not run.get('trace') or not run.get('cell'):
        return None
    try:
        path = trace.newest_xplane(os.path.join(REPO_ROOT, 'output', 'benchmarks', 'trace', run['cell']))
    except FileNotFoundError:
        return None
    gaps, spans = _trace_of(path, os.path.getmtime(path))
    return (gaps, spans) if 'task.train_step' in spans else None


def overlap(gap, merged) -> int:
    """ns of `gap` covered by the sorted, merged intervals."""
    return sum(max(0, min(e, gap[1]) - max(s, gap[0])) for s, e in merged)


def attribute(gaps, spans: dict) -> dict:
    """Idle ns by what the host was inside: under `task.train_step` ('step'),
    under the fetch and the placing of a batch ('input'), under the rest of
    `train.step` ('loop'), under none of the program's spans ('outside')."""
    of = lambda names: [iv for n in names for iv in spans.get(n, ())]  # noqa: E731
    step, feed = trace.union(of(STEP)), trace.union(of(INPUT))
    anywhere = trace.union(of(STEP + INPUT + LOOP))
    out = {'step': 0, 'input': 0, 'loop': 0, 'outside': 0}
    for gap in gaps:
        in_step, in_input, covered = overlap(gap, step), overlap(gap, feed), overlap(gap, anywhere)
        out['step'] += in_step
        out['input'] += in_input
        out['loop'] += covered - in_step - in_input
        out['outside'] += (gap[1] - gap[0]) - covered
    return out


def idle_by_layer(run: dict):
    """`attribute` of the traced window, in ms a traced step, plus 'share': the
    idle time under any of the program's spans over all of it, in %."""
    found = traced_spans(run)
    work = (run.get('trace') or {}).get('work')
    if found is None or not work:
        return None
    ns = attribute(*found)
    total = sum(ns.values())
    out = {k: v / 1e6 / work for k, v in ns.items()}
    out['share'] = 100.0 * (total - ns['outside']) / total if total else None
    return out
