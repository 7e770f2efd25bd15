"""From a profiler trace (`*.xplane.pb`, read with `jax.profiler.ProfileData`)
to numbers: the time an operation ran on the device, the idle share, the
operations that took most time, the longest idle gaps and what the host was
doing in them. The same reduction for every PR; checked in the tests on the
recorded chip trace under `benchmarks/fixtures/`.

Device planes are named `/device:TPU:<n>`. Of a device plane's lines only the
op-level one (`XLA Ops`) is read: a module- or step-level line covers the
whole program and would read as never idle. Overlapping events are merged
before they are summed (busy time is the union of intervals).

Host spans are `jax.profiler.TraceAnnotation`s whose name starts with
`bench.`; the profiler writes them into a host plane on the trace's own clock.
"""
from __future__ import annotations

import glob
import os

DEVICE_PLANE = '/device:TPU:'
OP_LINE = 'XLA Ops'
SPAN_PREFIX = 'bench.'
TOP = 10


def start(trace_dir: str) -> None:
    """Start the profiler with the Python tracer off: the `bench.` spans and
    the device planes are all the reduction reads, and a trace of every Python
    call slows the host it is measuring."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def describe(path: str, events: int = 3) -> str:
    """Planes, lines, event counts and the first few events of a trace: the
    hand look that comes before trusting `read_planes`."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        out.append(f'plane {plane.name!r}: {len(lines)} lines')
        for line in lines:
            evs = list(line.events)
            head = ', '.join(f'{e.name[:60]}@{e.start_ns:.0f}+{e.duration_ns:.0f}' for e in evs[:events])
            out.append(f'  line {line.name!r}: {len(evs)} events: {head}')
    return '\n'.join(out)


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')),
                   key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f'no *.xplane.pb under {trace_dir}')
    return found[-1]


def read_planes(path: str):
    """(device ops, host spans): per device plane a list of (name, start_ns,
    end_ns) from its op-level line, and the `bench.` host spans as one list."""
    from jax.profiler import ProfileData
    devices, spans = {}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OP_LINE:
                    devices[plane.name] = [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
        elif plane.name.startswith('/host:'):
            for line in plane.lines:
                spans.extend((e.name[len(SPAN_PREFIX):], e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name.startswith(SPAN_PREFIX))
    return devices, spans


def op_family(event_name: str) -> str:
    """`%convert_reduce_fusion.18 = (f32[128,197]...) fusion(...)` -> `convert_reduce_fusion`: the trace
    names an op by its whole HLO line; the instances of one fusion pattern are summed under its base name."""
    head = event_name.split(' = ', 1)[0].strip().lstrip('%')
    base, _, suffix = head.rpartition('.')
    return base if base and suffix.isdigit() else head


def union(intervals):
    """Sorted, merged (start, end) pairs of possibly overlapping intervals."""
    merged = []
    for start, end in sorted((s, e) for s, e in intervals if e > s):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def reduce_device(ops, window=None) -> dict:
    """One device's op events -> busy seconds (union), window seconds, idle
    share, per-op totals and idle gaps. `window` is (start_ns, end_ns); events
    are clipped to it; default is first start to last end."""
    if window is None:
        window = (min(s for _, s, _ in ops), max(e for _, _, e in ops)) if ops else (0, 0)
    w0, w1 = window
    clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in ops if e > w0 and s < w1]
    busy = union((s, e) for _, s, e in clipped)
    busy_ns = sum(e - s for s, e in busy)
    totals = {}
    for name, s, e in clipped:
        totals[op_family(name)] = totals.get(op_family(name), 0) + (e - s)
    edges = [w0] + [t for pair in busy for t in pair] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    window_ns = max(w1 - w0, 0)
    return {'busy_s': busy_ns / 1e9, 'window_s': window_ns / 1e9,
            'idle_share': 1.0 - busy_ns / window_ns if window_ns else 0.0,
            'op_seconds': {k: v / 1e9 for k, v in totals.items()}, 'gaps_ns': gaps}


def label_gap(gap, spans, default: str) -> str:
    """The host span that covers most of an idle gap, `default` if none does."""
    g0, g1 = gap
    best, cover = default, 0
    for name, s, e in spans:
        c = min(e, g1) - max(s, g0)
        if c > cover:
            best, cover = name, c
    return best


def reduce_trace(path: str, default_gap_label: str = 'host') -> dict:
    """The whole reduction: busy and idle averaged over the device planes, the
    window being the span of the `bench.window` host span when there is one
    (else first device op to last), and the `breakdown` of the result line."""
    devices, spans = read_planes(path)
    if not devices:
        raise ValueError(f'no {DEVICE_PLANE}* plane with an {OP_LINE!r} line in {path}')
    marks = [(s, e) for name, s, e in spans if name == 'window']
    window = (min(s for s, _ in marks), max(e for _, e in marks)) if marks else None
    reduced = [reduce_device(ops, window) for ops in devices.values()]
    n = len(reduced)
    op_seconds = {}
    for r in reduced:
        for k, v in r['op_seconds'].items():
            op_seconds[k] = op_seconds.get(k, 0.0) + v / n
    others = [s for s in spans if s[0] != 'window']
    gap_seconds = {}
    for g in reduced[0]['gaps_ns']:
        label = label_gap(g, others, default_gap_label)
        gap_seconds[label] = max(gap_seconds.get(label, 0.0), (g[1] - g[0]) / 1e9)
    top = lambda d: sorted(([k, v] for k, v in d.items()), key=lambda kv: -kv[1])[:TOP]  # noqa: E731
    return {'busy_s': sum(r['busy_s'] for r in reduced) / n,
            'window_s': sum(r['window_s'] for r in reduced) / n,
            'idle_share': sum(r['idle_share'] for r in reduced) / n,
            'op_seconds': op_seconds,
            'idle_total_s': sum(e - s for s, e in reduced[0]['gaps_ns']) / 1e9,
            'breakdown': {'device_ops': top(op_seconds), 'idle_gaps': top(gap_seconds)}}
