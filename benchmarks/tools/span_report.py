#!/usr/bin/env python3
"""One traced run of a training cell, then what the program's own spans
(`timm_tpu/utils/tracing.py`) say about it, for PERF.md section 5:

    python3 benchmarks/tools/span_report.py --workload <cell> --seed <n> [--seconds 20] [--trace 0]

Prints the window's steps split by span (median wall and thread-CPU ms, spans a
step), set-up by span with every compilation under the span that asked for it,
the consistency checks between the ring, the wrapper's clocks and the trace,
what one `span()` costs on this host, and as its last line the result line
`benchmarks/run.py` prints for the same record. With `--trace 0` the window is
the untraced one (all of its steps at full speed) and the trace's part is left out.
"""
import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402  (takes the process's start time as it is imported)


def report(run: dict, out=print) -> None:
    from benchmarks.harness import program_spans as ps
    from benchmarks.harness import trace
    from benchmarks.harness.train_runner import TRACE_AFTER, TRACE_STEPS
    from timm_tpu.utils import tracing

    w = ps.window(run)
    if w is None:
        out('no window in the ring')
        return
    main = w['roots'][0].thread
    out(f'window: {len(w["roots"])} steps; span, spans a step, median wall ms, median thread-CPU ms a step')
    for name in tracing.SPANS:
        wall, cpu = ps.per_step(w, name), ps.per_step(w, name, what=ps.cpu_ms)
        n = sum(s.name == name for spans in w['under'].values() for s in spans) / len(w['roots'])
        if n:
            out(f'  {name:24s} {n:5.2f} {statistics.median(wall):9.3f} {statistics.median(cpu):9.3f}')
    roots_wall = [ps.wall_ms(r) for r in w['roots']]
    out(f'  {"train.step":24s}  1.00 {statistics.median(roots_wall):9.3f}')
    a_step = statistics.median(sum(s.thread == main for s in spans) + 1 for spans in w['under'].values())
    out(f'spans a step on the main thread: {a_step}')
    depths = [v for t, v in w['gauges'].get('loader.batch_q_depth', ()) if t >= w['roots'][0].start_ns]
    out(f'loader.batch_q_depth: mean {statistics.fmean(depths):.2f}, zero in {sum(d == 0 for d in depths)} of {len(depths)}')
    marks = [c for t, c in w['marks'] if w['roots'][0].start_ns <= t <= w['roots'][-1].end_ns]
    did = {k: marks[-1][k] - marks[0].get(k, 0) for k in marks[-1]}
    out(f'counters over the window: {did}')

    by_id = {s.id: s for s in w['spans']}
    before = ps.setup(w)
    out('set-up: span, count, seconds')
    for name, row in tracing.summary(spans=before).items():
        out(f'  {name:24s} {row["n"]:4d} {row["wall_ms_sum"] / 1e3:8.2f}')
    under = {}
    for s in before:
        if s.name == 'xla.backend_compile':
            key = by_id[s.parent].name if s.parent in by_id else '(no span open)'
            under.setdefault(key, []).append(ps.wall_ms(s) / 1e3)
    out('set-up: compilations by the span that asked for them: span, count, seconds')
    for key, rows in sorted(under.items(), key=lambda kv: -sum(kv[1])):
        out(f'  {key:24s} {len(rows):4d} {sum(rows):8.2f}')

    out('checks:')
    children = ('task.state_split', 'task.scalars_put', 'task.step_call', 'task.state_update', 'task.sentinel_poll')
    whole = ps.per_step(w, 'task.train_step')
    out(f'  children cover task.train_step: {100 * sum(ps.per_step(w, *children)) / sum(whole):.2f} %')
    outside = statistics.median(run['spans']['train_step_dispatch_s']) * 1e3
    apart = [o * 1e3 - i for o, i in zip(run['spans']['train_step_dispatch_s'], whole)]
    out(f'  task.train_step median {statistics.median(whole):.3f} ms; the wrapper\'s dispatch_host_ms.train '
        f'{outside:.3f} ms; step by step the wrapper reads {statistics.median(apart):.3f} ms more (median)')
    found = ps.traced_spans(run)
    if found is not None:
        ring = whole[TRACE_AFTER:TRACE_AFTER + TRACE_STEPS]
        traced = [(e - s) / 1e6 for s, e in sorted(found[1]['task.train_step'])]
        out(f'  traced task.train_step: ring {sum(ring):.3f} ms over {len(ring)}, xplane {sum(traced):.3f} ms over '
            f'{len(traced)}: {100 * (sum(traced) / sum(ring) - 1):+.3f} %')
        idle = ps.idle_by_layer(run)
        per_step = run['trace']['idle_total_s'] * 1e3 / run['trace']['work']
        out(f'  idle ms a traced step: step {idle["step"]:.3f} + input {idle["input"]:.3f} + loop {idle["loop"]:.3f} '
            f'(+ outside {idle["outside"]:.3f}) against {per_step:.3f}; attributed {idle["share"]:.2f} %')
        out(f'  spans in the trace: ' + ', '.join(f'{k} {len(v)}' for k, v in sorted(found[1].items())))
        timeline(trace.newest_xplane(os.path.join(ROOT, 'output', 'benchmarks', 'trace', run['cell'])), out)


def timeline(path: str, out) -> None:
    """Per traced `task.step_call`, on the trace's clock: when the step's
    program (`jit_train_step` on the device's `XLA Modules` line) began after
    the call began, how long it ran, and when the call returned against the
    program's end; then what the host's threads were inside during one call."""
    from jax.profiler import ProfileData
    from benchmarks.harness import trace
    programs, calls, host = [], [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if plane.name.startswith(trace.DEVICE_PLANE) and line.name == 'XLA Modules':
                programs += [(e.start_ns, e.start_ns + e.duration_ns) for e in line.events if e.name.startswith('jit_train_step')]
            elif plane.name.startswith('/host:'):
                for e in line.events:
                    if e.name == 'task.step_call':
                        calls.append((e.start_ns, e.start_ns + e.duration_ns))
                    elif e.duration_ns >= 2e6:
                        host.append((e.start_ns, e.start_ns + e.duration_ns, line.name, e.name))
    if not calls:
        return
    calls.sort()
    out('traced steps: ms from task.step_call start to its program starting on the device; the program\'s run; '
        'the call\'s return after (+) or before (-) the program\'s end; the call\'s length')
    for (start, end), (began, ended) in zip(calls, sorted(p for p in programs if p[0] >= calls[0][0])):
        out(f'  {(began - start) / 1e6:8.3f} {(ended - began) / 1e6:9.3f} {(end - ended) / 1e6:+9.3f} {(end - start) / 1e6:9.3f}')
    start, end = calls[len(calls) // 2]
    inside = [row for row in sorted(host) if row[0] >= start and row[1] <= end]
    out(f'host events of 2 ms and more inside one task.step_call ({(end - start) / 1e6:.1f} ms): thread, event, count, '
        f'ms after the call began (first), ms (sum)')
    grouped = {}
    for s, e, thread, name in inside:
        row = grouped.setdefault((thread, name), [0, (s - start) / 1e6, 0.0])
        row[0] += 1
        row[2] += (e - s) / 1e6
    for (thread, name), (n, first, total) in grouped.items():
        out(f'  {thread[:28]:28s} {name[:60]:60s} {n:4d} {first:9.3f} {total:9.3f}')


def cost_ns(body, n: int = 100000) -> float:
    t0 = time.perf_counter_ns()
    for _ in range(n):
        body()
    return (time.perf_counter_ns() - t0) / n


def span_cost_ns(n: int = 100000) -> float:
    from timm_tpu.utils import tracing

    def body():
        with tracing.span('train.log_sync'):
            pass
    return cost_ns(body, n)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, required=True)
    parser.add_argument('--seconds', type=float, default=20.0)
    parser.add_argument('--trace', type=int, choices=(0, 1), default=1)
    args = parser.parse_args(argv)

    from benchmarks.harness.manifest import Manifest, runner_module
    manifest = Manifest()
    cell = manifest.cell(args.workload)
    device = bench_run.require_chips(cell['chips'])
    record = runner_module(cell['runner']).run(
        cell, manifest.config(cell['config']), seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        process_start=bench_run.PROCESS_START, scratch=bench_run.SCRATCH)
    line = bench_run.result_line(manifest, args.workload, record, device, bool(args.trace))
    report(record)
    import jax
    print(f'span() enter + exit, no profiler session, loader threads idle: {min(span_cost_ns() for _ in range(3)):.0f} ns; '
          f'of it time.thread_time_ns() x 2: {2 * cost_ns(time.thread_time_ns):.0f}, '
          f'time.perf_counter_ns() x 2: {2 * cost_ns(time.perf_counter_ns):.0f}, '
          f'TraceAnnotation: {cost_ns(lambda: jax.profiler.TraceAnnotation("x").__enter__().__exit__(None, None, None)):.0f}')
    print(json.dumps(line), flush=True)
    return 0


if __name__ == '__main__':
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)   # as run.py leaves: the loader's daemon threads may still hold the device
