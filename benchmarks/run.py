#!/usr/bin/env python3
"""Run one cell of the benchmark once, in this process, on the chips it is
started on:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Earlier lines are free text (every number compared with its limit, the
loader's workers, where set-up went); the LAST line of stdout is one
JSON object with `correct`, `attempted`, `failed`, `metrics`, `device`,
when traced `breakdown`, and last `checks`: every number `correct` compared,
beside its limit, as the runner's record holds them (`check.compared`); the
same numbers are the last lines of stderr. With `--trace 0`
the metrics are the cell's end-to-end metrics, with `--trace 1` its per-layer
metrics.

Everything that belongs to one cell, configuration or metric is a file found
by the name in `BENCHMARK.json` (`harness/manifest.py`). There is no CPU
branch: without a TPU, with another number of chips than the cell asks for, or
on a device kind the peak table does not know, the run exits non-zero and
prints no result.
"""
import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)
SCRATCH = os.path.join(REPO_ROOT, 'output', 'benchmarks')


def require_chips(chips: int) -> dict:
    """The platform must be a TPU with exactly `chips` devices of a kind the
    peak table knows; anything else is fatal."""
    import jax

    from benchmarks.harness import peaks
    devices = jax.devices()
    device = {'platform': devices[0].platform, 'kind': devices[0].device_kind, 'count': len(devices)}
    if device['platform'] != 'tpu':
        raise SystemExit(f'no accelerator: jax.devices()[0].platform is {device["platform"]!r}, need "tpu"')
    if device['count'] != chips:
        raise SystemExit(f'the cell needs {chips} chip(s), JAX reports {device["count"]}')
    try:
        peaks.peak(device['kind'])
    except KeyError as e:
        raise SystemExit(str(e)) from None
    return device


def result_line(manifest, cell_name: str, record: dict, device: dict, trace: bool) -> dict:
    """The contract's one JSON object from a runner's record."""
    metrics = {}
    if trace:
        for name in manifest.metrics_of(cell_name, 'per_layer'):
            value = manifest.reader(name)(record)
            if value is not None:
                metrics[name] = {'value': float(value), 'unit': manifest.per_layer[name]['unit']}
    else:
        for name in manifest.metrics_of(cell_name, 'end_to_end'):
            metrics[name] = {'value': float(record['end_to_end'][name]),
                             'unit': manifest.end_to_end[name]['unit']}
    device = dict(device, memory_peak_bytes=record['memory_peak_bytes'])
    line = {'correct': bool(record['correct']), 'attempted': int(record['attempted']),
            'failed': int(record['failed']), 'metrics': metrics, 'device': device}
    if trace:
        device.update(busy_s=record['trace']['busy_s'], window_s=record['trace']['window_s'])
        line['breakdown'] = record['trace']['breakdown']
    line['checks'] = record.get('checks', {})
    return line


def check_lines(checks: dict) -> list:
    """One line a number compared, from the record's data: `check <name>: <number> <how> <limit> ok|NOT`."""
    return [f'check {name}: {c["value"]} {c["how"]} {c["limit"]} {"ok" if c["ok"] else "NOT"}' for name, c in checks.items()]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, required=True)
    parser.add_argument('--seconds', type=float, required=True)
    parser.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmarks.harness.manifest import Manifest, runner_module
    manifest = Manifest()
    cell = manifest.cell(args.workload)
    config = manifest.config(cell['config'])
    device = require_chips(cell['chips'])

    record = runner_module(cell['runner']).run(
        cell, config, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        process_start=PROCESS_START, scratch=SCRATCH)
    line = result_line(manifest, args.workload, record, device, bool(args.trace))
    print('\n'.join(check_lines(line['checks'])), file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == '__main__':
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the loader's daemon threads may still hold the device; leave without their teardown
    os._exit(code)
