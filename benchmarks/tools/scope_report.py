#!/usr/bin/env python3
"""One traced run of a training cell, then the device time of its step program
by device scope (`timm_tpu/utils/tracing.py` `scope`; `harness/step_scopes.py`),
for PERF.md section 5:

    python3 benchmarks/tools/scope_report.py --workload <cell> --seed <n> [--seconds 20]

Prints one line a scope (ms a traced step, % of the step program's busy time;
a row is the fusions whose ROOT is under the scope), the cover, the device time
of the window's other programs, the ten op families that took most time outside
every scope, what the reduction cost (the size of the step program's kept text,
the seconds from the record to the table), and as its last line the result line
`benchmarks/run.py` prints for the same record. Any cell's runner: the table
reads the cell's trace and the text the program kept, nothing of the runner's.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402  (takes the process's start time as it is imported)


def report(run: dict, out=print) -> None:
    from benchmarks.harness import step_scopes
    from timm_tpu.utils import tracing
    text = tracing.program_text(step_scopes.STEP_PROGRAM)
    t0 = time.perf_counter()
    lines = step_scopes.table(run)
    took = time.perf_counter() - t0
    for line in lines:
        out(line)
    step_ms = run['trace']['busy_s'] / run['trace']['work'] * 1e3
    out(f'step_device_ms.train {step_ms:.3f} (every program, the window\'s busy time a step); the step program\'s kept '
        f'text: {len(text or "") / 1e6:.2f} MB; trace and text reduced in {took:.2f} s')


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, required=True)
    parser.add_argument('--seconds', type=float, default=20.0)
    args = parser.parse_args(argv)

    from benchmarks.harness.manifest import Manifest, runner_module
    manifest = Manifest()
    cell = manifest.cell(args.workload)
    device = bench_run.require_chips(cell['chips'])
    record = runner_module(cell['runner']).run(
        cell, manifest.config(cell['config']), seed=args.seed, seconds=args.seconds, trace=True,
        process_start=bench_run.PROCESS_START, scratch=bench_run.SCRATCH)
    report(record)
    print(json.dumps(bench_run.result_line(manifest, args.workload, record, device, True)), flush=True)
    return 0


if __name__ == '__main__':
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)   # as run.py leaves: the loader's daemon threads may still hold the device
