#!/usr/bin/env python3
"""Read, on the chip and in one process, what a cell's limits are set from:
the numbers its `correct` compares on sound runs of the program over several
seeds, and the same numbers for the control — the reference put in the
program's place in the next lower precision (float8_e4m3fn for a bfloat16
configuration).

    chiprun -- python3 benchmarks/tools/limits.py <cell> <seconds> <control seeds> <seed> [<seed> ...]

`LIMITS_CONTROL=float8_wide_grad` in the environment reads the straight-through variant instead.

Every seed runs the cell for `seconds`; the first `control seeds` of them also
run the control. One JSON line per seed, then the largest sound and smallest
control reading of each number. Nothing here is run by the benchmark itself.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CONTROL = os.environ.get('LIMITS_CONTROL', 'float8')


def main(argv):
    cell_name, seconds, n_control = argv[0], float(argv[1]), int(argv[2])
    seeds = [int(s) for s in argv[3:]]
    from benchmarks import run as bench_run
    from benchmarks.harness.manifest import Manifest, runner_module
    manifest = Manifest()
    cell = manifest.cell(cell_name)
    config = manifest.config(cell['config'])
    bench_run.require_chips(cell['chips'])
    sound, control = {}, {}
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        record = runner_module(cell['runner']).run(
            cell, config, seed=seed, seconds=seconds, trace=False, process_start=t0,
            scratch=bench_run.SCRATCH, control_precision=CONTROL if i < n_control else None)
        line = {'seed': seed, 'correct': record['correct'], 'end_to_end': record['end_to_end'],
                'numbers': record['numbers'], 'control': record.get('control_numbers'),
                'wall_s': time.perf_counter() - t0}
        print('LIMITS ' + json.dumps(line), flush=True)
        if 'followed' in record:  # every leaf's norms, for choosing or re-reading a number off the chip
            os.makedirs(os.path.join(ROOT, 'chiprun_out'), exist_ok=True)
            with open(os.path.join(ROOT, 'chiprun_out', f'followed_{cell_name}.jsonl'), 'a') as f:
                f.write(json.dumps({'seed': seed, **record['followed']}) + '\n')
        for k, v in record['numbers'].items():
            sound.setdefault(k, []).append(v)
        for k, v in (record.get('control_numbers') or {}).items():
            control.setdefault(k, []).append(v)
    for k in sound:
        print(f'LIMITS {k}: sound max {max(sound[k]):.6g} (all {sorted(sound[k])}) '
              f'control min {min(control[k]) if k in control else None} (all {sorted(control.get(k, []))})', flush=True)


if __name__ == '__main__':
    main(sys.argv[1:])
    sys.stdout.flush()
    os._exit(0)
