"""CLI: probe the matrix and compare (or re-baseline) the budgets.

    python -m timm_tpu.perfbudget                     # compare vs checked-in budgets
    python -m timm_tpu.perfbudget --update-budgets    # re-baseline (the ONLY way
                                                      # to accept an improvement)
    python -m timm_tpu.perfbudget --configs base,fsdp4 --json

The probe matrix runs on 8 virtual CPU devices and its metrics are
CPU-provable, so this tool pins itself to that platform before its first JAX
device call (`use_virtual_cpu_devices`): it takes no chip and starts no child.
"""
from __future__ import annotations

import argparse
import json
import sys

_REQUIRED_DEVICES = 8


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    parser = argparse.ArgumentParser(prog='python -m timm_tpu.perfbudget')
    parser.add_argument('--update-budgets', action='store_true',
                        help='re-baseline: write the measured metrics as the new '
                             'budget file instead of comparing')
    parser.add_argument('--budgets', default=None, metavar='PATH',
                        help='budget file (default: tests/fixtures/perf_budgets.json, '
                             'env TIMM_TPU_PERF_BUDGETS)')
    parser.add_argument('--configs', default='', metavar='A,B',
                        help='comma-separated subset of the probe matrix')
    parser.add_argument('--json', action='store_true',
                        help='print measured metrics + violations as JSON')
    parser.add_argument('--note', default='', help='note recorded on --update-budgets')
    args = parser.parse_args(argv)

    from ..parallel import use_virtual_cpu_devices
    use_virtual_cpu_devices(_REQUIRED_DEVICES)

    from . import budgets as B
    from .probe import run_matrix

    names = [n.strip() for n in args.configs.split(',') if n.strip()] or None
    measured = run_matrix(names=names,
                          log=lambda m: print(m, file=sys.stderr, flush=True))

    if args.update_budgets:
        doc = B.update_budgets(measured, path=args.budgets, note=args.note)
        path = args.budgets or B.BUDGETS_PATH
        print(f'perfbudget: re-baselined {len(doc["configs"])} config(s) -> {path}')
        if args.json:
            print(json.dumps(doc, indent=1))
        return 0

    budgets = B.load_budgets(args.budgets)
    violations = B.compare_budgets(measured, budgets, configs=names)
    if args.json:
        print(json.dumps({'measured': measured, 'violations': violations}, indent=1))
    print(B.format_violations(violations))
    return 1 if violations else 0


if __name__ == '__main__':
    raise SystemExit(main())
