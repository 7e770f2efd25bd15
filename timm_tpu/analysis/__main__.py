"""CLI: run the static-analysis suite.

    python -m timm_tpu.analysis                      # all rules, full zoo
    python -m timm_tpu.analysis --rules silent-except,fp32-softmax
    python -m timm_tpu.analysis --tiers A            # source rules only
    python -m timm_tpu.analysis --json out.json      # machine-readable report
    python -m timm_tpu.analysis --list               # rule table

Exit codes: 0 clean / 2 violations / 3 internal error (a crashed rule is
never evidence of a clean repo).

Tier B/C rules consume programs the perfbudget probes lower on 8 virtual CPU
devices. Every verdict is CPU-provable, so this tool pins itself to that
platform before its first JAX device call (`use_virtual_cpu_devices`): it
takes no chip and starts no child.
"""
from __future__ import annotations

import argparse
import sys

_REQUIRED_DEVICES = 8


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    parser = argparse.ArgumentParser(prog='python -m timm_tpu.analysis')
    parser.add_argument('--rules', default='', metavar='A,B',
                        help='comma-separated rule subset (default: all)')
    parser.add_argument('--tiers', default='', metavar='A,B,C',
                        help='comma-separated tier subset')
    parser.add_argument('--json', default=None, metavar='PATH',
                        help='write the full report as JSON ("-" = stdout)')
    parser.add_argument('--list', action='store_true',
                        help='print the rule table and exit')
    parser.add_argument('--source-root', default=None, metavar='DIR',
                        help='scan this tree instead of the repo (source '
                             'rules; used by the planted-violation tests)')
    parser.add_argument('--probe-configs', default='', metavar='A,B',
                        help='perfbudget configs to lower for Tier B/C '
                             '(default: the full analysis set)')
    parser.add_argument('--zoo-families', default='', metavar='A,B',
                        help='family subset for zoo-abstract-trace '
                             '(default: every registered family)')
    parser.add_argument('-q', '--quiet', action='store_true',
                        help='suppress progress logging')
    args = parser.parse_args(argv)

    from . import registry as R
    from .report import EXIT_ERROR

    if args.list:
        for r in R.all_rules():
            needs = ' [programs]' if r.needs_programs else ''
            print(f'{r.tier}  {r.name:24s}{needs}  {r.description}')
        return 0

    names = [n.strip() for n in args.rules.split(',') if n.strip()] or None
    tiers = [t.strip() for t in args.tiers.split(',') if t.strip()] or None
    try:
        rules = R.select(names=names, tiers=tiers)
    except KeyError as e:
        print(f'analysis: {e}', file=sys.stderr)
        return EXIT_ERROR

    if any(r.needs_programs or r.needs_devices > 1 for r in rules):
        from ..parallel import use_virtual_cpu_devices
        use_virtual_cpu_devices(_REQUIRED_DEVICES)

    log = (lambda m: None) if args.quiet else (
        lambda m: print(m, file=sys.stderr, flush=True))
    probe_names = ([n.strip() for n in args.probe_configs.split(',')
                    if n.strip()] or None)
    zoo_families = ([f.strip() for f in args.zoo_families.split(',')
                     if f.strip()] or None)
    ctx = R.AnalysisContext(root=args.source_root, probe_names=probe_names,
                            zoo_families=zoo_families, log=log)
    try:
        report = R.run_analysis(ctx, rules)
    except Exception as e:  # noqa: BLE001 - driver failure = exit 3
        print(f'analysis: internal error: {type(e).__name__}: {e}',
              file=sys.stderr)
        return EXIT_ERROR

    if args.json == '-':
        print(report.to_json(indent=1))
    elif args.json:
        with open(args.json, 'w', encoding='utf-8') as f:
            f.write(report.to_json(indent=1))
        log(f'analysis: report -> {args.json}')
    print(report.format_text())
    return report.exit_code


if __name__ == '__main__':
    raise SystemExit(main())
