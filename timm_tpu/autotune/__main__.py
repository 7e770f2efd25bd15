"""CLI: rank configs for a model and emit the table as JSON.

    python -m timm_tpu.autotune --model vit_base_patch16_224 --global-batch 1024
    python -m timm_tpu.autotune --model test_vit --global-batch 64 \
        --model-kwargs '{"num_classes": 10, "img_size": 32}' --probe-top-k 3
    python -m timm_tpu.autotune ... --table        # human table on stderr too

The search is over the devices JAX reports in this process — the attached
chips, or whatever `XLA_FLAGS=--xla_force_host_platform_device_count=N` with
`JAX_PLATFORMS=cpu` gives a CPU rehearsal. `--devices N` enumerates for a
hypothetical topology instead (analytic tier only — no probing a mesh we
don't have).
"""
from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    parser = argparse.ArgumentParser(prog='python -m timm_tpu.autotune')
    parser.add_argument('--model', required=True)
    parser.add_argument('--model-kwargs', default='{}', metavar='JSON',
                        help='create_model kwargs, e.g. \'{"img_size": 32}\'')
    parser.add_argument('--global-batch', type=int, required=True,
                        help='global batch held exactly constant across the search')
    parser.add_argument('--devices', type=int, default=0,
                        help='enumerate for N devices instead of the live '
                             'topology (analytic tier only, no probing)')
    parser.add_argument('--num-slices', type=int, default=1)
    parser.add_argument('--hbm-gb', type=float, default=0.0,
                        help='per-device HBM budget override in GiB '
                             '(default: 90%% of the detected device class)')
    parser.add_argument('--probe-top-k', type=int, default=0,
                        help='lower the top-K real programs and re-rank on '
                             'their compiled costs')
    parser.add_argument('--no-probe-anchor', action='store_true',
                        help='skip the one-anchor estimator calibration '
                             '(pure analytic tier)')
    parser.add_argument('--max-accum', type=int, default=64)
    parser.add_argument('--no-tp', action='store_true')
    parser.add_argument('--no-remat', action='store_true')
    parser.add_argument('--top', type=int, default=0,
                        help='truncate the emitted ranking to N rows')
    parser.add_argument('--table', action='store_true',
                        help='also print the human table on stderr')
    args = parser.parse_args(argv)

    hypothetical = bool(args.devices)

    from .solver import AutotuneError, autotune, format_table, to_json

    try:
        result = autotune(
            args.model, json.loads(args.model_kwargs),
            global_batch=args.global_batch,
            n_devices=args.devices or None,
            num_slices=args.num_slices,
            hbm_budget_bytes=int(args.hbm_gb * 2**30) if args.hbm_gb else None,
            probe_top_k=0 if hypothetical else args.probe_top_k,
            probe_anchor=not (hypothetical or args.no_probe_anchor),
            max_accum=args.max_accum,
            allow_tp=not args.no_tp,
            allow_remat=not args.no_remat,
            log=lambda m: print(m, file=sys.stderr, flush=True))
    except AutotuneError as e:
        print(json.dumps({'schema': 'autotune/v1', 'error': str(e),
                          'rejections': [str(r) for r in e.rejections]},
                         indent=1))
        return 1

    if args.table:
        print(format_table(result), file=sys.stderr, flush=True)
    print(json.dumps(to_json(result, top=args.top or None), indent=1))
    return 0


if __name__ == '__main__':
    raise SystemExit(main())
