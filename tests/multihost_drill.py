"""CLI for the multi-process host-loss drill (tests/test_multihost.py runs the
same drill in tier-1; this wrapper exists for manual runs).

Launches an N-subprocess JAX cluster on CPU (one device per process, real
`jax.distributed.initialize` over a localhost coordinator), trains the tiny
fixture ViT on host-sharded synthetic data, SIGKILLs one host mid-epoch, and
asserts the full recovery contract:

  - the survivors reach stop consensus over the coordination-service KV store
    and exit 0 with their recovery state saved;
  - the save that lost the victim leaves only uncommitted shard litter (no
    global manifest) — the previous checkpoint stays the newest valid one;
  - a fresh cluster resumes `--resume auto --elastic` from the host-sharded
    checkpoint and lands within 1e-6 of an uninterrupted baseline.

Usage:
  python tests/multihost_drill.py [workdir]
      [--processes N] [--kill-update K] [--victim P]
      [--no-compare] [--no-resume] [--timeout SECONDS]

Prints one JSON line with {ok, checks, details}; exit 0 on success.
"""
import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('workdir', nargs='?', default=None,
                    help='scratch dir for logs + checkpoints (default: a tempdir)')
    ap.add_argument('--processes', type=int, default=2)
    ap.add_argument('--kill-update', type=int, default=4,
                    help='global update index at which the victim SIGKILLs itself')
    ap.add_argument('--victim', type=int, default=None,
                    help='process index to kill (default: the last, keeping the '
                         'coordinator on process 0 alive)')
    ap.add_argument('--no-compare', action='store_true',
                    help='skip the uninterrupted-baseline parity leg')
    ap.add_argument('--no-resume', action='store_true',
                    help='stop after the kill + crash-safety checks')
    ap.add_argument('--timeout', type=float, default=420.0)
    args = ap.parse_args()

    from timm_tpu.resilience import run_kill_drill

    workdir = args.workdir or tempfile.mkdtemp(prefix='timm_tpu_multihost_')
    result = run_kill_drill(
        workdir,
        processes=args.processes,
        kill_update=args.kill_update,
        victim=args.victim,
        compare=not args.no_compare,
        resume=not args.no_resume,
        timeout=args.timeout,
        log=lambda m: print(f'[multihost_drill] {m}', file=sys.stderr, flush=True),
    )
    print(json.dumps(result, sort_keys=True, default=str))
    return 0 if result['ok'] else 1


if __name__ == '__main__':
    sys.exit(main())
