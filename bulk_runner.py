#!/usr/bin/env python3
"""Run validate.py / benchmark.py over model lists as subprocesses
(reference: bulk_runner.py:1-244 — used to produce results/*.csv).

One process per chip: this parent lists model names and never makes a JAX
device call, so each child, run one at a time, has the device to itself.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

parser = argparse.ArgumentParser(description='Per-model subprocess launcher')
parser.add_argument('script', choices=['validate', 'benchmark'], help='which script to run per model')
parser.add_argument('--model-list', default='', type=str,
                    help='txt file of model names, or a wildcard for list_models')
parser.add_argument('--pretrained', action='store_true', help='restrict wildcard to pretrained models')
parser.add_argument('--results-file', default='bulk_results.json', type=str)
parser.add_argument('--timeout', default=3600, type=int, help='per-model timeout (s)')
parser.add_argument('--start', default=0, type=int, help='resume: skip first N models')
# everything after '--' is forwarded to the child script


def main():
    argv = sys.argv[1:]
    passthrough = []
    if '--' in argv:
        idx = argv.index('--')
        passthrough = argv[idx + 1:]
        argv = argv[:idx]
    args = parser.parse_args(argv)

    if os.path.exists(args.model_list):
        with open(args.model_list) as f:
            model_names = [l.strip() for l in f if l.strip()]
    else:
        from timm_tpu.models import list_models
        model_names = list_models(args.model_list or '*', pretrained=args.pretrained)
    model_names = model_names[args.start:]
    print(f'Running {args.script} over {len(model_names)} models')

    def _extract_json(text: str):
        """Parse the trailing (possibly multi-line, indented) JSON payload."""
        for opener in ('{', '['):
            idx = text.rfind('\n' + opener)
            if idx == -1 and text.startswith(opener):
                idx = -1  # payload starts at position 0
            if idx != -1 or text.startswith(opener):
                candidate = text[idx + 1 if idx != -1 else 0:]
                try:
                    return json.loads(candidate)
                except json.JSONDecodeError:
                    continue
        return None

    results = []
    if args.start > 0 and os.path.exists(args.results_file):
        with open(args.results_file) as f:
            results = json.load(f)  # resume: keep completed entries
    for i, name in enumerate(model_names):
        cmd = [sys.executable, f'{args.script}.py', '--model', name] + passthrough
        t0 = time.time()
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True, timeout=args.timeout)
            ok = proc.returncode == 0
            payload = _extract_json(proc.stdout.strip())
            results.append({'model': name, 'ok': ok, 'seconds': round(time.time() - t0, 1),
                            'result': payload,
                            'error': proc.stderr.strip().splitlines()[-1] if (not ok and proc.stderr.strip()) else None})
        except subprocess.TimeoutExpired:
            results.append({'model': name, 'ok': False, 'seconds': args.timeout, 'error': 'timeout'})
        print(f'[{i + 1}/{len(model_names)}] {name}: {"OK" if results[-1]["ok"] else "FAIL"}')
        with open(args.results_file, 'w') as f:  # timm-tpu-lint: disable=process-zero-io single-process bulk driver; children are processes, not a pod
            json.dump(results, f, indent=2)
    print(f'Wrote {args.results_file}')


if __name__ == '__main__':
    main()
