#!/usr/bin/env python3
"""Print `trace.describe` and `trace.reduce_trace` of the newest trace under a
directory (default: every cell's under output/benchmarks/trace/)."""
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

if __name__ == '__main__':
    from benchmarks.harness import trace
    dirs = sys.argv[1:] or sorted(glob.glob(os.path.join(ROOT, 'output', 'benchmarks', 'trace', '*')))
    for d in dirs:
        path = trace.newest_xplane(d)
        print(d, os.path.getsize(path), 'bytes')
        print(trace.describe(path))
        reduced = trace.reduce_trace(path)
        reduced.pop('op_seconds')
        print(json.dumps(reduced, indent=1))
