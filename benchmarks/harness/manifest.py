"""Where the benchmark's data lives, found by the names in `BENCHMARK.json`.

A cell is `benchmarks/workloads/<name>.json`, its configuration
`benchmarks/configs/<config>.json`, a per-layer metric's reader
`benchmarks/layer_metrics/<metric>.py`, a reference
`benchmarks/reference/<reference>.py`, a runner
`benchmarks/harness/<runner>_runner.py` (its `run`, which gives the record, and
`needed_work`, which says what a step of its family needs: the readers know no
family's shapes). Adding any of them is adding a file and an entry to
`BENCHMARK.json`; no file that is there is edited.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Manifest:
    """`BENCHMARK.json` plus the files it names, under one `bench_dir`."""

    def __init__(self, bench_dir: str = BENCH_DIR, manifest_path: str | None = None):
        self.bench_dir = bench_dir
        self.data = load_json(manifest_path or os.path.join(os.path.dirname(bench_dir), 'BENCHMARK.json'))
        self.cells = {w['name']: w for w in self.data['workloads']}
        self.end_to_end = {m['name']: m for m in self.data['end_to_end']}
        self.per_layer = {m['name']: m for m in self.data['per_layer']}

    def cell(self, name: str) -> dict:
        """The manifest's entry for a cell merged over the cell's own file."""
        if name not in self.cells:
            raise KeyError(f'no workload {name!r} in BENCHMARK.json (have {sorted(self.cells)})')
        entry = self.cells[name]
        cell = load_json(os.path.join(self.bench_dir, 'workloads', f'{name}.json'))
        for key in ('config', 'chips'):
            if cell.get(key, entry[key]) != entry[key]:
                raise ValueError(f'{name}: {key} {cell[key]!r} in the cell file, {entry[key]!r} in BENCHMARK.json')
        return dict(cell, name=name, config=entry['config'], chips=entry['chips'])

    def config(self, name: str) -> dict:
        return load_json(os.path.join(self.bench_dir, 'configs', f'{name}.json'))

    def metrics_of(self, cell_name: str, kind: str) -> list:
        """Names of the `end_to_end` or `per_layer` metrics the cell reports:
        those that list it, or list nothing and move something it reports."""
        e2e = [m['name'] for m in self.data['end_to_end']
               if cell_name in m.get('workloads', [cell_name])]
        if kind == 'end_to_end':
            return e2e
        return [m['name'] for m in self.data['per_layer']
                if cell_name in m.get('workloads', [cell_name]) and m['moves'] in e2e]

    @staticmethod
    def load_reader(path: str):
        """A per-layer metric's own file as a module (`LAYER`, `UNIT`, `MOVES`, `read`)."""
        name = 'layer_metric_' + os.path.basename(path)[:-3].replace('.', '_')
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def reader(self, metric: str):
        """The `read(run)` function of a per-layer metric's own file."""
        module = self.load_reader(os.path.join(self.bench_dir, 'layer_metrics', f'{metric}.py'))
        entry = self.per_layer[metric]
        for key, have in (('layer', module.LAYER), ('unit', module.UNIT), ('moves', module.MOVES)):
            if entry[key] != have:
                raise ValueError(f'{metric}: {key} {have!r} in its reader, {entry[key]!r} in BENCHMARK.json')
        return module.read


def reference_module(name: str):
    return importlib.import_module(f'benchmarks.reference.{name}')


def runner_module(name: str):
    return importlib.import_module(f'benchmarks.harness.{name}_runner')
