"""The eight per-layer readings of the language-model cell (ISSUE 26), as plain functions of a run's record.

They are not metrics of `BENCHMARK.json` yet. Two tests the benchmark has pull against each other:
`test_program_spans.py:39` holds `per_layer[8:]` EQUAL to PR 24's eighteen names, so no entry can be appended,
and `test_harness.py:69` holds the files of `layer_metrics/` equal to the entries, so no reader file can wait
there without one; a PR that adds a configuration may edit neither (PERF.md section 7). Until a `benchmark` PR
relaxes the first, a traced run of the cell prints these readings as free text (`lines`). That PR then adds,
for each name, `entry(name, cells)` to `per_layer` and a file `layer_metrics/<name>.py` of `LAYER`, `UNIT`,
`MOVES` and a `read` that calls `READERS[name].read`; `tests/benchmark_harness/test_lm_harness.py` does
exactly that in its toy manifest, and `result_line` prints them.

A record without the device scopes or the step counters (a parent older than them, another runner's run)
gives None; nothing here raises for that.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

from . import device_scopes

MOVES = 'train_img_per_s'


class Reader(NamedTuple):
    layer: str          # as PERF.md section 3 names it
    unit: str
    better: str
    source: str
    read: Callable      # run record -> float or None
    what: str


def lm_step_mfu(run: dict):
    from . import lm_flops, peaks
    trace, macs = run.get('trace'), device_scopes.needed_macs(run)
    if run.get('runner') != 'train' or not trace or not trace.get('work') or macs is None:
        return None
    return 100.0 * lm_flops.train_flops(macs) / (trace['busy_s'] / trace['work']) / peaks.peak(run['device_kind'])['bf16_flops']


def slots_per_expert(run: dict):
    slots, sizes = device_scopes.counter_mean(run, 'moe.local_slots'), run.get('sizes') or {}
    if not slots or 'experts_held' not in sizes:
        return None
    layers = sizes['num_hidden_layers'] - sizes['first_k_dense_replace'] + sizes['num_nextn_predict_layers']
    return slots / (sizes['experts_held'] * layers)


def load_max_over_mean(run: dict):
    worst, mean = device_scopes.counter_mean(run, 'moe.load_max'), slots_per_expert(run)
    return None if worst is None or not mean else worst / mean


READERS = {
    'lm_step_mfu.train': Reader(
        'step', '%', 'higher', 'device_trace', lm_step_mfu,
        'needed operations of a step (`lm_flops.py`: forward MACs x 2 x 3, the causal half of the core, the routed '
        'experts by the slots `moe.local_slots` counted, nothing recomputed) over busy device time a step, over the bf16 peak'),
    'moe_device_share.train': Reader(
        'experts', '%', 'lower', 'device_trace',
        lambda run: device_scopes.scope_share(run, 'glm.moe.route', 'glm.moe.experts', 'glm.moe.shared'),
        'share of busy device time under the expert layers\' scopes: routing and dispatch, grouped products, shared expert'),
    'mla_device_share.train': Reader(
        'attention', '%', 'lower', 'device_trace', lambda run: device_scopes.scope_share(run, 'glm.mla.proj', 'glm.mla.core'),
        'share of busy device time under latent attention\'s scopes: projections, norms, rotary turn, causal core'),
    'moe_experts_mfu.train': Reader(
        'experts', '%', 'higher', 'device_trace', lambda run: device_scopes.scope_mfu(run, 'glm.moe.experts'),
        'roofline share of the grouped products (compute-bound): the counted slots\' operations, forward and backward, '
        'over the device time under `glm.moe.experts`, over the bf16 peak'),
    'mla_core_mfu.train': Reader(
        'attention', '%', 'higher', 'device_trace', lambda run: device_scopes.scope_mfu(run, 'glm.mla.core'),
        'roofline share of the causal core (compute-bound): the S(S+1)/2 pairs\' operations, forward and backward, over '
        'the device time under `glm.mla.core`, over the bf16 peak'),
    'moe_route_device_ms.train': Reader(
        'experts', 'ms', 'lower', 'device_trace', lambda run: device_scopes.scope_ms(run, 'glm.moe.route'),
        'device ms a step under `glm.moe.route`: router, top-k, sort, gather, weighted scatter; the memory-bound part'),
    'moe_slots_per_expert.train': Reader(
        'experts', 'count', 'higher', 'program_counter', slots_per_expert,
        'mean `moe.local_slots` a step over the experts held and the expert layers: slots one expert works on in one layer'),
    'moe_load_max_over_mean.train': Reader(
        'experts', 'ratio', 'lower', 'program_counter', load_max_over_mean,
        'mean `moe.load_max` a step over `moe_slots_per_expert.train`: 1 = even routing (`moe.dropped_slots` is held at 0)'),
}


def entry(name: str, cells: list) -> dict:
    """The `per_layer` entry of `BENCHMARK.json` for one of the readings."""
    r = READERS[name]
    return {'name': name, 'unit': r.unit, 'better': r.better, 'source': r.source, 'layer': r.layer, 'moves': MOVES,
            'workloads': list(cells)}


def lines(run: dict) -> list:
    """One line a reading on this run's record."""
    out = []
    for name, r in READERS.items():
        value = r.read(run)
        out.append(f'reading {name}: ' + ('nothing to read' if value is None else f'{value:.6g} {r.unit}')
                   + f' (layer {r.layer}, moves {MOVES}, {r.better} is better)')
    return out
