"""The per-layer readings of the language-model cells that more than one family has (ISSUE 26 brought them for
the GLM cell), as plain functions of a run's record.

Five are metrics of `BENCHMARK.json` since PR 35, which opened the pin that had kept them out (the test of
`test_program_spans.py` held `per_layer[8:]` equal to PR 24's eighteen names; it holds `[8:26]` now): each has its
entry (`entry(name, cells)`) and its file `layer_metrics/<name>.py`, which calls `READERS[name].read`. None asks
which family a record is of: a reading takes the device time under the scopes it names (`trace.scopes`) and the
needed operations of the part it names (`needed_macs`, which the cell's runner put into the record from its own
operation table), and finds nothing where the record has neither. So `moe_*` reads both cells, and a later
family whose step runs under the same scopes lists its cell and adds no code. The whole step's share of the peak
is not here: `step_mfu.train` reads the record's `needed_step_flops` in every training cell. Device time is kept
in ms a step, not as a share of busy time: a share moves when ANOTHER layer's time does (the scope table a traced
run prints has the shares). The two of `PRINTED` stay free text of a traced run (`lines`): they describe the
seeded routing, not the program's speed, and would move with the traffic.

A record without the device scopes or the step counters (a parent older than them, another runner's run)
gives None; nothing here raises for that.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

from . import device_scopes

MOVES = 'train_img_per_s'
PRINTED = ('moe_slots_per_expert.train', 'moe_load_max_over_mean.train')    # readings that are no metric


class Reader(NamedTuple):
    layer: str          # as PERF.md section 3 names it
    unit: str
    better: str
    source: str
    read: Callable      # run record -> float or None
    what: str


def slots_per_expert(run: dict):
    slots, lm = device_scopes.counter_mean(run, 'moe.local_slots'), run.get('lm') or {}
    held = (run.get('sizes') or {}).get('experts_held')
    if not slots or not held or not lm.get('expert_layers'):
        return None
    return slots / (held * lm['expert_layers'])


def load_max_over_mean(run: dict):
    worst, mean = device_scopes.counter_mean(run, 'moe.load_max'), slots_per_expert(run)
    return None if worst is None or not mean else worst / mean


READERS = {
    'moe_route_device_ms.train': Reader(
        'experts', 'ms', 'lower', 'device_trace', lambda run: device_scopes.scope_ms(run, 'glm.moe.route'),
        'device ms a step under `glm.moe.route`: router, top-k, sort, gather, weighted scatter; the memory-bound part'),
    'moe_device_ms.train': Reader(
        'experts', 'ms', 'lower', 'device_trace',
        lambda run: device_scopes.scope_ms(run, 'glm.moe.route', 'glm.moe.experts', 'glm.moe.shared'),
        'device ms a step under the expert layers\' scopes: routing and dispatch, grouped products, shared expert'),
    'moe_experts_mfu.train': Reader(
        'experts', '%', 'higher', 'device_trace', lambda run: device_scopes.part_mfu(run, 'glm.moe.experts', 'moe_experts'),
        'roofline share of the grouped products (compute-bound): the counted slots\' operations, forward and backward, '
        'over the device time under `glm.moe.experts`, over the bf16 peak'),
    'mla_device_ms.train': Reader(
        'attention', 'ms', 'lower', 'device_trace', lambda run: device_scopes.scope_ms(run, 'glm.mla.proj', 'glm.mla.core'),
        'device ms a step under latent attention\'s scopes: projections, norms, rotary turn, causal core'),
    'mla_core_mfu.train': Reader(
        'attention', '%', 'higher', 'device_trace', lambda run: device_scopes.part_mfu(run, 'glm.mla.core', 'mla_core'),
        'roofline share of the causal core (compute-bound): the S(S+1)/2 pairs\' operations, forward and backward, over '
        'the device time under `glm.mla.core`, over the bf16 peak'),
    'moe_slots_per_expert.train': Reader(
        'experts', 'count', 'higher', 'program_counter', slots_per_expert,
        'mean `moe.local_slots` a step over the experts held and the expert layers: slots one expert works on in one layer'),
    'moe_load_max_over_mean.train': Reader(
        'experts', 'ratio', 'lower', 'program_counter', load_max_over_mean,
        'mean `moe.load_max` a step over `moe_slots_per_expert.train`: 1 = even routing (`moe.dropped_slots` is held at 0)'),
}


def entry(name: str, cells: list, readers: dict = None) -> dict:
    """The `per_layer` entry of `BENCHMARK.json` for one of the readings."""
    r = (READERS if readers is None else readers)[name]
    return {'name': name, 'unit': r.unit, 'better': r.better, 'source': r.source, 'layer': r.layer, 'moves': MOVES,
            'workloads': list(cells)}


def lines(run: dict) -> list:
    """One line a reading of `PRINTED` on this run's record."""
    out = []
    for name in PRINTED:
        r, value = READERS[name], READERS[name].read(run)
        out.append(f'reading {name}: ' + ('nothing to read' if value is None else f'{value:.6g} {r.unit}')
                   + f' (layer {r.layer}, {r.better} is better)')
    return out
