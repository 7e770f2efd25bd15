"""The per-layer readings of the window/full language-model cell (ISSUE 31), as plain functions of a run's
record, beside `lm_readers.py`'s for the GLM cell and for the same reason not metrics of `BENCHMARK.json` yet:
`test_program_spans.py:39` pins `per_layer[8:]` (PERF.md section 7 (a)). A traced run prints them as
`reading <name>: <value> <unit>` (`lines`); the `benchmark` PR that lifts the pin adds `entry(name, cells)` and a
four-line `layer_metrics/<name>.py` for each, as `test_swa_lm_harness.py`'s toy manifest does. Where the GLM cell
has a reading of the same name (`lm_step_mfu.train`, the five `moe_*`), name, unit, layer and direction are the
same here, so that one metric will list both cells; `read_any` reads a record of either family.

The operations are `swa_lm_flops.py`'s, the scopes `swa.attn.*` and the shared `glm.*` ones, the counters the
step's own. A record without them (a parent older than them, the GLM cell's, an image cell's, an empty one) gives
None; nothing here raises for that.
"""
from __future__ import annotations

import math

from . import device_scopes, lm_readers
from .lm_readers import MOVES, Reader

# every device scope the cell's step runs under -> the part of `swa_lm_flops.forward_macs` computed under it
SCOPE_PARTS = {'glm.embed': None, 'swa.attn.proj': 'attn_proj', 'swa.attn.core_full': 'attn_core_full',
               'swa.attn.core_window': 'attn_core_window', 'glm.moe.route': 'moe_route',
               'glm.moe.experts': 'moe_experts', 'glm.head_loss': 'head'}


def declared_scopes() -> set:
    """The device scopes `tracing.SPANS` declares, the window/full family's among them (their kind reads
    'swa device scope': `device_scopes.declared_scopes()` is held to the nine it had)."""
    try:
        from timm_tpu.utils import tracing
    except ImportError:
        return set()
    return {name for name, (_, what) in tracing.SPANS.items() if what.split(':')[0].endswith('device scope')}


def ours(run: dict) -> bool:
    """Whether the record is a run of this family: its sizes name the layers' kinds."""
    return 'sliding_window_layout' in (run.get('sizes') or {})


def _scoped(read):
    """A reading by scope alone, kept to this family's records (the GLM cell's own reader reads its)."""
    return lambda run: read(run) if ours(run) else None


def needed_macs(run: dict):
    """`swa_lm_flops.forward_macs` of one of the run's steps; None where the record is not this family's."""
    from . import swa_lm_flops
    lm, sizes = run.get('lm'), run.get('sizes') or {}
    slots = device_scopes.counter_mean(run, 'moe.local_slots')
    if not lm or slots is None or not ours(run):
        return None
    return swa_lm_flops.forward_macs(sizes, lm['seq_len'], lm['sequences'], slots)


def _peak(run: dict) -> float:
    from . import peaks
    return peaks.peak(run['device_kind'])['bf16_flops']


def step_mfu(run: dict):
    from . import swa_lm_flops
    trace, macs = run.get('trace'), needed_macs(run)
    if run.get('runner') != 'train' or not trace or not trace.get('work') or macs is None:
        return None
    return 100.0 * swa_lm_flops.train_flops(macs) / (trace['busy_s'] / trace['work']) / _peak(run)


def scope_mfu(run: dict, scope: str):
    """% of the bfloat16 peak that the needed operations of `scope`'s part make over the scope's device time."""
    from . import swa_lm_flops
    macs, ms = needed_macs(run), device_scopes.scope_ms(run, scope)
    if macs is None or not ms or SCOPE_PARTS.get(scope) is None:
        return None
    return 100.0 * swa_lm_flops.train_flops(macs[SCOPE_PARTS[scope]]) / (ms / 1e3) / _peak(run)


def slots_per_expert(run: dict):
    slots, sizes = device_scopes.counter_mean(run, 'moe.local_slots'), run.get('sizes') or {}
    if not slots or not ours(run):
        return None
    return slots / (sizes['experts_held'] * sizes['num_hidden_layers'])


def load_max_over_mean(run: dict):
    worst, mean = device_scopes.counter_mean(run, 'moe.load_max'), slots_per_expert(run)
    return None if worst is None or not mean else worst / mean


def block_side(run: dict):
    """Positions a side of a tile, from the full cores' own count: a full core over n query blocks multiplies
    n (n + 1) / 2 tiles a layer and sequence."""
    from . import swa_lm_flops
    lm, sizes = run.get('lm'), run.get('sizes') or {}
    tiles = device_scopes.counter_mean(run, 'attn.full_blocks')
    if not lm or not tiles or not ours(run):
        return None
    full, _ = swa_lm_flops.layer_kinds(sizes)
    n = (math.sqrt(1 + 8 * tiles / (full * lm['sequences'])) - 1) / 2 if full else 0
    return lm['seq_len'] / n if n >= 1 and abs(n - round(n)) < 1e-6 else None


def window_block_fill(run: dict):
    from . import swa_lm_flops
    side, tiles = block_side(run), device_scopes.counter_mean(run, 'attn.window_blocks')
    if side is None or not tiles:
        return None
    lm, sizes = run['lm'], run['sizes']
    pairs = swa_lm_flops.window_pairs(lm['seq_len'], sizes['sliding_window_size']) * lm['sequences'] * swa_lm_flops.layer_kinds(sizes)[1]
    return 100.0 * pairs / (tiles * side * side)


def _shared(name: str, read, what: str = None) -> Reader:
    """A reading the GLM cell has too: its layer, unit, direction and source, this family's `read`."""
    r = lm_readers.READERS[name]
    return Reader(r.layer, r.unit, r.better, r.source, read, what or r.what)


READERS = {
    'lm_step_mfu.train': _shared(
        'lm_step_mfu.train', step_mfu,
        'needed operations of a step (`swa_lm_flops.py`: forward MACs x 2 x 3, the causal and the window pairs of the '
        'cores, the routed experts by `moe.local_slots`, nothing recomputed) over busy device time a step, over the bf16 peak'),
    'attn_device_share.train': Reader(
        'attention', '%', 'lower', 'device_trace', _scoped(lambda run: device_scopes.scope_share(run, 'swa.attn.')),
        'share of busy device time under `swa.attn.*`: the q/k/v/o products with norm and rotary turn, and both kinds of core'),
    'attn_proj_mfu.train': Reader(
        'attention', '%', 'higher', 'device_trace', lambda run: scope_mfu(run, 'swa.attn.proj'),
        'roofline share of the q/k/v/o products (compute-bound) over the device time under `swa.attn.proj`, over the bf16 peak'),
    'attn_full_core_mfu.train': Reader(
        'attention', '%', 'higher', 'device_trace', lambda run: scope_mfu(run, 'swa.attn.core_full'),
        'roofline share of the full layers\' causal core (compute-bound): the S(S+1)/2 pairs\' operations, forward and '
        'backward, over the device time under `swa.attn.core_full`, over the bf16 peak'),
    'attn_window_core_mfu.train': Reader(
        'attention', '%', 'higher', 'device_trace', lambda run: scope_mfu(run, 'swa.attn.core_window'),
        'the same for the window layers\' core, on the NEEDED pairs (i - j < window): a core that multiplies tiles the '
        'window excludes reads lower, never higher'),
    'attn_window_block_fill.train': Reader(
        'attention', '%', 'higher', 'program_counter', window_block_fill,
        'needed window pairs over `attn.window_blocks` x the tile\'s area: what the block shape wastes at the window\'s two edges'),
    'moe_device_share.train': _shared('moe_device_share.train', _scoped(lm_readers.READERS['moe_device_share.train'].read)),
    'moe_experts_mfu.train': _shared('moe_experts_mfu.train', lambda run: scope_mfu(run, 'glm.moe.experts')),
    'moe_route_device_ms.train': _shared('moe_route_device_ms.train', _scoped(lm_readers.READERS['moe_route_device_ms.train'].read)),
    'moe_slots_per_expert.train': _shared('moe_slots_per_expert.train', slots_per_expert),
    'moe_load_max_over_mean.train': _shared('moe_load_max_over_mean.train', load_max_over_mean),
}


def read_any(name: str, run: dict):
    """A reading of a record of either language-model family: this family's reader first, the GLM cell's where
    that finds nothing and has the name. What a `layer_metrics/<name>.py` listing both cells will call."""
    value = READERS[name].read(run) if name in READERS else None
    if value is None and name in lm_readers.READERS:
        value = lm_readers.READERS[name].read(run)
    return value


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


def scope_table(run: dict) -> list:
    """One line a scope: ms a traced step, share of busy time, roofline share of its part; then the cover."""
    scopes = (run.get('trace') or {}).get('scopes') or {}
    if not scopes.get('scope_s'):
        return ['device scopes: none in the trace']
    out = []
    for scope in SCOPE_PARTS:
        ms, share, mfu = device_scopes.scope_ms(run, scope), device_scopes.scope_share(run, scope), scope_mfu(run, scope)
        if ms is not None:
            out.append(f'device scope {scope}: {ms:.2f} ms a step, {share:.1f} % of busy'
                       + (f', {mfu:.1f} % of peak on its needed operations' if mfu is not None else ''))
    out.append(f'device scopes cover {device_scopes.scope_share(run, *SCOPE_PARTS):.1f} % of busy device time; outside them: '
               + ', '.join(f'{k} {v * 1e3:.1f} ms' for k, v in scopes['unscoped'][:5]))
    return out
