"""The per-layer readings that the window/full language-model family alone has (ISSUE 31), as plain functions
of a run's record, beside `lm_readers.py`'s, which read this family's records too (`moe_route_device_ms.train`,
`moe_device_ms.train`, `moe_experts_mfu.train`: one metric lists both cells). The five here are metrics of
`BENCHMARK.json` since PR 35, each with its file `layer_metrics/<name>.py`. Like `lm_readers.py`'s they name a
scope, a part of the record's `needed_macs` or a counter, and find nothing in a record that lacks it (the GLM
cell's, an image cell's, a parent older than them, an empty one); nothing here raises for that.
"""
from __future__ import annotations

import math

from . import device_scopes, lm_readers
from .lm_readers import Reader

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


def block_side(run: dict):
    """Positions a side of a tile, from the full cores' own count: a full core over n query blocks multiplies
    n (n + 1) / 2 tiles a layer and sequence."""
    from . import swa_lm_flops
    lm, tiles = run.get('lm'), device_scopes.counter_mean(run, 'attn.full_blocks')
    if not lm or not tiles:
        return None
    full, _ = swa_lm_flops.layer_kinds(run['sizes'])
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


READERS = {
    'attn_device_ms.train': Reader(
        'attention', 'ms', 'lower', 'device_trace', lambda run: device_scopes.scope_ms(run, 'swa.attn.'),
        'device ms a step under `swa.attn.*`: the q/k/v/o products with norm and rotary turn, and both kinds of core'),
    'attn_proj_mfu.train': Reader(
        'attention', '%', 'higher', 'device_trace', lambda run: device_scopes.part_mfu(run, 'swa.attn.proj', 'attn_proj'),
        'roofline share of the q/k/v/o products (compute-bound) over the device time under `swa.attn.proj`, over the bf16 peak'),
    'attn_full_core_mfu.train': Reader(
        'attention', '%', 'higher', 'device_trace', lambda run: device_scopes.part_mfu(run, 'swa.attn.core_full', 'attn_core_full'),
        'roofline share of the full layers\' causal core (compute-bound): the S(S+1)/2 pairs\' operations, forward and '
        'backward, over the device time under `swa.attn.core_full`, over the bf16 peak'),
    'attn_window_core_mfu.train': Reader(
        'attention', '%', 'higher', 'device_trace',
        lambda run: device_scopes.part_mfu(run, 'swa.attn.core_window', 'attn_core_window'),
        'roofline share of the window layers\' causal core (compute-bound), on the NEEDED pairs (i - j < window), over the '
        'device time under `swa.attn.core_window`, over the bf16 peak: a core that multiplies tiles the window excludes '
        'reads lower, never higher'),
    'attn_window_block_fill.train': Reader(
        'attention', '%', 'higher', 'program_counter', window_block_fill,
        'needed window pairs over `attn.window_blocks` x the tile\'s area: what the block shape wastes at the window\'s two edges'),
}


def entry(name: str, cells: list) -> dict:
    """The `per_layer` entry of `BENCHMARK.json` for one of the readings."""
    return lm_readers.entry(name, cells, READERS)
