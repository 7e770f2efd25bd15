"""The per-layer readings that the block-diffusion language-model family alone has (ISSUE 37), as plain functions
of a run's record, beside `lm_readers.py`'s and `swa_lm_readers.py`'s, which read this family's records too: its
step runs under `glm.moe.*`, `swa.attn.proj` and `glm.head_loss`, and its runner puts the parts `moe_route`,
`moe_experts`, `attn_proj` and `head` into the record, so `moe_*` and `attn_device_ms.train` / `attn_proj_mfu.train`
list its cell and need no new code. The two here are metrics of `BENCHMARK.json`, each with its file
`layer_metrics/<name>.py`. They name a scope, a part of the record's `needed_macs` or a counter, and find nothing
in a record that lacks it (another family's, an image cell's, a parent older than them, an empty one); nothing here
raises for that.
"""
from __future__ import annotations

import math

from . import device_scopes, lm_readers
from .lm_readers import Reader
from .swa_lm_readers import declared_scopes  # noqa: F401  both kinds of device scope

# every device scope the cell's step runs under -> the part of `bd_lm_flops.forward_macs` computed under it
SCOPE_PARTS = {'glm.embed': None, 'swa.attn.proj': 'attn_proj', 'swa.attn.core_bd': 'attn_core_bd',
               'glm.moe.route': 'moe_route', 'glm.moe.experts': 'moe_experts', 'glm.head_loss': 'head'}


def block_side(run: dict):
    """Positions a side of a tile, from the cores' own count. Over n = L / side query blocks a half, a layer
    multiplies n (n + 1) / 2 clean-on-clean tiles, as many noised-on-clean ones and the n of the noised
    diagonal, and a last layer its noised queries' alone: tiles a sequence = (layers - 1) (n^2 + 2 n) +
    (n^2 + 3 n) / 2. None where no whole n gives the count (tiles not square, or narrower than a block)."""
    lm, tiles, sizes = run.get('lm'), device_scopes.counter_mean(run, 'attn.bd_blocks'), run.get('sizes') or {}
    if not lm or not tiles or 'block_length' not in sizes:
        return None
    a, b = sizes['num_hidden_layers'] - 0.5, 2 * sizes['num_hidden_layers'] - 0.5
    n = (math.sqrt(b * b + 4 * a * tiles / lm['sequences']) - b) / (2 * a)
    return lm['seq_len'] / round(n) if n >= 1 and abs(n - round(n)) < 1e-6 else None


def block_fill(run: dict):
    from . import bd_lm_flops
    side, tiles = block_side(run), device_scopes.counter_mean(run, 'attn.bd_blocks')
    if side is None or not tiles:
        return None
    lm, sizes = run['lm'], run['sizes']
    pairs = ((sizes['num_hidden_layers'] - 1) * bd_lm_flops.mask_pairs(lm['seq_len'], sizes['block_length'])
             + bd_lm_flops.noised_pairs(lm['seq_len'], sizes['block_length'])) * lm['sequences']
    return 100.0 * pairs / (tiles * side * side)


READERS = {
    'attn_bd_core_mfu.train': Reader(
        'attention', '%', 'higher', 'device_trace', lambda run: device_scopes.part_mfu(run, 'swa.attn.core_bd', 'attn_core_bd'),
        'roofline share of the core under the block-diffusion mask (compute-bound), on the NEEDED pairs (L^2 + L K a '
        'layer, the last layer its noised queries\' alone), forward and backward, over the device time under '
        '`swa.attn.core_bd`, over the bf16 peak: a core that multiplies tiles the mask excludes reads lower, never higher'),
    'attn_bd_block_fill.train': Reader(
        'attention', '%', 'higher', 'program_counter', block_fill,
        'needed pairs over `attn.bd_blocks` x the tile\'s area: what the block shape wastes along the two diagonals'),
}


def entry(name: str, cells: list) -> dict:
    """The `per_layer` entry of `BENCHMARK.json` for one of the readings."""
    return lm_readers.entry(name, cells, READERS)
