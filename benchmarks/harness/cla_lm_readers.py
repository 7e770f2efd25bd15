"""The per-layer readings that the chunk-pooled linear-attention family alone has (ISSUE 41), as plain functions of
a run's record, beside `lm_readers.py`'s. The six here are metrics of `BENCHMARK.json`, each with its file
`layer_metrics/<name>.py`. Like `lm_readers.py`'s they name a scope, a part of the record's `needed_macs` or a
counter, and find nothing in a record that lacks it (another family's, an image cell's, a parent older than the
scopes, an empty one); nothing here raises for that.
"""
from __future__ import annotations

from . import device_scopes, lm_readers
from .lm_readers import Reader
from .swa_lm_readers import declared_scopes  # noqa: F401  every name `tracing.SPANS` declares as a device scope

# every device scope the cell's step runs under -> the part of `cla_lm_flops.forward_macs` computed under it
SCOPE_PARTS = {'glm.embed': None, 'evabyte.attn.proj': 'attn_proj', 'evabyte.attn.summary': 'attn_summary',
               'evabyte.attn.core': 'attn_core', 'evabyte.ffn': 'ffn', 'glm.head_loss': 'head'}
SIDES = (1024, 512, 256, 128, 64, 32, 16, 8)        # tile sides a core may count its visited tiles in


def block_side(run: dict):
    """Positions a side of a tile, from the core's own count: the side at which the tiles the mask needs
    (`cla_lm_flops.visited_tiles`, from the shapes) are as many as `attn.eva_blocks` says the core visited."""
    from . import cla_lm_flops
    lm, sizes, tiles = run.get('lm'), run.get('sizes') or {}, device_scopes.counter_mean(run, 'attn.eva_blocks')
    if not lm or not tiles or 'window_size' not in sizes:
        return None
    for side in SIDES:
        one = cla_lm_flops.visited_tiles(lm['seq_len'], sizes['window_size'], sizes['chunk_size'], side)
        if one and one * sizes['num_hidden_layers'] * lm['sequences'] == tiles:
            return side
    return None


def block_fill(run: dict):
    side, pairs = block_side(run), device_scopes.counter_mean(run, 'attn.eva_pairs')
    if side is None or not pairs:
        return None
    tiles = device_scopes.counter_mean(run, 'attn.eva_blocks')
    return 100.0 * pairs / (tiles * run['sizes']['heads_held'] * side * side)


def summary_hbm_share(run: dict):
    from . import cla_lm_flops, peaks
    lm, sizes, ms = run.get('lm'), run.get('sizes') or {}, device_scopes.scope_ms(run, 'evabyte.attn.summary')
    if not lm or not ms or 'chunk_size' not in sizes:
        return None
    needed = cla_lm_flops.summary_bytes(sizes, lm['seq_len'], lm['sequences'])
    return 100.0 * needed / (ms / 1e3) / peaks.peak(run['device_kind'])['hbm_bytes_per_s']


READERS = {
    'eva_device_ms.train': Reader(
        'attention', 'ms', 'lower', 'device_trace', lambda run: device_scopes.scope_ms(run, 'evabyte.attn.'),
        'device ms a step under `evabyte.attn.*`: the q/k/v/o products with norm and rotary turn, the chunk summaries, the core'),
    'eva_core_mfu.train': Reader(
        'attention', '%', 'higher', 'device_trace', lambda run: device_scopes.part_mfu(run, 'evabyte.attn.core', 'attn_core'),
        'roofline share of the core (compute-bound) on the NEEDED pairs of both kinds (a window\'s single keys, the earlier '
        'windows\' summaries), forward and backward, over the device time under `evabyte.attn.core`, over the bf16 peak: a '
        'core that multiplies masked tiles reads lower, never higher'),
    'eva_block_fill.train': Reader(
        'attention', '%', 'higher', 'program_counter', block_fill,
        '`attn.eva_pairs` over `attn.eva_blocks` x the tile\'s area x the heads held: what the block shape wastes on the '
        'windows\' diagonals and on the summaries\' partly seen block'),
    'eva_summary_hbm_share.train': Reader(
        'attention', '%', 'higher', 'device_trace', summary_hbm_share,
        'roofline share of the chunk summaries (memory-bound): the bytes they need to move, forward and backward '
        '(`cla_lm_flops.summary_bytes`), over the device time under `evabyte.attn.summary`, over the HBM peak'),
    'ffn_device_ms.train': Reader(
        'feed-forward', 'ms', 'lower', 'device_trace', lambda run: device_scopes.scope_ms(run, 'evabyte.ffn'),
        'device ms a step under `evabyte.ffn`: the dense SwiGLU of every layer, its norm and residual add'),
    'ffn_mfu.train': Reader(
        'feed-forward', '%', 'higher', 'device_trace', lambda run: device_scopes.part_mfu(run, 'evabyte.ffn', 'ffn'),
        'roofline share of the dense SwiGLU (compute-bound): its three products, forward and backward, over the device '
        'time under `evabyte.ffn`, over the bf16 peak'),
}


def entry(name: str, cells: list) -> dict:
    """The `per_layer` entry of `BENCHMARK.json` for one of the readings."""
    return lm_readers.entry(name, cells, READERS)
