"""The per-layer readings that the delta-rule language-model family alone has (ISSUE 47), as plain functions of a run's
record, beside `lm_readers.py`'s, `swa_lm_readers.py`'s and `sconv_lm_readers.py`'s, which read this family's records
too (its one attention layer runs under `swa.attn.*`, its experts under `glm.moe.*`, its head under `glm.head_loss`:
those metrics list the cell and add no code). The four here are metrics of `BENCHMARK.json`, each with its file
`layer_metrics/<name>.py`. Like the others they name a scope, a part of the record's `needed_macs` or a counter, and
find nothing in a record that lacks it (another family's, an image cell's, a parent older than the scopes, an empty one);
nothing here raises for that.
"""
from __future__ import annotations

from . import device_scopes, lm_readers
from .lm_readers import Reader
from .swa_lm_readers import declared_scopes  # noqa: F401  every name `tracing.SPANS` declares as a device scope

# every device scope the cell's step runs under -> the part of `kda_lm_flops.forward_macs` computed under it
SCOPE_PARTS = {'glm.embed': None, 'kda.proj': 'kda_proj', 'kda.mix': None, 'kda.core': 'kda_core',
               'swa.attn.proj': 'attn_proj', 'swa.attn.core_full': 'attn_core_full', 'glm.moe.route': 'moe_route',
               'glm.moe.experts': 'moe_experts', 'glm.moe.shared': 'moe_shared', 'glm.head_loss': 'head'}


def mix_hbm_share(run: dict):
    """% of the chip's HBM bandwidth that the bytes the middles need (`kda_lm_flops.mix_bytes` of the step's `kda.rows`)
    make over the device time under `kda.mix`. Bytes and time are of the same ops: the program keeps what the middle
    reads and gives behind barriers (`layers/delta_attention.py` `_written`). Nothing is cut off at 100."""
    from . import kda_lm_flops, peaks
    rows, ms, sizes = device_scopes.counter_mean(run, 'kda.rows'), device_scopes.scope_ms(run, 'kda.mix'), run.get('sizes') or {}
    if not rows or not ms or 'heads_held' not in sizes or 'head_dim' not in sizes:
        return None
    needed = kda_lm_flops.mix_bytes(rows, sizes['heads_held'] * sizes['head_dim'])
    return 100.0 * needed / (ms / 1e3) / peaks.peak(run['device_kind'])['hbm_bytes_per_s']


def chunk_positions(run: dict):
    """Positions a chunk of the recurrence as the program ran it, from the step's own counters: `kda.rows` x heads held
    over `kda.chunks` (64 unless the layer's chunk changed). No metric: a traced run prints it (`lines`), so that
    `kda_core_mfu.train` is read beside the chunk it was measured at."""
    rows, chunks = device_scopes.counter_mean(run, 'kda.rows'), device_scopes.counter_mean(run, 'kda.chunks')
    held = (run.get('sizes') or {}).get('heads_held')
    return rows * held / chunks if rows and chunks and held else None


def lines(run: dict) -> list:
    """The family's readings that are no metric, one line each."""
    value = chunk_positions(run)
    return ['reading kda_chunk_positions.train: ' + ('nothing to read' if value is None else f'{value:.6g} positions a chunk')]


READERS = {
    'kda_device_ms.train': Reader(
        'delta attention', 'ms', 'lower', 'device_trace', lambda run: device_scopes.scope_ms(run, 'kda.'),
        'device ms a step under `kda.*`: the mixer\'s products with the norm before them, its elementwise middle, and the '
        'chunked recurrence, forward, rematerialised and backward'),
    'kda_proj_mfu.train': Reader(
        'delta attention', '%', 'higher', 'device_trace', lambda run: device_scopes.part_mfu(run, 'kda.proj', 'kda_proj'),
        'roofline share of the mixer\'s products (compute-bound): q, k, v, o, the two low-rank gates and beta, forward and '
        'backward, over the device time under `kda.proj`, over the bf16 peak'),
    'kda_core_mfu.train': Reader(
        'delta attention', '%', 'higher', 'device_trace', lambda run: device_scopes.part_mfu(run, 'kda.core', 'kda_core'),
        'share of the bf16 peak that the RECURRENCE\'s operations (4 x d_k x d_v MACs a position and head, forward and '
        'backward, whatever chunk or kernel computes them) make over the device time under `kda.core`'),
    'kda_mix_hbm_share.train': Reader(
        'delta attention', '%', 'higher', 'device_trace', mix_hbm_share,
        'roofline share of the elementwise middle (memory-bound): the bytes it needs to move once, forward and backward '
        '(`kda_lm_flops.mix_bytes` of `kda.rows`), over the device time under `kda.mix`, over the HBM peak'),
}


def entry(name: str, cells: list) -> dict:
    """The `per_layer` entry of `BENCHMARK.json` for one of the readings."""
    return lm_readers.entry(name, cells, READERS)
