"""The per-layer readings that the short-convolution language-model family alone has (ISSUE 43), as plain functions of
a run's record, beside `lm_readers.py`'s and `swa_lm_readers.py`'s, which read this family's records too (its one
attention layer runs under `swa.attn.*`, its experts under `glm.moe.*`: those metrics list the cell and add no code).
The six here are metrics of `BENCHMARK.json`, each with its file `layer_metrics/<name>.py`: three of the short
convolution, and three of the dense SwiGLU and the head, which this family shares with the GLM family scope for scope. Like the others they
name a scope, a part of the record's `needed_macs` or a counter, and find nothing in a record that lacks it (another
family's, an image cell's, a parent older than the scopes, an empty one); nothing here raises for that.
"""
from __future__ import annotations

from . import device_scopes, lm_readers
from .lm_readers import Reader
from .swa_lm_readers import declared_scopes  # noqa: F401  every name `tracing.SPANS` declares as a device scope

# every device scope the cell's step runs under -> the part of `sconv_lm_flops.forward_macs` computed under it
SCOPE_PARTS = {'glm.embed': None, 'sconv.proj': 'sconv_proj', 'sconv.mix': None, 'swa.attn.proj': 'attn_proj',
               'swa.attn.core_full': 'attn_core_full', 'glm.dense_ffn': 'dense_ffn', 'glm.moe.route': 'moe_route',
               'glm.moe.experts': 'moe_experts', 'glm.head_loss': 'head'}


def mix_hbm_share(run: dict):
    """% of the chip's HBM bandwidth that the bytes the middles need (`sconv_lm_flops.mix_bytes` of the step's
    `sconv.rows`) make over the device time under `sconv.mix`. Bytes and time are of the same ops: the program keeps
    what the middle reads and gives behind barriers (`layers/short_conv.py` `_written`), so no product's fusion holds
    an op of the middle and the scope holds all of it. A program that lets them fuse again reads over 100 here,
    and the driver refuses that: nothing is cut off."""
    from . import peaks, sconv_lm_flops
    rows, ms = device_scopes.counter_mean(run, 'sconv.rows'), device_scopes.scope_ms(run, 'sconv.mix')
    if not rows or not ms or 'hidden_size' not in (run.get('sizes') or {}):
        return None
    needed = sconv_lm_flops.mix_bytes(rows, run['sizes']['hidden_size'])
    return 100.0 * needed / (ms / 1e3) / peaks.peak(run['device_kind'])['hbm_bytes_per_s']


READERS = {
    'sconv_device_ms.train': Reader(
        'short convolution', 'ms', 'lower', 'device_trace', lambda run: device_scopes.scope_ms(run, 'sconv.proj', 'sconv.mix'),
        'device ms a step under `sconv.proj` and `sconv.mix`: the two products with the norm before them, the gates and the taps'),
    'sconv_proj_mfu.train': Reader(
        'short convolution', '%', 'higher', 'device_trace', lambda run: device_scopes.part_mfu(run, 'sconv.proj', 'sconv_proj'),
        'roofline share of the mixer\'s two products (compute-bound): their operations, forward and backward, over the '
        'device time under `sconv.proj`, over the bf16 peak'),
    'sconv_mix_hbm_share.train': Reader(
        'short convolution', '%', 'higher', 'device_trace', mix_hbm_share,
        'roofline share of the gates and taps (memory-bound): the bytes they need to move, forward and backward '
        '(`sconv_lm_flops.mix_bytes` of `sconv.rows`), over the device time under `sconv.mix`, over the HBM peak'),
    # the two parts of the cell's `why` that no accepted metric reads: the leading dense SwiGLU and the head. Both scopes
    # and both parts are the GLM family's too (`device_scopes.SCOPE_PARTS`), whose cell lists them
    'dense_ffn_device_ms.train': Reader(
        'feed-forward', 'ms', 'lower', 'device_trace', lambda run: device_scopes.scope_ms(run, 'glm.dense_ffn'),
        'device ms a step under `glm.dense_ffn`: the dense SwiGLU of the leading layers, forward and backward'),
    'dense_ffn_mfu.train': Reader(
        'feed-forward', '%', 'higher', 'device_trace', lambda run: device_scopes.part_mfu(run, 'glm.dense_ffn', 'dense_ffn'),
        'roofline share of the leading dense SwiGLU (compute-bound): its three products, forward and backward, over the '
        'device time under `glm.dense_ffn`, over the bf16 peak'),
    'head_device_ms.train': Reader(
        'step', 'ms', 'lower', 'device_trace', lambda run: device_scopes.scope_ms(run, 'glm.head_loss'),
        'device ms a step under `glm.head_loss`: the final norm, the head\'s product in chunks and the loss, forward and '
        'backward; where the head is tied, the embedding leaf\'s second gradient'),
}


def entry(name: str, cells: list) -> dict:
    """The `per_layer` entry of `BENCHMARK.json` for one of the readings."""
    return lm_readers.entry(name, cells, READERS)
