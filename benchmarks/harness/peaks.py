"""The one table of chip peaks, keyed by `jax.devices()[0].device_kind`.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bfloat16,
394 TOP/s in int8, 16 GB of HBM at 819 GB/s per chip. A device that is not in
the table is an error, never a default. Beside it: how this runtime's memory
statistics add up to a peak.
"""
from __future__ import annotations

PEAKS = {
    'TPU v5 lite': {'bf16_flops': 197e12, 'hbm_bytes_per_s': 819e9, 'hbm_bytes': 16e9},
    'TPU v5e': {'bf16_flops': 197e12, 'hbm_bytes_per_s': 819e9, 'hbm_bytes': 16e9},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f'no peaks known for device kind {device_kind!r} (have {sorted(PEAKS)})') from None


def memory_peak_bytes(stats: dict) -> int:
    """Peak device memory from `Device.memory_stats()`: the peak of live
    buffers plus the peak the runtime reserved for the loaded programs'
    temporaries. On a TPU `peak_bytes_in_use` alone leaves out a step's
    activations (ViT-B bs128: 2.4 GB live, 7.5 GB reserved, and the largest
    free block shrinks by both; PERF.md section 2)."""
    return int(stats.get('peak_bytes_in_use', 0)) + int(stats.get('peak_bytes_reserved', 0))
