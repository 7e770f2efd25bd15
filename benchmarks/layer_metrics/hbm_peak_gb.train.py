"""The run's `memory_peak_bytes` (`harness/peaks.py`: `peak_bytes_in_use` +
`peak_bytes_reserved` of `jax.devices()[0].memory_stats()`), read when the
window closes, before the reference runs."""
LAYER = 'device'
UNIT = 'GB'
MOVES = 'train_img_per_s'


def read(run: dict):
    if run.get('runner') != 'train' or not run.get('memory_peak_bytes'):
        return None
    return run['memory_peak_bytes'] / 1e9
