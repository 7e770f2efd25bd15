"""1 - (union of device-op intervals / traced window), averaged over chips."""
LAYER = 'device'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    trace = run.get('trace')
    if run.get('runner') != 'train' or not trace:
        return None
    return 100.0 * trace['idle_share']
