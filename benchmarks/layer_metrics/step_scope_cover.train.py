"""% of the step program's busy device time under ANY declared device scope (a union of intervals over a union
of intervals: never over 100). What is outside has no name in `tools/scope_report.py`'s table."""
LAYER = 'device'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import step_scopes
    return step_scopes.cover(run)
