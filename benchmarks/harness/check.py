"""The comparison that decides `correct`: every number compared is printed
beside its limit, and one number over its limit makes the run not correct.
The limits live in the configuration's file (`limits`), each set from chip
readings recorded in PERF.md section 2. What was compared is also kept as
data (`compared`, the `into` of `judge` and `judge_exact`): the record's
`checks`, which `run.py` closes its result line and its stderr with."""
from __future__ import annotations

import math
import statistics


def worst_leaf_gap(program: dict, reference: dict) -> tuple:
    """The largest gap, over leaves, between the program's norm and the
    reference's norm of that leaf, measured against the reference's norm of the
    leaf or of the median leaf, whichever is larger (some gradients are all
    but zero). Returns (gap, leaf)."""
    if set(program) != set(reference):
        raise ValueError(f'leaves differ: {sorted(set(program) ^ set(reference))[:4]}')
    median = statistics.median(reference.values())
    worst, where = 0.0, ''
    for name, ref in reference.items():
        gap = abs(program[name] - ref) / max(ref, median, 1e-30)
        if not gap <= worst:  # a NaN gap is the worst there is
            worst, where = (gap, name) if math.isfinite(gap) else (math.inf, name)
    return worst, where


def training_numbers(program: dict, reference: dict) -> dict:
    """name -> (value, note) from the program's and the reference's
    `losses`, `first_grad_norms`, `param_change_norms` and, where the recipe
    keeps one, `ema_change_norms`."""
    numbers = {}
    for i, (a, b) in enumerate(zip(program['losses'], reference['losses']), start=1):
        numbers[f'loss_gap_step{i}'] = (abs(a - b) if math.isfinite(a) else math.inf, f'program {a:.6f} reference {b:.6f}')
    gap, leaf = worst_leaf_gap(program['first_grad_norms'], reference['first_grad_norms'])
    numbers['first_grad_norm_gap'] = (gap, f'worst leaf {leaf}')
    for what in ('param_change_norms', 'ema_change_norms'):
        if what in program:
            gap, leaf = worst_leaf_gap(program[what], reference[what])
            numbers[what[:-1] + '_gap'] = (gap, f'worst leaf {leaf}')
    return numbers


def feed_numbers(followed: list, recipe: dict) -> dict:
    """name -> (value, limit, note) of the followed steps' batches as the step
    got them: no row given twice (a feed that repeats a batch or a row), every
    target smoothed, and mixup or cutmix seen at all — a row whose target
    names two classes. One lambda serves a whole batch and may come out as 1,
    so 'every row mixed' would fail sound runs; 'no row in three batches' does
    not."""
    smoothing = recipe.get('smoothing', 0.0)
    rows = [row.tobytes() for step in followed for row in step['input']]
    targets = [t for step in followed for t in step['target'].astype('float32')]
    off = smoothing / len(targets[0])
    hard = sum(float(t.max()) > 1.0 - smoothing / 2 for t in targets) if smoothing else 0
    mixed = sum(int((t > 1.5 * off + 1e-6).sum()) >= 2 for t in targets)
    numbers = {'feed_repeated_rows': (len(rows) - len(set(rows)), 0, f'{len(rows)} rows in {len(followed)} batches'),
               'feed_hard_targets': (hard, 0, f'largest entry over {1.0 - smoothing / 2:g}')}
    if recipe.get('mixup') or recipe.get('cutmix'):
        numbers['feed_never_mixed'] = (int(mixed == 0), 0, f'{mixed} rows name two classes')
    return numbers


def rng_numbers(before: dict, after: dict, calls: int) -> dict:
    """name -> (value, limit, note): every stochastic-depth site's stream
    handed out one key for each step the task was called for."""
    off = sorted(site for site in before if after.get(site, -1) - before[site] != calls)
    return {'rng_counts_off': (len(off) + int(set(after) != set(before)), 0,
                               f'{len(before)} sites, {calls} steps' + (f', e.g. {off[0]}' if off else ''))}


def compared(value, limit, ok: bool, how: str = 'at most') -> dict:
    """One number compared, as the record's `checks` keep it: the number, its limit, how the limit is meant
    (`at most`, `at least`, `equal`) and the verdict. A number that is not finite is kept as its name: the result
    line is strict JSON."""
    value = float(value)
    return {'value': value if math.isfinite(value) else str(value), 'limit': limit, 'how': how, 'ok': bool(ok)}


def judge_exact(numbers: dict, out=print, into: dict = None) -> bool:
    ok = True
    for name, (value, limit, note) in numbers.items():
        within = value == limit
        ok = ok and within
        out(f'check {name}: {value} limit {limit} {"ok" if within else "OVER"} ({note})')
        if into is not None:
            into[name] = compared(value, limit, within, 'equal')
    return ok


def judge(numbers: dict, limits: dict, out=print, into: dict = None) -> bool:
    """Print each number beside its limit, and keep it in `into`; True when every one is within."""
    ok = True
    for name, (value, note) in numbers.items():
        limit = limits['loss_gap' if name.startswith('loss_gap_step') else name]
        within = value <= limit
        ok = ok and within
        out(f'check {name}: {value:.6g} limit {limit:.6g} {"ok" if within else "OVER"} ({note})')
        if into is not None:
            into[name] = compared(value, limit, within)
    return ok
