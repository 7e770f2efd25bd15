"""The language-model training runner in its FOLDED form (cell `runner`:
`table_lm_train`; ROADMAP D19): `lm_train_runner.py`'s run — the same window,
clocks, record, followed steps, feed checks, route agreement, memory peak and
reference follow, all imported from there as the family runners import them —
with everything a family differs in read from a TABLE, the `family` block of
the configuration's file, so that `run` names no family:

  model_module      the program's module to import first: a program without the family fails there, before any work
  flops             the family's operation table in `harness/` (`forward_macs(sizes, seq_len, sequences, local_slots)`,
                    `expert_layers(sizes)`), which `needed_work` puts into the record
  readers           its scopes and readings in `harness/` (`declared_scopes()`, `SCOPE_PARTS`, and `lines(record)` where it
                    has readings that are no metric)
  own_counters      step counters the family's model returns beside `lm_train_runner.COUNTERS`
  expert_bias       absent, or {"experts_key": k}: ONE selection-bias vector of `sizes[k]` values, uniform in
                    +-`EXPERT_BIAS_SPAN`, drawn from `--seed`, placed in every expert layer's `score_bias` before anything
                    reads it and given to the reference as `sizes['expert_bias']` (`sconv_lm_train_runner.expert_bias` /
                    `place_expert_bias` / `BiasedLmStepWatcher`, imported, not copied)
  first_loss_head_std  the seeded head's std: an untrained model owes ln V + hidden_size x std^2 / 2 (0: ln V alone)

Limits are `limits['table_lm_train']`. The seeded weights are made a group of
leaves at a time (`seeded_weights`); a reference that has `finish_weights(seed,
sizes, weights)` draws there the leaves a normal draw does not fit. None of the
six family runners is edited or re-pointed by the PR that brought this file
(PR 47); the fold PERF.md section 7 (j) asks a `benchmark` PR for is now a
`family` block in each of five configurations and five deletions.
"""
from __future__ import annotations

import gc
import importlib
import math
import os
import statistics
import time
import traceback

from . import lm_train_runner
from .lm_train_runner import (COUNTERS, LmStepWatcher, build_argv, feed_numbers, memory_peak, reference_follow,
                              route_agreement)
from .sconv_lm_train_runner import EXPERT_BIAS_SPAN, BiasedLmStepWatcher, expert_bias
from .train_runner import FOLLOWED, WindowClosed

LIMITS = 'table_lm_train'
GROUP_SEED_STRIDE = 7919     # a group of leaves is drawn under `--seed` + this x its index


def _harness(name: str):
    return importlib.import_module(f'{__package__}.{name}')


def needed_work(config: dict, record: dict) -> dict:
    """`lm_train_runner.needed_work` by the operation table the configuration's `family` block names."""
    return lm_train_runner.needed_work(config, record, _harness(config['family']['flops']).forward_macs)


def seeded_weights(seed: int, reference, sizes: dict, host: bool) -> dict:
    """The run's weights from `--seed`, made a GROUP of leaves at a time (a block; the embedding; the head; ..), each group
    by `weights.make` under a seed of its own, then the reference's `finish_weights` where it has one. One call for all
    leaves holds the weights twice over on the device beside the task's parameters and moments: 841M parameters do not
    fit that way (GLM's 706M did). `host` gives numpy arrays, for the program: `load_task_weights` then copies leaf by
    leaf and no second device copy of the whole exists; the reference, which runs once the program's state is gone,
    takes device arrays. Same values either way."""
    import jax

    from . import weights
    groups = {}
    for name, entry in reference.init_spec(sizes).items():
        parts = name.split('.')
        groups.setdefault('.'.join(parts[:2]) if parts[0] == 'blocks' else parts[0], {})[name] = entry
    out = {}
    for i, group in enumerate(sorted(groups)):
        made = weights.make(seed + GROUP_SEED_STRIDE * i, groups[group])
        out.update(jax.device_get(made) if host else made)
    finish = getattr(reference, 'finish_weights', None)
    out = finish(seed, sizes, out) if finish else out
    return jax.device_get(out) if host else out


def counting(inner, names: tuple, seen: list):
    """`inner` (a `train_step`) with the counters `names` of every step's metrics kept, device arrays, in `seen`:
    `LmStepWatcher` keeps the names of `lm_train_runner.COUNTERS` only."""
    def step(task, batch, lr, step=0):
        metrics = inner(task, batch, lr, step)
        seen.append({name: metrics[name] for name in names if name in metrics})
        return metrics
    return step


def run(cell: dict, config: dict, *, seed: int, seconds: float, trace: bool, process_start: float,
        scratch: str, inner_step=None, control_precision=None, log=print) -> dict:
    """Run the cell once; returns the run record `run.py` reduces to the result line (the keys of
    `lm_train_runner.run`'s). `inner_step` replaces the program's `train_step` underneath the wrapper;
    `control_precision` also follows the steps with the reference in that lower precision (`tools/limits.py`,
    the tests; no benchmark run does)."""
    import jax

    family = config['family']
    importlib.import_module(family['model_module'])         # a program without the family fails here, before any work
    import train
    from timm_tpu.task import CausalLMTask
    from timm_tpu.utils.compile_cache import collect_cache_events, configure_compile_cache

    from . import check, device_scopes, lm_readers, lm_traffic
    from .manifest import reference_module
    from .peaks import memory_peak_bytes

    flops, readers, own_counters = _harness(family['flops']), _harness(family['readers']), tuple(family['own_counters'])
    configure_compile_cache()
    t_imported = time.perf_counter() - process_start
    reference = reference_module(config['reference'])
    sizes = config['sizes']
    biased = family.get('expert_bias')
    bias = expert_bias(seed, sizes[biased['experts_key']]) if biased else None
    followed_config = dict(config, sizes=dict(sizes, expert_bias=bias)) if biased else config     # what the reference is given
    stream = cell['traffic']['token_stream']
    data_dir = lm_traffic.write_token_stream(os.path.join(scratch, 'data', stream['name']), stream, sizes['vocab_held'])
    trace_dir = os.path.join(scratch, 'trace', cell['name']) if trace else None
    argv = build_argv(config, cell, seed, os.path.join(scratch, 'train'), data_dir)
    log('train.main ' + ' '.join(argv))
    if biased:
        log(f'expert_bias: {len(bias)} values uniform in +-{EXPERT_BIAS_SPAN} from the seed, the first four '
            + ', '.join(f'{x:+.4f}' for x in bias[:4]))
    make_weights = lambda host=False: seeded_weights(seed, reference, sizes, host)  # noqa: E731

    own = []
    with collect_cache_events() as events:
        kw = dict(make_weights=lambda: make_weights(host=True), seconds=seconds, warmup_steps=cell['traffic']['warmup_steps'],
                  process_start=process_start, events=events, trace_dir=trace_dir,
                  inner=counting(inner_step or CausalLMTask.train_step, own_counters, own))
        watcher = (BiasedLmStepWatcher(bias=bias, **kw) if biased else LmStepWatcher(**kw)).install()
        try:
            train.main(argv)
            raise RuntimeError(f'train.main ended after {watcher.calls} steps, before the window closed: '
                               f'the epoch is shorter than warm-up + window')
        except WindowClosed as e:
            traceback.clear_frames(e.__traceback__)
        finally:
            watcher.uninstall()
            if watcher.annotation is not None:
                watcher._span(None)

    device = jax.devices()[0]
    stats = device.memory_stats() or {}
    log(f'memory_stats: {stats}')
    if watcher.step_memory:
        log(f'step program memory_analysis, bytes: {watcher.step_memory}')
    log(f'setup: imports done at {t_imported:.1f} s, ' + ', '.join(f'{what} at {t:.1f} s' for what, t in watcher.marks)
        + f', window opened at {watcher.t_open - process_start:.1f} s')
    summed = memory_peak_bytes(stats)
    peak = memory_peak(stats, watcher.live_bytes, summed)
    log(f'memory_peak_bytes: {peak} reported (the larger of the set-up\'s live peak {int(stats.get("peak_bytes_in_use", 0))} '
        f'and the window\'s live {watcher.live_bytes} + reserved {int(stats.get("peak_bytes_reserved", 0))}); '
        f'peaks.memory_peak_bytes, the image cells\' sum of the two peaks: {summed}')
    steps = len(watcher.return_t)
    window_s = watcher.t_close - watcher.t_open
    losses = [float(x) for x in jax.device_get(watcher.losses)]
    failed = sum(not math.isfinite(x) for x in losses)
    kept = [dict(c, **t) for c, t in zip(jax.device_get(watcher.counters), jax.device_get(own[len(own) - steps:]))]
    counters = {name: [int(c[name]) for c in kept if name in c] for name in COUNTERS + own_counters}
    between = [c - r for c, r in zip(watcher.call_t[1:], watcher.return_t[:-1])]
    inside = [r - c for c, r in zip(watcher.call_t, watcher.return_t)]
    expert_layers = flops.expert_layers(sizes)
    record = {
        'runner': 'train', 'cell': cell['name'], 'attempted': steps, 'failed': failed,
        'window_s': window_s, 'steps': steps, 'batch_size': watcher.batch_size,
        'setup_s': watcher.t_open - process_start,
        'memory_peak_bytes': peak, 'memory_peak_bytes_summed': summed,
        'compiles_in_window': watcher.compiles_at_close - watcher.compiles_at_open,
        'setup_events': watcher.setup_compiles,
        'spans': {'loader_next_s': between, 'train_step_dispatch_s': inside},
        'losses_window': losses, 'reference': config['reference'], 'sizes': sizes,
        'device_kind': device.device_kind,
        'counters': {k: v for k, v in counters.items() if v},
        'lm': {'seq_len': watcher.seq_len, 'sequences': watcher.batch_size, 'expert_layers': expert_layers,
               'tokens_per_s': steps * watcher.batch_size * watcher.seq_len / window_s},
    }
    if biased:
        record['lm']['expert_bias'] = bias
    record.update(needed_work(config, record))
    # a training sample here is one sequence: `train_img_per_s` reads sequences a second
    record['end_to_end'] = {'train_img_per_s': steps * watcher.batch_size / window_s, 'setup_s': record['setup_s']}
    if trace:
        from . import trace as trace_mod
        path = trace_mod.newest_xplane(trace_dir)
        record['trace'] = trace_mod.reduce_trace(path, default_gap_label='host')
        record['trace']['work'] = watcher.trace_window[2]
        record['trace']['scopes'] = device_scopes.reduce_scopes(path, watcher.hlo_text or '', readers.declared_scopes())
        record['trace']['breakdown']['device_scopes'] = sorted(
            ([k, v] for k, v in record['trace']['scopes']['scope_s'].items()), key=lambda kv: -kv[1])
        for line in device_scopes.scope_table(record, readers.SCOPE_PARTS) + lm_readers.lines(record) + getattr(readers, 'lines', lambda r: [])(record):
            log(line)
    log(f'window: {steps} steps of {watcher.batch_size} x {watcher.seq_len} tokens in {window_s:.3f} s = '
        f'{record["lm"]["tokens_per_s"]:.0f} tokens/s; '
        f'loader_next median {statistics.median(between) * 1e3 if between else float("nan"):.3f} ms; '
        f'compilations in the window: {record["compiles_in_window"]}')
    walls = [b + d for b, d in zip(between, inside[1:])]
    if walls:
        usual = statistics.median(walls)
        slow = [(i + 1, w) for i, w in enumerate(walls) if w > 1.25 * usual]
        log(f'step walls: median {usual * 1e3:.1f} ms, longest {max(walls) * 1e3:.1f} ms; over 1.25 x the median: '
            + (', '.join(f'step {i} {w * 1e3:.0f} ms' for i, w in slow) or 'none'))
    log('counters, mean a step: ' + ', '.join(f'{k} {sum(v) / len(v):.1f}' for k, v in record['counters'].items()))

    # the program's state goes before the reference's comes
    followed, program_numbers = watcher.followed, watcher.program
    routed = expert_layers > 0
    dropped = sum(record['counters'].get('moe.dropped_slots', [0]))
    missing = [name for name in (('moe.dropped_slots',) if routed else ()) + own_counters if name not in record['counters']]
    checks = record['checks'] = {}                       # every number `correct` compares, beside its limit
    exact = {**feed_numbers(followed),
             'moe_dropped_slots': (dropped, 0, f'over the window\'s {steps} steps'),
             'step_counters_missing': (len(missing), 0, f'the step returns its counters {missing or ""}'.rstrip())}
    if biased:
        placed = watcher.biased_layers
        exact['expert_bias_unplaced'] = (expert_layers - placed, 0, f'{placed} of {expert_layers} expert layers hold the run\'s bias')
    exact = check.judge_exact(exact, out=log, into=checks)
    del watcher
    gc.collect()

    t_ref = time.perf_counter()
    ref_numbers = reference_follow(reference, followed_config, make_weights, followed, 'float32')
    numbers = check.training_numbers(program_numbers, ref_numbers)
    ok = check.judge(numbers, config['limits'][LIMITS], out=log, into=checks)
    agreement, agreed, floor = None, True, None
    if routed:
        agreement = route_agreement(program_numbers['routes'], ref_numbers['routes']) if 'routes' in program_numbers else 0.0
        floor = config['limits_lm']['route_agreement_min']
        agreed = agreement >= floor
        checks['route_agreement'] = check.compared(agreement, floor, agreed, 'at least')
        log(f'check route_agreement: {agreement:.6g} at least {floor:.6g} {"ok" if agreed else "UNDER"} '
            f'(share of the program\'s chosen (token, expert) pairs of step 1, all {expert_layers} expert layers, '
            f'the reference chose too' + (', both under the run\'s bias)' if biased else ')'))
    log(f'reference: {FOLLOWED} steps followed in {time.perf_counter() - t_ref:.1f} s ('
        + ', '.join(f'{k} {v:.1f}' for k, v in ref_numbers['seconds'].items()) + ')')
    # what an untrained model owes: ln V for uniform targets plus half the logits' variance, and a seeded head's N(0, std)
    # columns on a unit-RMS input of `hidden_size` give logits of variance hidden_size x std^2
    first = program_numbers['losses'][0]
    owed = math.log(sizes['vocab_held']) + sizes['hidden_size'] * family['first_loss_head_std'] ** 2 / 2
    sane = abs(first - owed) <= 0.5
    checks['first_loss'] = check.compared(first, [owed - 0.5, owed + 0.5], sane, 'within')
    log(f'check first_loss: {first:.4f} within ln({sizes["vocab_held"]}) + {owed - math.log(sizes["vocab_held"]):.3f} '
        f'(half the seeded logits\' variance) = {owed:.4f} +- 0.5: {"ok" if sane else "OVER"}')
    zero_compiles = record['compiles_in_window'] == 0
    checks['compiles_in_window'] = check.compared(record['compiles_in_window'], 0, zero_compiles, 'equal')
    log(f'check compiles_in_window: {record["compiles_in_window"]} limit 0 {"ok" if zero_compiles else "OVER"}')
    record['correct'] = bool(ok and exact and agreed and sane and zero_compiles and failed == 0 and steps > 0)
    record['numbers'] = {k: v[0] for k, v in numbers.items()}
    if routed:
        record['numbers']['route_agreement'] = agreement
    strip = lambda d: {k: v for k, v in d.items() if k not in ('routes', 'seconds')}  # noqa: E731
    record['followed'] = {'program': strip(program_numbers), 'reference': strip(ref_numbers)}
    if control_precision:
        lower = reference_follow(reference, followed_config, make_weights, followed, control_precision)
        against = check.training_numbers(lower, ref_numbers)
        record['control_numbers'] = {k: v[0] for k, v in against.items()}
        record['followed']['control'] = strip(lower)
        # the control through the same comparison, beside the same limits: which of them it fails, if any
        gaps_ok = check.judge(against, config['limits'][LIMITS], out=lambda line: log(f'control {control_precision} {line}'))
        if routed:
            record['control_numbers']['route_agreement'] = route_agreement(lower['routes'].transpose(1, 0, 2, 3), ref_numbers['routes'])
            gaps_ok = gaps_ok and record['control_numbers']['route_agreement'] >= floor
        record['control_correct'] = bool(gaps_ok)
    return record
