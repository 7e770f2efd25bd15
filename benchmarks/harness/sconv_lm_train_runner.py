"""The runner of the short-convolution language-model cell (cell `runner`:
`sconv_lm_train`): `lm_train_runner.py`'s run — the same window, clocks, record,
followed steps, feed checks, route agreement, memory peak and reference follow,
all imported from there as `swa_lm_train_runner.py` imports them — with what
this family needs instead of what the GLM share needs:

  * its own operation and byte table (`sconv_lm_flops.py`, which `needed_work`
    puts into the record), scopes and readings (`sconv_lm_readers.py`), the
    step's `attn.full_blocks` and `sconv.rows` beside the `moe.*` counters,
    limits under `limits['sconv_lm_train']`;
  * a router SELECTION BIAS that is not zero: a published checkpoint holds
    learned values, and a zero vector would leave the bias's path untested at
    the timed size. One vector, uniform in +-`EXPERT_BIAS_SPAN`, is drawn from
    `--seed` (`expert_bias`) and given to the program (every expert layer's
    `score_bias`, placed before the seeded weights, the routes' read and the
    first step: `BiasedLmStepWatcher`) and to the reference
    (`sizes['expert_bias']`) alike;
  * a first loss without an MTP term, held to what the seeded TIED head owes:
    ln V + half its logits' variance (hidden_size x STD^2 / 2).

A sixth runner file is the price until the fold PERF.md section 7 (j) asks a
`benchmark` PR for.
"""
from __future__ import annotations

import gc
import math
import os
import statistics
import time
import traceback

from . import lm_train_runner
from .lm_train_runner import (COUNTERS, LmStepWatcher, build_argv, feed_numbers, memory_peak, reference_follow,
                              route_agreement)
from .train_runner import FOLLOWED, WindowClosed

OWN_COUNTERS = ('attn.full_blocks', 'sconv.rows')
EXPERT_BIAS_SPAN = 0.05     # a fifth of the sigmoid scores' spread under a 0.02-std router on a unit-RMS input


def needed_work(config: dict, record: dict) -> dict:
    """`lm_train_runner.needed_work` by this family's operation table (`sconv_lm_flops.forward_macs`)."""
    from . import sconv_lm_flops
    return lm_train_runner.needed_work(config, record, sconv_lm_flops.forward_macs)


def expert_bias(seed: int, num_experts: int) -> list:
    """The run's selection bias, one vector for every expert layer: uniform in +-`EXPERT_BIAS_SPAN`, float32 values as
    Python floats, from `--seed`."""
    import numpy as np
    rng = np.random.default_rng(seed % (2 ** 31))
    return [float(x) for x in rng.uniform(-EXPERT_BIAS_SPAN, EXPERT_BIAS_SPAN, num_experts).astype(np.float32)]


def place_expert_bias(model, bias: list) -> int:
    """`bias` into the `score_bias` buffer of every layer of `model.blocks` that routes; -> how many got it."""
    import jax.numpy as jnp
    placed = 0
    for blk in model.blocks:
        buffer = getattr(blk.mlp, 'score_bias', None)
        if buffer is not None:
            buffer[...] = jnp.asarray(bias, jnp.float32)
            placed += 1
    return placed


class BiasedLmStepWatcher(LmStepWatcher):
    """`LmStepWatcher` that places the run's selection bias in the task's model before anything else reads it."""

    def __init__(self, *, bias: list, **kw):
        super().__init__(**kw)
        self.bias, self.biased_layers = bias, 0

    def step(self, task, batch, lr, step):
        if self.calls == 0:
            self.biased_layers = place_expert_bias(task.model, self.bias)
        return super().step(task, batch, lr, step)


def own_counting(inner, seen: list):
    """`inner` (a `train_step`) with the family's own counters of every step's metrics kept, device arrays, in
    `seen`: `LmStepWatcher` keeps the names of `lm_train_runner.COUNTERS` only."""
    def step(task, batch, lr, step=0):
        metrics = inner(task, batch, lr, step)
        seen.append({name: metrics[name] for name in OWN_COUNTERS if name in metrics})
        return metrics
    return step


def run(cell: dict, config: dict, *, seed: int, seconds: float, trace: bool, process_start: float,
        scratch: str, inner_step=None, control_precision=None, log=print) -> dict:
    """Run the cell once; returns the run record `run.py` reduces to the result line (the keys of
    `lm_train_runner.run`'s). `inner_step` replaces the program's `train_step` underneath the wrapper;
    `control_precision` also follows the steps with the reference in that lower precision (`tools/limits.py`,
    the tests; no benchmark run does)."""
    import jax

    import timm_tpu.models.lfm2_moe  # noqa: F401  a program without the family fails here, before any work
    import train
    from timm_tpu.task import CausalLMTask
    from timm_tpu.utils.compile_cache import collect_cache_events, configure_compile_cache

    from . import check, device_scopes, lm_readers, lm_traffic, sconv_lm_flops, sconv_lm_readers, weights
    from .manifest import reference_module
    from .peaks import memory_peak_bytes

    configure_compile_cache()
    t_imported = time.perf_counter() - process_start
    reference = reference_module(config['reference'])
    sizes = config['sizes']
    bias = expert_bias(seed, sizes['num_experts'])
    followed_config = dict(config, sizes=dict(sizes, expert_bias=bias))       # what the reference is given
    stream = cell['traffic']['token_stream']
    data_dir = lm_traffic.write_token_stream(os.path.join(scratch, 'data', stream['name']), stream, sizes['vocab_held'])
    trace_dir = os.path.join(scratch, 'trace', cell['name']) if trace else None
    argv = build_argv(config, cell, seed, os.path.join(scratch, 'train'), data_dir)
    log('train.main ' + ' '.join(argv))
    log(f'expert_bias: {sizes["num_experts"]} values uniform in +-{EXPERT_BIAS_SPAN} from the seed, the first four '
        + ', '.join(f'{x:+.4f}' for x in bias[:4]))
    make_weights = lambda: weights.make(seed, reference.init_spec(sizes))  # noqa: E731

    own = []
    with collect_cache_events() as events:
        watcher = BiasedLmStepWatcher(bias=bias, make_weights=make_weights, seconds=seconds,
                                      warmup_steps=cell['traffic']['warmup_steps'], process_start=process_start, events=events,
                                      trace_dir=trace_dir, inner=own_counting(inner_step or CausalLMTask.train_step, own)).install()
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
    counters = {name: [int(c[name]) for c in kept if name in c] for name in COUNTERS + OWN_COUNTERS}
    between = [c - r for c, r in zip(watcher.call_t[1:], watcher.return_t[:-1])]
    inside = [r - c for c, r in zip(watcher.call_t, watcher.return_t)]
    expert_layers = sconv_lm_flops.layer_kinds(sizes)[3]
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
               'expert_bias': bias, 'tokens_per_s': steps * watcher.batch_size * watcher.seq_len / window_s},
    }
    record.update(needed_work(config, record))
    # a training sample here is one sequence: `train_img_per_s` reads sequences a second
    record['end_to_end'] = {'train_img_per_s': steps * watcher.batch_size / window_s, 'setup_s': record['setup_s']}
    if trace:
        from . import trace as trace_mod
        path = trace_mod.newest_xplane(trace_dir)
        record['trace'] = trace_mod.reduce_trace(path, default_gap_label='host')
        record['trace']['work'] = watcher.trace_window[2]
        record['trace']['scopes'] = device_scopes.reduce_scopes(path, watcher.hlo_text or '', sconv_lm_readers.declared_scopes())
        record['trace']['breakdown']['device_scopes'] = sorted(
            ([k, v] for k, v in record['trace']['scopes']['scope_s'].items()), key=lambda kv: -kv[1])
        for line in device_scopes.scope_table(record, sconv_lm_readers.SCOPE_PARTS) + lm_readers.lines(record):
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
    dropped = sum(record['counters'].get('moe.dropped_slots', [0]))
    missing = [name for name in ('moe.dropped_slots',) + OWN_COUNTERS if name not in record['counters']]
    checks = record['checks'] = {}                       # every number `correct` compares, beside its limit
    exact = check.judge_exact({**feed_numbers(followed),
                               'moe_dropped_slots': (dropped, 0, f'over the window\'s {steps} steps'),
                               'step_counters_missing': (len(missing), 0, f'the step returns its counters {missing or ""}'.rstrip()),
                               'expert_bias_unplaced': (expert_layers - watcher.biased_layers, 0,
                                                        f'{watcher.biased_layers} of {expert_layers} expert layers hold the run\'s bias')},
                              out=log, into=checks)
    del watcher
    gc.collect()

    t_ref = time.perf_counter()
    ref_numbers = reference_follow(reference, followed_config, make_weights, followed, 'float32')
    numbers = check.training_numbers(program_numbers, ref_numbers)
    ok = check.judge(numbers, config['limits']['sconv_lm_train'], out=log, into=checks)
    agreement = route_agreement(program_numbers['routes'], ref_numbers['routes']) if 'routes' in program_numbers else 0.0
    floor = config['limits_lm']['route_agreement_min']
    agreed = agreement >= floor
    checks['route_agreement'] = check.compared(agreement, floor, agreed, 'at least')
    log(f'check route_agreement: {agreement:.6g} at least {floor:.6g} {"ok" if agreed else "UNDER"} '
        f'(share of the program\'s chosen (token, expert) pairs of step 1, all {expert_layers} expert layers, '
        f'the reference chose too, both under the run\'s bias)')
    log(f'reference: {FOLLOWED} steps followed in {time.perf_counter() - t_ref:.1f} s ('
        + ', '.join(f'{k} {v:.1f}' for k, v in ref_numbers['seconds'].items()) + ')')
    # what an untrained model owes: ln V for uniform targets plus half the logits' variance, and the seeded tied head
    # (the embedding's N(0, STD) rows on a unit-RMS input of `hidden_size`) gives logits of variance hidden_size x STD^2
    first = program_numbers['losses'][0]
    owed = math.log(sizes['vocab_held']) + sizes['hidden_size'] * weights.STD ** 2 / 2
    sane = abs(first - owed) <= 0.5
    checks['first_loss'] = check.compared(first, [owed - 0.5, owed + 0.5], sane, 'within')
    log(f'check first_loss: {first:.4f} within ln({sizes["vocab_held"]}) + {owed - math.log(sizes["vocab_held"]):.3f} '
        f'(half the seeded logits\' variance) = {owed:.4f} +- 0.5: {"ok" if sane else "OVER"}')
    zero_compiles = record['compiles_in_window'] == 0
    checks['compiles_in_window'] = check.compared(record['compiles_in_window'], 0, zero_compiles, 'equal')
    log(f'check compiles_in_window: {record["compiles_in_window"]} limit 0 {"ok" if zero_compiles else "OVER"}')
    record['correct'] = bool(ok and exact and agreed and sane and zero_compiles and failed == 0 and steps > 0)
    record['numbers'] = dict({k: v[0] for k, v in numbers.items()}, route_agreement=agreement)
    strip = lambda d: {k: v for k, v in d.items() if k not in ('routes', 'seconds')}  # noqa: E731
    record['followed'] = {'program': strip(program_numbers), 'reference': strip(ref_numbers)}
    if control_precision:
        lower = reference_follow(reference, followed_config, make_weights, followed, control_precision)
        against = check.training_numbers(lower, ref_numbers)
        record['control_numbers'] = dict({k: v[0] for k, v in against.items()},
                                         route_agreement=route_agreement(lower['routes'].transpose(1, 0, 2, 3),
                                                                         ref_numbers['routes']))
        record['followed']['control'] = strip(lower)
        # the control through the same comparison, beside the same limits: which of them it fails, if any
        gaps_ok = check.judge(against, config['limits']['sconv_lm_train'], out=lambda line: log(f'control {control_precision} {line}'))
        record['control_correct'] = bool(gaps_ok and record['control_numbers']['route_agreement'] >= floor)
    return record
