"""The runner of the chunk-pooled linear-attention language-model cell (cell
`runner`: `cla_lm_train`): `lm_train_runner.py`'s run — the same window, clocks,
record, followed steps, feed checks, memory peak and reference follow, all
imported from there as `swa_lm_train_runner.py` imports them — with what 8
targets a position and no router need instead of what the GLM share needs:

  * the model has no `routes` and no `moe.*` counter, and none is demanded:
    `LmStepWatcher.step` asks every model for its routes before the first step,
    so for the run's length that question is asked by attribute
    (`routes_by_attribute`); there is no `route_agreement`;
  * the wrapper under the watcher keeps, for each followed step, the 8 targets
    a position as the task builds them (`task.causal_lm.head_targets`), held to
    "head p's target is the input p + 1 positions on, nothing past the window",
    and every step's `attn.eva_blocks` / `attn.eva_pairs` / `lm.head_nll`;
  * its own operation and byte tables (`cla_lm_flops.py`, which `needed_work`
    puts into the record; nothing there reads a counter), scopes and readings
    (`cla_lm_readers.py`), limits under `limits['cla_lm_train']`;
  * the sanity band is on HEAD 0's first mean cross-entropy (the next-byte loss,
    `lm.head_nll[0]`): ln V + half the seeded logits' variance; the step's loss,
    the mean over the 8 heads, is compared with the reference's.

A fifth runner file is the price until the fold PERF.md section 7 (j) asks a
`benchmark` PR for.
"""
from __future__ import annotations

import contextlib
import gc
import math
import os
import statistics
import time
import traceback

from . import lm_train_runner
from .lm_train_runner import COUNTERS, LmStepWatcher, build_argv, feed_numbers, memory_peak, reference_follow
from .train_runner import FOLLOWED, WindowClosed

OWN_COUNTERS = ('attn.eva_blocks', 'attn.eva_pairs', 'lm.head_nll')


def needed_work(config: dict, record: dict) -> dict:
    """What a step of this run NEEDS (`lm_train_runner.needed_work`): `needed_macs`, the forward MACs of one step by
    part (`cla_lm_flops.forward_macs`: from the shapes alone, the family routes nothing), and `needed_step_flops`,
    their sum x 2 x 3. Nothing where the record does not say how long its sequences are."""
    from . import cla_lm_flops
    lm = record.get('lm')
    if not lm:
        return {}
    macs = cla_lm_flops.forward_macs(config['sizes'], lm['seq_len'], lm['sequences'])
    return {'needed_macs': macs, 'needed_step_flops': cla_lm_flops.train_flops(macs)}


@contextlib.contextmanager
def routes_by_attribute():
    """While open, `lm_train_runner.program_routes` asks a model for its routes only if it has any (None else)."""
    asked = lm_train_runner.program_routes
    lm_train_runner.program_routes = lambda model, ids, target: asked(model, ids, target) if hasattr(model, 'routes') else None
    try:
        yield
    finally:
        lm_train_runner.program_routes = asked


def heads_following(inner, seen: list, targets: list):
    """`inner` (a `train_step`) with, before each of the first `FOLLOWED` calls, the targets of the model's
    prediction heads as the task builds them from the batch kept on the host in `targets` ((B, S, P)), and after
    every call the step's own counters of this family, device arrays, in `seen`."""
    import jax

    def step(task, batch, lr, step=0):
        if len(targets) < FOLLOWED:
            from timm_tpu.task.causal_lm import head_targets
            targets.append(jax.device_get(head_targets(batch['target'], task.model.num_pred_heads)))
        metrics = inner(task, batch, lr, step)
        seen.append({name: metrics[name] for name in OWN_COUNTERS if name in metrics})
        return metrics
    return step


def head_target_numbers(followed: list, targets: list, heads: int) -> dict:
    """name -> (value, limit, note): head p's target at position i is the INPUT p + 1 positions on, and -1 at the
    last p + 1 positions of the window, for every followed step."""
    off = 0
    for step, given in zip(followed, targets):
        ids = step['input']
        S = ids.shape[1]
        off += int(given.shape != ids.shape + (heads,))
        for p in range(min(heads, given.shape[-1])):
            off += int((given[:, :S - 1 - p, p] != ids[:, 1 + p:]).sum()) + int((given[:, S - 1 - p:, p] != -1).sum())
    return {'feed_head_targets_off': (off, 0, f'{heads} targets a position: head p\'s == input[i + 1 + p], -1 past the window; '
                                              f'{len(targets)} steps')}


def run(cell: dict, config: dict, *, seed: int, seconds: float, trace: bool, process_start: float,
        scratch: str, inner_step=None, control_precision=None, log=print) -> dict:
    """Run the cell once; returns the run record `run.py` reduces to the result line (the keys of
    `lm_train_runner.run`'s). `inner_step` replaces the program's `train_step` underneath the wrapper;
    `control_precision` also follows the steps with the reference in that lower precision (`tools/limits.py`,
    the tests; no benchmark run does)."""
    import jax

    import timm_tpu.models.evabyte  # noqa: F401  a program without the family fails here, before any work
    import train
    from timm_tpu.task import CausalLMTask
    from timm_tpu.utils.compile_cache import collect_cache_events, configure_compile_cache

    from . import check, cla_lm_readers, device_scopes, lm_traffic, weights
    from .manifest import reference_module
    from .peaks import memory_peak_bytes

    configure_compile_cache()
    t_imported = time.perf_counter() - process_start
    reference = reference_module(config['reference'])
    sizes = config['sizes']
    stream = cell['traffic']['token_stream']
    data_dir = lm_traffic.write_token_stream(os.path.join(scratch, 'data', stream['name']), stream, sizes['vocab_size'])
    trace_dir = os.path.join(scratch, 'trace', cell['name']) if trace else None
    argv = build_argv(config, cell, seed, os.path.join(scratch, 'train'), data_dir)
    log('train.main ' + ' '.join(argv))
    make_weights = lambda: weights.make(seed, reference.init_spec(sizes))  # noqa: E731

    own, head_targets = [], []
    with collect_cache_events() as events, routes_by_attribute():
        watcher = LmStepWatcher(make_weights=make_weights, seconds=seconds, warmup_steps=cell['traffic']['warmup_steps'],
                                process_start=process_start, events=events, trace_dir=trace_dir,
                                inner=heads_following(inner_step or CausalLMTask.train_step, own, head_targets)).install()
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
    own = jax.device_get(own)
    kept = [dict(c, **o) for c, o in zip(jax.device_get(watcher.counters), own[len(own) - steps:])]
    counters = {name: [float(c[name]) for c in kept if name in c] for name in COUNTERS + OWN_COUNTERS[:2]}
    head_nll = [[float(x) for x in c['lm.head_nll']] for c in kept if 'lm.head_nll' in c]
    between = [c - r for c, r in zip(watcher.call_t[1:], watcher.return_t[:-1])]
    inside = [r - c for c, r in zip(watcher.call_t, watcher.return_t)]
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
        'lm': {'seq_len': watcher.seq_len, 'sequences': watcher.batch_size, 'expert_layers': 0,
               'tokens_per_s': steps * watcher.batch_size * watcher.seq_len / window_s,
               # head 0's mean nll at the run's FIRST step (warm-up steps count: the seeded weights' own), and
               # every head's at the window's last
               'head_nll_first': float(own[0]['lm.head_nll'][0]) if own and 'lm.head_nll' in own[0] else math.nan,
               'head_nll_last': head_nll[-1] if head_nll else []},
    }
    record.update(needed_work(config, record))
    # a training sample here is one sequence: `train_img_per_s` reads sequences a second
    record['end_to_end'] = {'train_img_per_s': steps * watcher.batch_size / window_s, 'setup_s': record['setup_s']}
    if trace:
        from . import trace as trace_mod
        path = trace_mod.newest_xplane(trace_dir)
        record['trace'] = trace_mod.reduce_trace(path, default_gap_label='host')
        record['trace']['work'] = watcher.trace_window[2]
        record['trace']['scopes'] = device_scopes.reduce_scopes(path, watcher.hlo_text or '', cla_lm_readers.declared_scopes())
        record['trace']['breakdown']['device_scopes'] = sorted(
            ([k, v] for k, v in record['trace']['scopes']['scope_s'].items()), key=lambda kv: -kv[1])
        for line in device_scopes.scope_table(record, cla_lm_readers.SCOPE_PARTS):
            log(line)
    log(f'window: {steps} steps of {watcher.batch_size} x {watcher.seq_len} bytes in {window_s:.3f} s = '
        f'{record["lm"]["tokens_per_s"]:.0f} bytes/s; '
        f'loader_next median {statistics.median(between) * 1e3 if between else float("nan"):.3f} ms; '
        f'compilations in the window: {record["compiles_in_window"]}')
    walls = [b + d for b, d in zip(between, inside[1:])]
    if walls:
        usual = statistics.median(walls)
        slow = [(i + 1, w) for i, w in enumerate(walls) if w > 1.25 * usual]
        log(f'step walls: median {usual * 1e3:.1f} ms, longest {max(walls) * 1e3:.1f} ms; over 1.25 x the median: '
            + (', '.join(f'step {i} {w * 1e3:.0f} ms' for i, w in slow) or 'none'))
    log('counters, mean a step: ' + ', '.join(f'{k} {sum(v) / len(v):.1f}' for k, v in record['counters'].items())
        + '; the heads\' mean nll at the window\'s last step: ' + ' '.join(f'{x:.4f}' for x in record['lm']['head_nll_last']))

    # the program's state goes before the reference's comes
    followed, program_numbers = watcher.followed, watcher.program
    heads = sizes['num_pred_heads']
    returned = set(own[-1] if own else ()) | set(record['counters'])
    missing = [name for name in OWN_COUNTERS + ('lm.tokens',) if name not in returned]
    unasked = [name for name in record['counters'] if name.startswith('moe.')]
    checks = record['checks'] = {}                       # every number `correct` compares, beside its limit
    exact = check.judge_exact({**feed_numbers(followed), **head_target_numbers(followed, head_targets, heads),
                               'step_counters_missing': (len(missing) + len(unasked), 0,
                                                         f'the step returns its counters {missing or ""}'.rstrip()
                                                         + (f' and none of a router {unasked}' if unasked else ''))},
                              out=log, into=checks)
    del watcher
    gc.collect()

    t_ref = time.perf_counter()
    ref_numbers = reference_follow(reference, config, make_weights, followed, 'float32')
    numbers = check.training_numbers(program_numbers, ref_numbers)
    ok = check.judge(numbers, config['limits']['cla_lm_train'], out=log, into=checks)
    log(f'reference: {FOLLOWED} steps followed in {time.perf_counter() - t_ref:.1f} s ('
        + ', '.join(f'{k} {v:.1f}' for k, v in ref_numbers['seconds'].items()) + ')')
    # what an untrained head 0 owes: ln V for uniform targets plus half the logits' variance, and the seeded head
    # (N(0, STD) on a unit-RMS input of `hidden_size`) gives logits of variance hidden_size x STD^2: 1.64 at 4096
    first = record['lm']['head_nll_first']
    owed = math.log(sizes['vocab_size']) + sizes['hidden_size'] * weights.STD ** 2 / 2
    sane = abs(first - owed) <= 0.5
    checks['first_loss'] = check.compared(first, [owed - 0.5, owed + 0.5], sane, 'within')
    log(f'check first_loss: head 0\'s {first:.4f} within ln({sizes["vocab_size"]}) + {owed - math.log(sizes["vocab_size"]):.3f} '
        f'(half the seeded logits\' variance) = {owed:.4f} +- 0.5: {"ok" if sane else "OVER"}')
    zero_compiles = record['compiles_in_window'] == 0
    checks['compiles_in_window'] = check.compared(record['compiles_in_window'], 0, zero_compiles, 'equal')
    log(f'check compiles_in_window: {record["compiles_in_window"]} limit 0 {"ok" if zero_compiles else "OVER"}')
    record['correct'] = bool(ok and exact and sane and zero_compiles and failed == 0 and steps > 0)
    record['numbers'] = {k: v[0] for k, v in numbers.items()}
    strip = lambda d: {k: v for k, v in d.items() if k not in ('routes', 'seconds')}  # noqa: E731
    record['followed'] = {'program': strip(program_numbers), 'reference': strip(ref_numbers)}
    if control_precision:
        lower = reference_follow(reference, config, make_weights, followed, control_precision)
        against = check.training_numbers(lower, ref_numbers)
        record['control_numbers'] = {k: v[0] for k, v in against.items()}
        record['followed']['control'] = strip(lower)
        # the control through the same comparison, beside the same limits: which of them it fails, if any
        record['control_correct'] = bool(check.judge(against, config['limits']['cla_lm_train'],
                                                     out=lambda line: log(f'control {control_precision} {line}')))
    return record
