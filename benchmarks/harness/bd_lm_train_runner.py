"""The runner of the block-diffusion language-model cell (cell `runner`:
`bd_lm_train`): `lm_train_runner.py`'s run — the same window, clocks, record,
followed steps, feed checks, route agreement and memory peak, all imported from
there — with what this family needs instead of what a next-token family needs:

  * the step draws its own noise (`BlockDiffusionLMTask`), so the wrapper under
    the watcher reads, before each followed step, the noise that step is about
    to draw (`task.next_noise`: the task's pure noise function at the stream's
    next key) and, before the first, the program's routes over BOTH halves;
    each step's own count of masked positions is held against the noise read;
  * the reference (`reference/bd_lm_train_step.py`) is GIVEN the noised ids and
    the masking probabilities of the followed steps and draws nothing;
  * its own operation count (`bd_lm_flops.py`, which `needed_work` puts into the
    record), scopes and readings (`bd_lm_readers.py`), the step's
    `attn.bd_blocks` / `lm.noised_masked` / `lm.masked_nll` counters beside the
    `moe.*` ones, limits under `limits['bd_lm_train']`;
  * a sanity band on the PLAIN mean cross-entropy of the first step's masked
    positions (ln V + half the seeded logits' variance): the weighted loss
    swings with the p drawn, and is compared with the reference's at the same p.

`LmStepWatcher.step` also reads `model.routes(input, target)` before the first
step: for this family a forward pass over ids that mean nothing, thrown away
here (set-up seconds; PERF.md section 7 asks for the fold of the three runners).
"""
from __future__ import annotations

import gc
import math
import os
import statistics
import time
import traceback

from . import lm_train_runner
from .lm_train_runner import COUNTERS, LmStepWatcher, build_argv, feed_numbers, memory_peak, program_routes, route_agreement
from .train_runner import FOLLOWED, WindowClosed

OWN_COUNTERS = ('attn.bd_blocks', 'lm.noised_masked', 'lm.masked_nll')
SPREAD = 5.0        # binomial standard deviations a sequence's masked share may lie from its p


def needed_work(config: dict, record: dict) -> dict:
    """`lm_train_runner.needed_work` by this family's operation table (`bd_lm_flops.forward_macs`)."""
    from . import bd_lm_flops
    return lm_train_runner.needed_work(config, record, bd_lm_flops.forward_macs)


def noise_following(inner, seen: list, noise: list, routes: list):
    """`inner` (a `train_step`) with, before each of the first `FOLLOWED` calls, the noise the step is about to
    draw kept on the host in `noise` (noised ids, masked, p), before the first the program's routes over both
    halves in `routes`, and after every call the step's own counters of this family, device arrays, in `seen`."""
    import jax

    def step(task, batch, lr, step=0):
        if len(noise) < FOLLOWED:
            noised, masked, p = task.next_noise(batch['input'])
            if not noise:
                routes.append(program_routes(task.model, noised, batch['input']))
            noise.append(dict(zip(('noised', 'masked', 'p'), jax.device_get((noised, masked, p)))))
        metrics = inner(task, batch, lr, step)
        seen.append({name: metrics[name] for name in OWN_COUNTERS if name in metrics})
        return metrics
    return step


def noise_numbers(followed: list, noise: list, counted: list, mask_token_id: int) -> dict:
    """name -> (value, limit, note): the noise of the followed steps is what the objective says it is. The noised
    ids are the clean ones but for the mask token at the masked positions; no clean id is the mask token; each
    sequence's masked share lies within `SPREAD` binomial standard deviations of its p; no two sequences of the
    followed steps were noised alike; and each step counted as many masked positions as the noise read has."""
    import numpy as np
    off = clean_masks = wide = miscounted = 0
    for step, drawn, count in zip(followed, noise, counted):
        clean, noised, masked, p = step['input'], drawn['noised'], drawn['masked'], drawn['p']
        off += int((noised != np.where(masked, mask_token_id, clean)).sum())
        clean_masks += int((clean == mask_token_id).sum())
        share, sd = masked.mean(axis=1), np.sqrt(p * (1 - p) / masked.shape[1])
        wide += int((np.abs(share - p) > SPREAD * sd).sum())
        miscounted += int(count != masked.sum())
    ps = np.concatenate([drawn['p'] for drawn in noise])
    masks = [row.tobytes() for drawn in noise for row in drawn['masked']]
    return {'noise_ids_off': (off, 0, 'noised = clean but for the mask token where masked'),
            'noise_clean_is_mask': (clean_masks, 0, f'no clean id is the mask token {mask_token_id}'),
            'noise_share_off': (wide, 0, f'masked share within {SPREAD:g} binomial sd of p; p ' + ', '.join(f'{x:.4f}' for x in ps)),
            'noise_repeated': (len(ps) - len(set(ps.tolist())) + len(masks) - len(set(masks)), 0,
                               f'{len(ps)} sequences in {len(noise)} steps, each its own p and mask'),
            'noise_count_off': (miscounted, 0, 'the step\'s lm.noised_masked is the noise read before it')}


def reference_follow(reference, config, make_weights, steps, precision: str) -> dict:
    from ..reference import bd_lm_train_step
    recipe = config['recipe']
    return bd_lm_train_step.follow(reference, config['sizes'], make_weights, steps, clip=recipe['clip_grad'],
                                   weight_decay=recipe['weight_decay'], betas=recipe['betas'], precision=precision,
                                   block_q=config['reference_block_q'])


def run(cell: dict, config: dict, *, seed: int, seconds: float, trace: bool, process_start: float,
        scratch: str, inner_step=None, control_precision=None, log=print) -> dict:
    """Run the cell once; returns the run record `run.py` reduces to the result line (the keys of
    `lm_train_runner.run`'s). `inner_step` replaces the program's `train_step` underneath the wrapper;
    `control_precision` also follows the steps with the reference in that lower precision (`tools/limits.py`,
    the tests; no benchmark run does)."""
    import jax

    import train
    from timm_tpu.task import BlockDiffusionLMTask     # a program without the task fails here, before any work
    from timm_tpu.utils.compile_cache import collect_cache_events, configure_compile_cache

    from . import bd_lm_readers, check, device_scopes, lm_readers, lm_traffic, weights
    from .manifest import reference_module
    from .peaks import memory_peak_bytes

    configure_compile_cache()
    t_imported = time.perf_counter() - process_start
    reference = reference_module(config['reference'])
    sizes = config['sizes']
    stream = cell['traffic']['token_stream']
    # ids uniform over the rows below the mask token: no clean id is the mask
    data_dir = lm_traffic.write_token_stream(os.path.join(scratch, 'data', stream['name']), stream, sizes['mask_token_id'])
    trace_dir = os.path.join(scratch, 'trace', cell['name']) if trace else None
    argv = build_argv(config, cell, seed, os.path.join(scratch, 'train'), data_dir)
    log('train.main ' + ' '.join(argv))
    make_weights = lambda: weights.make(seed, reference.init_spec(sizes))  # noqa: E731

    own, noise, routes = [], [], []
    with collect_cache_events() as events:
        watcher = LmStepWatcher(make_weights=make_weights, seconds=seconds, warmup_steps=cell['traffic']['warmup_steps'],
                                process_start=process_start, events=events, trace_dir=trace_dir,
                                inner=noise_following(inner_step or BlockDiffusionLMTask.train_step, own, noise, routes)).install()
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
    kept = [dict(c, **t) for c, t in zip(jax.device_get(watcher.counters), own[len(own) - steps:])]
    counters = {name: [float(c[name]) if name == 'lm.masked_nll' else int(c[name]) for c in kept if name in c]
                for name in COUNTERS + OWN_COUNTERS}
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
        # a sequence is `seq_len` CLEAN tokens; the step runs twice as many rows
        'lm': {'seq_len': watcher.seq_len, 'sequences': watcher.batch_size, 'expert_layers': sizes['num_hidden_layers'],
               'tokens_per_s': steps * watcher.batch_size * watcher.seq_len / window_s},
    }
    record.update(needed_work(config, record))
    # a training sample here is one sequence: `train_img_per_s` reads clean sequences a second
    record['end_to_end'] = {'train_img_per_s': steps * watcher.batch_size / window_s, 'setup_s': record['setup_s']}
    if trace:
        from . import trace as trace_mod
        path = trace_mod.newest_xplane(trace_dir)
        record['trace'] = trace_mod.reduce_trace(path, default_gap_label='host')
        record['trace']['work'] = watcher.trace_window[2]
        record['trace']['scopes'] = device_scopes.reduce_scopes(path, watcher.hlo_text or '', bd_lm_readers.declared_scopes())
        record['trace']['breakdown']['device_scopes'] = sorted(
            ([k, v] for k, v in record['trace']['scopes']['scope_s'].items()), key=lambda kv: -kv[1])
        for line in device_scopes.scope_table(record, bd_lm_readers.SCOPE_PARTS) + lm_readers.lines(record):
            log(line)
    log(f'window: {steps} steps of {watcher.batch_size} x {watcher.seq_len} clean tokens (twice the rows) in {window_s:.3f} s = '
        f'{record["lm"]["tokens_per_s"]:.0f} clean tokens/s; '
        f'loader_next median {statistics.median(between) * 1e3 if between else float("nan"):.3f} ms; '
        f'compilations in the window: {record["compiles_in_window"]}')
    walls = [b + d for b, d in zip(between, inside[1:])]
    if walls:
        usual = statistics.median(walls)
        slow = [(i + 1, w, between[i], inside[i + 1]) for i, w in enumerate(walls) if w > 1.25 * usual]
        log(f'step walls: median {usual * 1e3:.1f} ms, longest {max(walls) * 1e3:.1f} ms; over 1.25 x the median: '
            + (', '.join(f'step {i} {w * 1e3:.0f} ms (between calls {b * 1e3:.0f}, in the call {d * 1e3:.0f})'
                         for i, w, b, d in slow) or 'none'))
    log('counters, mean a step: ' + ', '.join(f'{k} {sum(v) / len(v):.1f}' for k, v in record['counters'].items()))

    # the program's state goes before the reference's comes
    followed, program_numbers = watcher.followed, watcher.program
    program_numbers['routes'] = routes[0] if routes else None
    counted = [int(c.get('lm.noised_masked', -1)) for c in own[:FOLLOWED]]
    dropped = sum(record['counters'].get('moe.dropped_slots', [0]))
    missing = [name for name in ('moe.dropped_slots',) + OWN_COUNTERS if name not in record['counters']]
    checks = record['checks'] = {}                       # every number `correct` compares, beside its limit
    exact = check.judge_exact({**feed_numbers(followed),
                               **noise_numbers(followed, noise, counted, sizes['mask_token_id']),
                               'moe_dropped_slots': (dropped, 0, f'over the window\'s {steps} steps'),
                               'step_counters_missing': (len(missing), 0, f'the step returns its counters {missing or ""}'.rstrip())},
                              out=log, into=checks)
    first_nll = own[0]['lm.masked_nll'] / max(own[0]['lm.noised_masked'], 1) if own and 'lm.masked_nll' in own[0] else math.nan
    del watcher
    gc.collect()

    t_ref = time.perf_counter()
    given = [{'noised': n['noised'], 'clean': f['input'], 'p': n['p'], 'lr': f['lr']} for f, n in zip(followed, noise)]
    ref_numbers = reference_follow(reference, config, make_weights, given, 'float32')
    numbers = check.training_numbers(program_numbers, ref_numbers)
    ok = check.judge(numbers, config['limits']['bd_lm_train'], out=log, into=checks)
    agreement = route_agreement(program_numbers['routes'], ref_numbers['routes']) if program_numbers['routes'] is not None else 0.0
    floor = config['limits_lm']['route_agreement_min']
    agreed = agreement >= floor
    checks['route_agreement'] = check.compared(agreement, floor, agreed, 'at least')
    log(f'check route_agreement: {agreement:.6g} at least {floor:.6g} {"ok" if agreed else "UNDER"} '
        f'(share of the program\'s chosen (row, expert) pairs of step 1, both halves, all {sizes["num_hidden_layers"]} '
        f'layers, the reference chose too)')
    log(f'reference: {FOLLOWED} steps followed in {time.perf_counter() - t_ref:.1f} s ('
        + ', '.join(f'{k} {v:.1f}' for k, v in ref_numbers['seconds'].items()) + '); masked cross-entropy a step: program '
        + ', '.join(f'{c["lm.masked_nll"] / max(c["lm.noised_masked"], 1):.4f}' for c in own[:FOLLOWED] if 'lm.masked_nll' in c)
        + ' reference ' + ', '.join(f'{x:.4f}' for x in ref_numbers['masked_nll']))
    # what an untrained model owes a masked position: ln V for uniform targets plus half the logits' variance, and the
    # seeded head (N(0, STD) on a unit-RMS input of `hidden_size`) gives logits of variance hidden_size x STD^2
    owed = math.log(sizes['vocab_held']) + sizes['hidden_size'] * weights.STD ** 2 / 2
    sane = abs(first_nll - owed) <= 0.5
    checks['first_masked_nll'] = check.compared(first_nll, [owed - 0.5, owed + 0.5], sane, 'within')
    log(f'check first_masked_nll: {first_nll:.4f} (the first step\'s plain mean over its masked positions) within '
        f'ln({sizes["vocab_held"]}) + {owed - math.log(sizes["vocab_held"]):.3f} = {owed:.4f} +- 0.5: {"ok" if sane else "OVER"}')
    zero_compiles = record['compiles_in_window'] == 0
    checks['compiles_in_window'] = check.compared(record['compiles_in_window'], 0, zero_compiles, 'equal')
    log(f'check compiles_in_window: {record["compiles_in_window"]} limit 0 {"ok" if zero_compiles else "OVER"}')
    record['correct'] = bool(ok and exact and agreed and sane and zero_compiles and failed == 0 and steps > 0)
    record['numbers'] = dict({k: v[0] for k, v in numbers.items()}, route_agreement=agreement)
    strip = lambda d: {k: v for k, v in d.items() if k not in ('routes', 'seconds')}  # noqa: E731
    record['followed'] = {'program': strip(program_numbers), 'reference': strip(ref_numbers),
                          'p': [n['p'].tolist() for n in noise]}
    if control_precision:
        lower = reference_follow(reference, config, make_weights, given, control_precision)
        against = check.training_numbers(lower, ref_numbers)
        record['control_numbers'] = dict({k: v[0] for k, v in against.items()},
                                         route_agreement=route_agreement(lower['routes'].transpose(1, 0, 2, 3),
                                                                         ref_numbers['routes']))
        record['followed']['control'] = strip(lower)
        # the control through the same comparison, beside the same limits: which of them it fails, if any
        gaps_ok = check.judge(against, config['limits']['bd_lm_train'], out=lambda line: log(f'control {control_precision} {line}'))
        record['control_correct'] = bool(gaps_ok and record['control_numbers']['route_agreement'] >= floor)
    return record
