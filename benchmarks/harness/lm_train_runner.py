"""The language-model training runner (cell `runner`: `lm_train`): one cell,
once, through `train.main(argv)` in-process, as `train_runner.py` runs the
image cells — the same window, clocks and record, so the `*.train` readers
read it (`'runner': 'train'`) — with what a causal-LM step needs instead of
what an image step needs:

  * the wrapper sits on `CausalLMTask.train_step`; the feed is the program's
    token loader over a seeded stream (`lm_traffic.py`);
  * the seeded weights go to the host once, so that the change of every leaf
    after the followed steps is read leaf by leaf: beside the loaded step
    program no second copy of the weights fits on the chip;
  * before the first step the program's own routing choices are read
    (`model.routes`), for `route_agreement` with the reference's;
  * the step's counters (`moe.*`, `lm.tokens`) are kept as device arrays and
    read after the window; a traced run also reads the step program's compiled
    text and reduces device time by scope (`device_scopes.py`) for the cell's
    own per-layer metrics (`lm_readers.py`), and prints the scope table and the
    two readings that are no metric (`lm_readers.PRINTED`).

The reference (`reference/lm_train_step.py`) runs after the window, once the
program's state is freed.
"""
from __future__ import annotations

import gc
import math
import os
import statistics
import time
import traceback

from .train_runner import FOLLOWED, TRACE_AFTER, TRACE_STEPS, StepWatcher, WindowClosed

COUNTERS = ('moe.local_slots', 'moe.load_max', 'moe.dropped_slots', 'lm.tokens')
LOADER_THREADS = 2     # a window of ids is a memory copy; more threads only contend for the interpreter


def build_argv(config: dict, cell: dict, seed: int, out_dir: str, data_dir: str) -> list:
    return ['--model', config['model'], *config['train_args'], *cell.get('train_args', []),
            '--seed', str(seed % (2 ** 31)), '--epochs', '1', '--log-interval', '1000000',
            '--output', out_dir, '--experiment', cell['name'], '--data-dir', data_dir, '-j', str(LOADER_THREADS)]


def program_routes(model, ids, target):
    """The program's chosen experts (layers, B, S, k) for a batch, by its own forward pass."""
    import jax
    import jax.numpy as jnp
    from flax import nnx
    graphdef, state = nnx.split(model)
    chosen = jax.jit(lambda st, i, n: nnx.merge(graphdef, st).routes(i, n))(state, ids, jnp.where(target < 0, 0, target))
    return jax.device_get(chosen)


def change_norms(task, start_host: dict) -> dict:
    """Norm of every parameter leaf's change from the host copy of its start, one leaf on the device at a time."""
    import jax
    import jax.numpy as jnp
    from flax import nnx
    from . import program
    norm = jax.jit(lambda now, start: jnp.sqrt(jnp.sum(jnp.square(now.astype(jnp.float32) - start))))
    now = program.named_leaves(nnx.state(task.model, nnx.Param))
    return {k: float(norm(now[k], start_host[k])) for k in start_host}


class LmStepWatcher(StepWatcher):
    """`StepWatcher` on `CausalLMTask.train_step`."""

    def __init__(self, **kw):
        inner = kw.pop('inner', None)
        super().__init__(follow_ema=False, inner=inner, **kw)
        from timm_tpu.task import CausalLMTask
        self.cls, self.saved = CausalLMTask, CausalLMTask.train_step
        self.inner = inner or CausalLMTask.train_step
        self.counters = []          # per window step: the step's counters, device arrays
        self.start_host = None
        self.hlo_text = None
        self.live_bytes = 0         # live device bytes at the window's ends (the step's state and its batches)

    @staticmethod
    def _live_bytes() -> int:
        import jax
        return int((jax.devices()[0].memory_stats() or {}).get('bytes_in_use', 0))

    def step(self, task, batch, lr, step):
        import jax
        from . import program
        from . import trace as trace_mod
        k = self.calls
        self.calls += 1
        if k == 0:
            self._mark('first step called')
            weights = self.make_weights()
            program.load_task_weights(task, weights)
            self.start_host = jax.device_get(weights)
            del weights
            self._mark('seeded weights loaded')
            self.batch_size, self.seq_len = (int(n) for n in batch['input'].shape)
            self.program['routes'] = program_routes(task.model, batch['input'], batch['target'])
            self._mark('routes read')
        if k < FOLLOWED:
            self.followed.append({'input': jax.device_get(batch['input']), 'target': jax.device_get(batch['target']),
                                  'lr': float(lr)})
        in_window = k >= self.warmup_steps
        if k == self.warmup_steps:
            self.compiles_at_open = self._compiles()
            self.setup_compiles = dict(self.events)
            self.live_bytes = self._live_bytes()
            self.t_open = time.perf_counter()
        window_step = k - self.warmup_steps
        tracing = self.trace_dir is not None and in_window
        if tracing and window_step == TRACE_AFTER:
            jax.block_until_ready(self.last_metrics)
            trace_mod.start(self.trace_dir)
            self._window_span = jax.profiler.TraceAnnotation('bench.window')
            self._window_span.__enter__()
            self.trace_window = [time.perf_counter(), None, 0]
        traced_now = tracing and self.trace_window is not None and self.trace_window[1] is None
        if in_window:
            self.call_t.append(time.perf_counter())
        if traced_now:
            self._span('train_step_dispatch')

        metrics = self.inner(task, batch, lr, step)

        if in_window:
            self.return_t.append(time.perf_counter())
            self.losses.append(metrics['loss'])
            self.counters.append({name: metrics[name] for name in COUNTERS if name in metrics})
        self.last_metrics = metrics
        if traced_now:
            self.trace_window[2] += 1
            if self.trace_window[2] == TRACE_STEPS:
                jax.block_until_ready(metrics)
                self._span(None)
                self._window_span.__exit__(None, None, None)
                self.trace_window[1] = time.perf_counter()
                trace_mod.stop()
            else:
                self._span('loader_next')
        if k < FOLLOWED:
            self.program['losses'].append(float(metrics['loss']))
            if k == 0:
                self._mark('first step done')
                self.program['first_grad_norms'] = program.first_grad_norms(task)
            if k == FOLLOWED - 1:
                self.program['param_change_norms'] = change_norms(task, self.start_host)
                self.start_host = None
                self._mark('followed steps read')
        elif not in_window:
            jax.block_until_ready(metrics)
        if in_window and time.perf_counter() - self.t_open >= self.seconds \
                and (not tracing or (self.trace_window and self.trace_window[1])):
            jax.block_until_ready(metrics)
            self.t_close = time.perf_counter()
            self.compiles_at_close = self._compiles()
            self.live_bytes = max(self.live_bytes, self._live_bytes())
            if tracing:
                compiled = task.lower_train_step(batch, lr, step)
                analysis = compiled.memory_analysis()
                self.step_memory = {key: int(getattr(analysis, key + '_size_in_bytes'))
                                    for key in ('temp', 'argument', 'output', 'alias')}
                self.hlo_text = compiled.as_text()
            raise WindowClosed()
        return metrics


def expert_layers(sizes: dict) -> int:
    """Layers of the held model that route: all but the first dense ones, and the MTP module's."""
    return sizes['num_hidden_layers'] - sizes['first_k_dense_replace'] + sizes['num_nextn_predict_layers']


def needed_work(config: dict, record: dict, forward_macs=None) -> dict:
    """What a step of this run NEEDS, for the record (`train_runner.needed_work`): `needed_macs`, the forward
    MACs of one step by part (`lm_flops.forward_macs`, or the family's own table handed in; the routed experts by
    the window's mean of `moe.local_slots`, the step's own count, so the work is the configuration's whatever
    implements it), which the `*_mfu.train` readers take their part from, and `needed_step_flops`, their sum
    x 2 x 3. Nothing where the step returned no counters."""
    from . import device_scopes, lm_flops
    slots, lm = device_scopes.counter_mean(record, 'moe.local_slots'), record['lm']
    if slots is None:
        return {}
    macs = (forward_macs or lm_flops.forward_macs)(config['sizes'], lm['seq_len'], lm['sequences'], slots)
    return {'needed_macs': macs, 'needed_step_flops': lm_flops.train_flops(macs)}


def memory_peak(stats: dict, live_in_window: int, summed: int) -> int:
    """Peak device memory of the run. `peaks.memory_peak_bytes` adds the peak of live buffers to the peak the
    runtime reserved for loaded programs' temporaries; here the two peaks fall at different times (seeding
    706.5M parameters holds two extra copies during set-up, before the step program is loaded: 14.1 + 6.8 GB
    would be more than the chip has). So: the larger of the set-up's live peak and the window's live bytes
    plus the reservation, never more than the sum."""
    reserved = int(stats.get('peak_bytes_reserved', 0))
    return min(summed, max(int(stats.get('peak_bytes_in_use', 0)), live_in_window + reserved))


def feed_numbers(followed: list) -> dict:
    """name -> (value, limit, note): no sequence given twice in the followed steps, and every target the
    input one token on (the last position of a window has none)."""
    import numpy as np
    rows = [row.tobytes() for step in followed for row in step['input']]
    off = sum(int((step['target'][:, :-1] != step['input'][:, 1:]).sum() + (step['target'][:, -1] != -1).sum())
              for step in followed)
    lo = min(int(np.min(step['input'])) for step in followed)
    return {'feed_repeated_rows': (len(rows) - len(set(rows)), 0, f'{len(rows)} sequences in {len(followed)} batches'),
            'feed_targets_off': (off, 0, 'target[i] == input[i + 1], -1 at the end'),
            'feed_negative_ids': (int(lo < 0), 0, f'smallest id {lo}')}


def route_agreement(program_routes, reference_routes) -> float:
    """Share of the program's chosen (token, expert) pairs that the reference chose too. `program_routes` is
    (layers, B, S, k), `reference_routes` (B, layers, S, k)."""
    import numpy as np
    a = np.asarray(program_routes).transpose(1, 0, 2, 3)
    b = np.asarray(reference_routes)
    return float((a[..., :, None] == b[..., None, :]).any(-1).mean())


def reference_follow(reference, config, make_weights, followed, precision: str) -> dict:
    from ..reference import lm_train_step
    recipe = config['recipe']
    return lm_train_step.follow(reference, config['sizes'], make_weights, followed, clip=recipe['clip_grad'],
                                weight_decay=recipe['weight_decay'], betas=recipe['betas'], precision=precision,
                                block_q=config['reference_block_q'])


def run(cell: dict, config: dict, *, seed: int, seconds: float, trace: bool, process_start: float,
        scratch: str, inner_step=None, control_precision=None, log=print) -> dict:
    """Run the cell once; returns the run record `run.py` reduces to the result line (the keys of
    `train_runner.run`'s, plus `counters`, `lm` and, traced, `trace['scopes']`). `inner_step` replaces the
    program's `train_step` underneath the wrapper; `control_precision` also follows the steps with the
    reference in that lower precision and records its numbers against the float32 reference
    (`tools/limits.py`, the tests; no benchmark run does)."""
    import jax

    import train
    from timm_tpu.task import CausalLMTask  # noqa: F401  a program without the task fails here, before any work
    from timm_tpu.utils.compile_cache import collect_cache_events, configure_compile_cache

    from . import check, device_scopes, lm_readers, lm_traffic, weights
    from .manifest import reference_module
    from .peaks import memory_peak_bytes

    configure_compile_cache()
    t_imported = time.perf_counter() - process_start
    reference = reference_module(config['reference'])
    sizes = config['sizes']
    stream = cell['traffic']['token_stream']
    data_dir = lm_traffic.write_token_stream(os.path.join(scratch, 'data', stream['name']), stream, sizes['vocab_held'])
    out_dir = os.path.join(scratch, 'train')
    trace_dir = os.path.join(scratch, 'trace', cell['name']) if trace else None
    argv = build_argv(config, cell, seed, out_dir, data_dir)
    log('train.main ' + ' '.join(argv))
    make_weights = lambda: weights.make(seed, reference.init_spec(sizes))  # noqa: E731

    with collect_cache_events() as events:
        watcher = LmStepWatcher(make_weights=make_weights, seconds=seconds, warmup_steps=cell['traffic']['warmup_steps'],
                                process_start=process_start, events=events, trace_dir=trace_dir,
                                inner=inner_step).install()
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
    # two definitions under one name until a `benchmark` PR picks one (PERF.md section 7): both are printed
    log(f'memory_peak_bytes: {peak} reported (the larger of the set-up\'s live peak {int(stats.get("peak_bytes_in_use", 0))} '
        f'and the window\'s live {watcher.live_bytes} + reserved {int(stats.get("peak_bytes_reserved", 0))}); '
        f'peaks.memory_peak_bytes, the image cells\' sum of the two peaks: {summed}')
    steps = len(watcher.return_t)
    window_s = watcher.t_close - watcher.t_open
    losses = [float(x) for x in jax.device_get(watcher.losses)]
    failed = sum(not math.isfinite(x) for x in losses)
    counters = {name: [int(c[name]) for c in jax.device_get(watcher.counters) if name in c] for name in COUNTERS}
    between = [c - r for c, r in zip(watcher.call_t[1:], watcher.return_t[:-1])]
    record = {
        'runner': 'train', 'cell': cell['name'], 'attempted': steps, 'failed': failed,
        'window_s': window_s, 'steps': steps, 'batch_size': watcher.batch_size,
        'setup_s': watcher.t_open - process_start,
        'memory_peak_bytes': peak, 'memory_peak_bytes_summed': summed,
        'compiles_in_window': watcher.compiles_at_close - watcher.compiles_at_open,
        'setup_events': watcher.setup_compiles,
        'spans': {'loader_next_s': between,
                  'train_step_dispatch_s': [r - c for c, r in zip(watcher.call_t, watcher.return_t)]},
        'losses_window': losses, 'reference': config['reference'], 'sizes': sizes,
        'device_kind': device.device_kind,
        'counters': {k: v for k, v in counters.items() if v},
        'lm': {'seq_len': watcher.seq_len, 'sequences': watcher.batch_size, 'expert_layers': expert_layers(sizes),
               'tokens_per_s': steps * watcher.batch_size * watcher.seq_len / window_s},
    }
    record.update(needed_work(config, record))
    # a training sample here is one sequence: `train_img_per_s` reads sequences a second
    record['end_to_end'] = {'train_img_per_s': steps * watcher.batch_size / window_s, 'setup_s': record['setup_s']}
    if trace:
        from . import trace as trace_mod
        path = trace_mod.newest_xplane(trace_dir)
        record['trace'] = trace_mod.reduce_trace(path, default_gap_label='host')
        record['trace']['work'] = watcher.trace_window[2]
        record['trace']['scopes'] = device_scopes.reduce_scopes(path, watcher.hlo_text or '')
        record['trace']['breakdown']['device_scopes'] = sorted(
            ([k, v] for k, v in record['trace']['scopes']['scope_s'].items()), key=lambda kv: -kv[1])
        for line in device_scopes.scope_table(record) + lm_readers.lines(record):
            log(line)
    log(f'window: {steps} steps of {watcher.batch_size} x {watcher.seq_len} tokens in {window_s:.3f} s = '
        f'{record["lm"]["tokens_per_s"]:.0f} tokens/s; '
        f'loader_next median {statistics.median(between) * 1e3 if between else float("nan"):.3f} ms; '
        f'compilations in the window: {record["compiles_in_window"]}')
    inside = record['spans']['train_step_dispatch_s']
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
    dropped = sum(record['counters'].get('moe.dropped_slots', [0]))
    checks = record['checks'] = {}                       # every number `correct` compares, beside its limit
    own = check.judge_exact({**feed_numbers(followed),
                             'moe_dropped_slots': (dropped, 0, f'over the window\'s {steps} steps'),
                             'moe_counters_missing': (int('moe.dropped_slots' not in record['counters']), 0,
                                                      'the step returns its counters')}, out=log, into=checks)
    del watcher
    gc.collect()

    t_ref = time.perf_counter()
    ref_numbers = reference_follow(reference, config, make_weights, followed, 'float32')
    numbers = check.training_numbers(program_numbers, ref_numbers)
    ok = check.judge(numbers, config['limits']['lm_train'], out=log, into=checks)
    agreement = route_agreement(program_numbers['routes'], ref_numbers['routes']) if 'routes' in program_numbers else 0.0
    floor = config['limits_lm']['route_agreement_min']
    agreed = agreement >= floor
    checks['route_agreement'] = check.compared(agreement, floor, agreed, 'at least')
    log(f'check route_agreement: {agreement:.6g} at least {floor:.6g} {"ok" if agreed else "UNDER"} '
        f'(share of the program\'s chosen (token, expert) pairs of step 1 the reference chose too)')
    log(f'reference: {FOLLOWED} steps followed in {time.perf_counter() - t_ref:.1f} s ('
        + ', '.join(f'{k} {v:.1f}' for k, v in ref_numbers['seconds'].items()) + ')')
    weight = sizes['mtp_loss_weight'] if sizes['num_nextn_predict_layers'] else 0.0
    first, owed = program_numbers['losses'][0] / (1.0 + weight), math.log(sizes['vocab_held'])
    sane = abs(first - owed) <= 0.5
    checks['first_loss'] = check.compared(first, [owed - 0.5, owed + 0.5], sane, 'within')
    log(f'check first_loss: {first:.4f} (the loss over 1 + {weight:g}, the MTP term\'s weight) within '
        f'ln({sizes["vocab_held"]}) +- 0.5: {"ok" if sane else "OVER"}')
    zero_compiles = record['compiles_in_window'] == 0
    checks['compiles_in_window'] = check.compared(record['compiles_in_window'], 0, zero_compiles, 'equal')
    log(f'check compiles_in_window: {record["compiles_in_window"]} limit 0 {"ok" if zero_compiles else "OVER"}')
    record['correct'] = bool(ok and own and agreed and sane and zero_compiles and failed == 0 and steps > 0)
    record['numbers'] = dict({k: v[0] for k, v in numbers.items()}, route_agreement=agreement)
    strip = lambda d: {k: v for k, v in d.items() if k not in ('routes', 'seconds')}  # noqa: E731
    record['followed'] = {'program': strip(program_numbers), 'reference': strip(ref_numbers)}
    if control_precision:
        lower = reference_follow(reference, config, make_weights, followed, control_precision)
        record['control_numbers'] = dict({k: v[0] for k, v in check.training_numbers(lower, ref_numbers).items()},
                                         route_agreement=route_agreement(lower['routes'].transpose(1, 0, 2, 3),
                                                                         ref_numbers['routes']))
        record['followed']['control'] = strip(lower)
        # the control through the same comparison, beside the same limits: which of them it fails, if any
        record['control_correct'] = check.judge(check.training_numbers(lower, ref_numbers), config['limits']['lm_train'],
                                                out=lambda line: log(f'control {control_precision} {line}'))
    return record
