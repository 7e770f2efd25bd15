"""The training runner: one cell, once, through `train.main(argv)` in-process.

`train.main` builds the task, the loader and the compiled step and drives
them; the runner watches through its own wrapper around
`ClassificationTask.train_step` (the pattern of `chip_smoke.py`
`observe_train_steps`, copied, not imported). The wrapper

  * before the first step puts the benchmark's seeded weights into the task;
  * records what the first `FOLLOWED` steps were given (batch, learning rate,
    stochastic-depth keys) and what they did (loss, first gradient, change of
    the parameters and of their EMA) — the steps the plain reference follows
    afterwards;
  * after `warmup_steps` steps opens the window on the SAME task and feed,
    takes two host clocks a step, and when `--seconds` have passed blocks on
    the last step's metrics and raises `WindowClosed`, which `train.main`
    lets through untouched: no validation pass, no checkpoint. Closing, it
    reads how far each stochastic-depth stream has counted.

The reference runs after the window, once the program's state is freed, so
`memory_peak_bytes` stays the program's and `setup_s` does not pay for it.
"""
from __future__ import annotations

import gc
import math
import os
import statistics
import sys
import time
import traceback

FOLLOWED = 3          # steps the reference follows
TRACE_AFTER = 8       # window steps before the traced part, in a --trace 1 run
TRACE_STEPS = 10      # steps traced


class WindowClosed(Exception):
    """Raised by the wrapper when the window has closed; carries nothing."""


def needed_work(config: dict, record: dict) -> dict:
    """What a step of this run NEEDS, for the record: `needed_step_flops`, which `step_mfu.train` reads in every
    training cell. Every runner has this function and knows its family's shape functions; the readers know none.
    Here: the reference's forward MACs x 2 x 3 x the batch, nothing recomputed. The shape function is
    `flops.py`'s, or the reference file's own `forward_macs(sizes)` where a later family brings one beside its
    reference."""
    from . import flops
    from .manifest import reference_module
    own = getattr(reference_module(config['reference']), 'forward_macs', None)
    macs = own(config['sizes']) if own else flops.forward_macs(config['reference'], config['sizes'])
    return {'needed_step_flops': macs * 2 * 3 * record['batch_size']}


def loader_workers() -> int:
    return max(1, min(16, (os.cpu_count() or 4) - 2))


def build_argv(config: dict, cell: dict, seed: int, out_dir: str, data_dir) -> list:
    argv = ['--model', config['model'], *config['train_args'], *cell.get('train_args', []),
            '--seed', str(seed % (2 ** 31)), '--epochs', '1', '--log-interval', '1000000',
            '--output', out_dir, '--experiment', cell['name']]
    if data_dir is not None:
        argv += ['--data-dir', data_dir, '-j', str(loader_workers())]
    return argv


class StepWatcher:
    """The wrapper's state; `install()` swaps `ClassificationTask.train_step`
    for `self.step` and `uninstall()` puts it back."""

    def __init__(self, *, make_weights, seconds: float, warmup_steps: int, process_start: float,
                 events: dict, follow_ema: bool, trace_dir=None, inner=None):
        from timm_tpu.task import ClassificationTask
        self.cls = ClassificationTask
        self.inner = inner or ClassificationTask.train_step
        self.saved = ClassificationTask.train_step
        self.make_weights, self.follow_ema = make_weights, follow_ema
        self.seconds, self.warmup_steps, self.process_start = seconds, max(warmup_steps, FOLLOWED), process_start
        self.events, self.trace_dir = events, trace_dir
        self.calls = 0
        self.followed = []            # per followed step: input, target, lr, drop_keys
        self.program = {'losses': []}
        self.t_open = self.t_close = None
        self.call_t, self.return_t, self.losses = [], [], []
        self.compiles_at_open = self.compiles_at_close = None
        self.trace_window = None      # (t0, t1, steps) of the traced part, host clock
        self.annotation = None
        self.batch_size = None
        self.setup_compiles = None
        self.marks = []               # (what, seconds since process start) through set-up
        self.rng_counts = {}          # 'before' the first step, 'after' the last
        self.step_memory = None       # the compiler's plan of the step program (traced run)

    def install(self):
        watcher = self

        def train_step(task, batch, lr, step=0):
            return watcher.step(task, batch, lr, step)

        self.cls.train_step = train_step
        return self

    def uninstall(self):
        self.cls.train_step = self.saved

    def _mark(self, what):
        self.marks.append((what, time.perf_counter() - self.process_start))

    def _compiles(self):
        from timm_tpu.utils.compile_cache import cache_event_total
        return cache_event_total(self.events, 'backend_compile_duration')

    def _span(self, name):
        """Close the open host span, open `name` (traced part only)."""
        import jax
        if self.annotation is not None:
            self.annotation.__exit__(None, None, None)
            self.annotation = None
        if name is not None:
            self.annotation = jax.profiler.TraceAnnotation('bench.' + name)
            self.annotation.__enter__()

    def step(self, task, batch, lr, step):
        import jax
        from . import program
        from . import trace as trace_mod
        k = self.calls
        self.calls += 1
        if k == 0:
            self._mark('first step called')
            program.load_task_weights(task, self.make_weights())
            self._mark('seeded weights loaded')
            self.batch_size = int(batch['input'].shape[0])
            self.rng_counts['before'] = program.drop_path_counts(task.model)
        if k < FOLLOWED:
            # kept on the host: nothing of the check may sit in device memory while the window runs
            self.followed.append({'input': jax.device_get(batch['input']), 'target': jax.device_get(batch['target']),
                                  'lr': float(lr), 'drop_keys': jax.device_get(
                                      jax.tree.map(jax.random.key_data, program.drop_path_keys(task.model)))})
        in_window = k >= self.warmup_steps
        if k == self.warmup_steps:
            self.compiles_at_open = self._compiles()
            self.setup_compiles = dict(self.events)
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
                start = self.make_weights()
                self.program['param_change_norms'] = program.param_change_norms(task, start)
                if self.follow_ema:
                    self.program['ema_change_norms'] = program.ema_change_norms(task, start)
                del start
                self._mark('followed steps read')
        elif not in_window:
            jax.block_until_ready(metrics)
        if in_window and time.perf_counter() - self.t_open >= self.seconds \
                and (not tracing or (self.trace_window and self.trace_window[1])):
            jax.block_until_ready(metrics)
            self.t_close = time.perf_counter()
            self.compiles_at_close = self._compiles()
            self.rng_counts['after'] = program.drop_path_counts(task.model)
            if tracing:
                self.step_memory = program.step_memory(task, batch, lr, step)
            raise WindowClosed()
        return metrics


def run(cell: dict, config: dict, *, seed: int, seconds: float, trace: bool, process_start: float,
        scratch: str, inner_step=None, control_precision=None, log=print) -> dict:
    """Run the cell once; returns the run record `run.py` reduces to the
    result line. `inner_step` replaces the program's `train_step` underneath
    the wrapper (the tests break it there). `control_precision` also follows
    the steps with the reference in that lower precision and records its
    numbers against the float32 reference, and the loss of a first step given
    half its batch (`tools/limits.py`; no benchmark run does)."""
    import jax

    import train
    from timm_tpu.utils.compile_cache import collect_cache_events, configure_compile_cache

    from . import check, traffic, weights
    from .peaks import memory_peak_bytes
    from .manifest import reference_module

    configure_compile_cache()
    t_imported = time.perf_counter() - process_start
    reference = reference_module(config['reference'])
    sizes = config['sizes']
    data_dir = None
    if 'image_folder' in cell['traffic']:
        data_dir = traffic.write_image_folder(
            os.path.join(scratch, 'data', cell['traffic']['image_folder']['name']), cell['traffic']['image_folder'])
    log(f'loader workers: {loader_workers()} (host cores reported: {os.cpu_count()})')
    out_dir = os.path.join(scratch, 'train')
    trace_dir = os.path.join(scratch, 'trace', cell['name']) if trace else None
    argv = build_argv(config, cell, seed, out_dir, data_dir)
    log('train.main ' + ' '.join(argv))

    with collect_cache_events() as events:
        watcher = StepWatcher(
            make_weights=lambda: weights.make(seed, reference.init_spec(sizes)),
            seconds=seconds, warmup_steps=cell['traffic']['warmup_steps'], process_start=process_start,
            events=events, follow_ema='ema_decay' in config['recipe'], trace_dir=trace_dir,
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
    steps = len(watcher.return_t)
    window_s = watcher.t_close - watcher.t_open
    losses = [float(x) for x in jax.device_get(watcher.losses)]
    failed = sum(not math.isfinite(x) for x in losses)
    between = [c - r for c, r in zip(watcher.call_t[1:], watcher.return_t[:-1])]
    record = {
        'runner': 'train', 'cell': cell['name'], 'attempted': steps, 'failed': failed,
        'window_s': window_s, 'steps': steps, 'batch_size': watcher.batch_size,
        'setup_s': watcher.t_open - process_start,
        'memory_peak_bytes': memory_peak_bytes(stats),
        'compiles_in_window': watcher.compiles_at_close - watcher.compiles_at_open,
        'setup_events': watcher.setup_compiles,
        'spans': {'loader_next_s': between,
                  'train_step_dispatch_s': [r - c for c, r in zip(watcher.call_t, watcher.return_t)]},
        'losses_window': losses, 'reference': config['reference'], 'sizes': sizes,
        'device_kind': device.device_kind,
    }
    record['end_to_end'] = {'train_img_per_s': steps * watcher.batch_size / window_s, 'setup_s': record['setup_s']}
    record.update(needed_work(config, record))
    if trace:
        from . import trace as trace_mod
        record['trace'] = trace_mod.reduce_trace(trace_mod.newest_xplane(trace_dir), default_gap_label='host')
        record['trace']['work'] = watcher.trace_window[2]
    log(f'window: {steps} steps of {watcher.batch_size} in {window_s:.3f} s; '
        f'loader_next median {statistics.median(between) * 1e3 if between else float("nan"):.3f} ms; '
        f'compilations in the window: {record["compiles_in_window"]}')

    # the program's state goes before the reference's comes
    followed, program_numbers = watcher.followed, watcher.program
    checks = record['checks'] = {}                       # every number `correct` compares, beside its limit
    own = check.judge_exact({**check.feed_numbers(followed, config['recipe']),
                             **check.rng_numbers(watcher.rng_counts['before'], watcher.rng_counts['after'],
                                                 watcher.calls)}, out=log, into=checks)
    del watcher
    gc.collect()

    t_ref = time.perf_counter()
    ref_cfg = dict(sizes, drop_path_rate=config['drop_path_rate'])
    ref_numbers = reference_follow(reference, ref_cfg, weights.make(seed, reference.init_spec(sizes)), followed,
                                   config, precision='float32')
    numbers = check.training_numbers(program_numbers, ref_numbers)
    ok = check.judge(numbers, config['limits']['train'], out=log, into=checks)
    log(f'reference: {FOLLOWED} steps followed in {time.perf_counter() - t_ref:.1f} s')
    first, owed = program_numbers['losses'][0], math.log(sizes['num_classes'])
    sane = abs(first - owed) <= 0.5
    checks['first_loss'] = check.compared(first, [owed - 0.5, owed + 0.5], sane, 'within')
    log(f'check first_loss: {first:.4f} within ln({sizes["num_classes"]}) +- 0.5: {"ok" if sane else "OVER"}')
    zero_compiles = record['compiles_in_window'] == 0
    checks['compiles_in_window'] = check.compared(record['compiles_in_window'], 0, zero_compiles, 'equal')
    log(f'check compiles_in_window: {record["compiles_in_window"]} limit 0 {"ok" if zero_compiles else "OVER"}')
    record['correct'] = bool(ok and own and sane and zero_compiles and failed == 0 and steps > 0)
    record['numbers'] = {k: v[0] for k, v in numbers.items()}
    if control_precision:
        lower = reference_follow(reference, ref_cfg, weights.make(seed, reference.init_spec(sizes)), followed,
                                 config, precision=control_precision)
        record['control_numbers'] = {k: v[0] for k, v in check.training_numbers(lower, ref_numbers).items()}
        # the fault `loss_gap` is held against: the first step given half its batch
        n = len(followed[0]['input']) // 2
        half = reference_follow(reference, ref_cfg, weights.make(seed, reference.init_spec(sizes)),
                                [dict(followed[0], input=followed[0]['input'][:n], target=followed[0]['target'][:n])],
                                config, precision='float32')
        record['control_numbers']['loss_gap_half_batch'] = abs(half['losses'][0] - ref_numbers['losses'][0])
        record['followed'] = {'program': program_numbers, 'reference': ref_numbers, 'control': lower}
    else:
        record['followed'] = {'program': program_numbers, 'reference': ref_numbers}
    return record


def reference_follow(reference, ref_cfg, start_weights, followed, config, precision: str) -> dict:
    from ..reference import train_step
    recipe = config['recipe']
    return train_step.follow(reference, ref_cfg, start_weights, followed, clip=recipe['clip_grad'],
                             weight_decay=recipe['weight_decay'], ema_decay=recipe.get('ema_decay', 0.0),
                             precision=precision,
                             rows=config['reference_rows'])
