"""Tests of the benchmark (`BENCHMARK.json`, `benchmarks/`), in ONE file so
that the driver's `--dist loadfile` lands them on one worker. Everything runs
at `test_vit` / `test_convnext` size on the CPU through the runners' functions;
nothing here asks for a topology or a chip.

Wall time of the file is stated in PERF.md section 2 ("Tests").
"""
import json
import math
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import check, flops, peaks, trace, traffic  # noqa: E402
from benchmarks.harness.manifest import BENCH_DIR, Manifest, load_json, runner_module  # noqa: E402

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
RESULT_KEYS = {'correct', 'attempted', 'failed', 'metrics', 'device', 'checks'}

TOY_SIZES = {'img_size': 160, 'patch_size': 16, 'in_chans': 3, 'embed_dim': 64, 'depth': 2, 'num_heads': 2,
             'mlp_ratio': 3, 'num_classes': 1000}
# float32 against float32 on the CPU: the two differ by summation order only (1e-6 and less was seen),
# while bfloat16 operands move every number by 1e-3 and more, so 2e-4 separates them
TOY_LIMITS = {'train': {'loss_gap': 2e-4, 'first_grad_norm_gap': 2e-4, 'param_change_norm_gap': 2e-4,
                        'ema_change_norm_gap': 2e-4}}


@pytest.fixture(scope='module')
def manifest():
    return Manifest()


# -- the manifest and the files it names ---------------------------------------

def _names(m):
    data = m.data
    return ([c['name'] for c in data['configs']] + [w['name'] for w in data['workloads']]
            + [w['traffic'] for w in data['workloads']] + [x['name'] for x in data['end_to_end'] + data['per_layer']])


def test_manifest_names_units_and_shape(manifest):
    data = manifest.data
    assert set(data) == {'command', 'paths', 'run_seconds', 'configs', 'workloads', 'end_to_end', 'per_layer'}
    assert data['paths'] == ['benchmarks', 'tests/benchmark_harness'] and data['command'][-1] == 'benchmarks/run.py'
    assert all(NAME.match(n) for n in _names(manifest)), [n for n in _names(manifest) if not NAME.match(n)]
    metrics = data['end_to_end'] + data['per_layer']
    assert all(UNIT.match(x['unit']) and x['better'] in ('lower', 'higher') for x in metrics)
    assert all(x['source'] in ('host_clock', 'device_trace') for x in data['end_to_end'])
    assert all(0.01 <= x['bound'] <= 0.1 for x in data['end_to_end']) and 'setup_s' in manifest.end_to_end
    assert sum(w['chips'] == 4 for w in data['workloads']) <= max(1, len(data['workloads']) // 4)
    assert len(set(_names(manifest))) == len(_names(manifest)) - len(data['workloads']) + len({w['traffic'] for w in data['workloads']})
    assert all(len(c['source']) <= 200 and len(c['why']) <= 200 for c in data['configs'])
    assert all(len(w['why']) <= 200 and w['chips'] in (1, 4) for w in data['workloads'])
    assert 1 <= data['run_seconds'] <= 51 and os.path.getsize(os.path.join(ROOT, 'BENCHMARK.json')) < 64 * 1024


@pytest.mark.parametrize('folder,suffix,key', [('configs', '.json', 'configs'), ('workloads', '.json', 'workloads'),
                                               ('layer_metrics', '.py', 'per_layer')])
def test_every_named_file_exists_and_every_file_is_named(manifest, folder, suffix, key):
    on_disk = {f[:-len(suffix)] for f in os.listdir(os.path.join(BENCH_DIR, folder)) if f.endswith(suffix)}
    assert on_disk == {x['name'] for x in manifest.data[key]}


def test_cells_reach_their_config_reference_runner_and_metrics(manifest):
    for name in manifest.cells:
        cell = manifest.cell(name)
        config = manifest.config(cell['config'])
        assert config['name'] == cell['config'] and config['reduced'] == [
            c for c in manifest.data['configs'] if c['name'] == cell['config']][0]['reduced']
        assert os.path.exists(os.path.join(BENCH_DIR, 'reference', config['reference'] + '.py'))
        assert hasattr(runner_module(cell['runner']), 'run')
        e2e = manifest.metrics_of(name, 'end_to_end')
        assert 'setup_s' in e2e and len(e2e) >= 2
        layer = manifest.metrics_of(name, 'per_layer')
        assert layer and all(callable(manifest.reader(x)) for x in layer)
        assert set(config['limits'][cell['runner']]) == set(TOY_LIMITS['train'])


# -- peaks and shape functions ---------------------------------------------------

def test_peaks_and_known_macs(manifest):
    assert peaks.peak('TPU v5 lite')['bf16_flops'] == 197e12
    with pytest.raises(KeyError):
        peaks.peak('TPU v9 imaginary')
    vit = dict(manifest.config('vit_b16')['sizes'])
    cnx = dict(manifest.config('convnext_b')['sizes'])
    assert flops.forward_macs('vit', vit) / 1e9 == pytest.approx(17.6, abs=0.05)       # arXiv:2010.11929 / timm
    assert flops.forward_macs('convnext', cnx) / 1e9 == pytest.approx(15.4, abs=0.05)  # arXiv:2201.03545 Table 1
    assert flops.train_flops_per_image('vit', vit) == 6 * flops.forward_macs('vit', vit)


# -- traffic -----------------------------------------------------------------------

def test_the_image_folder_is_seeded_linked_and_written_once(tmp_path):
    mix = {'name': 'toy', 'data_seed': 5, 'files': 4, 'links': 3, 'classes': 2, 'width': 48, 'height': 40}
    roots = [traffic.write_image_folder(str(tmp_path / d), mix) for d in ('a', 'b')]
    files = sorted(os.path.relpath(os.path.join(d, f), roots[0]) for d, _, fs in os.walk(roots[0]) for f in fs)
    assert sum(f.startswith('train') and f.endswith('.jpg') for f in files) == 4 * 3
    first = os.path.join(roots[0], 'train', 'class_000', '00000_000.jpg')
    assert os.stat(first).st_nlink == 3 and os.path.samefile(first, first.replace('_000.jpg', '_002.jpg'))
    assert all(open(os.path.join(roots[0], f), 'rb').read() == open(os.path.join(roots[1], f), 'rb').read() for f in files)
    stamp = os.stat(first).st_mtime_ns
    assert traffic.write_image_folder(roots[0], mix) == roots[0] and os.stat(first).st_mtime_ns == stamp
    other = traffic.write_image_folder(str(tmp_path / 'c'), dict(mix, data_seed=6))
    assert open(first, 'rb').read() != open(first.replace(roots[0], other), 'rb').read()


# -- the trace reduction, on the recorded chip trace ---------------------------------

def test_trace_reduction_on_the_recorded_chip_trace():
    want = load_json(os.path.join(BENCH_DIR, 'fixtures', 'toy_matmuls.expected.json'))
    got = trace.reduce_trace(os.path.join(BENCH_DIR, 'fixtures', 'toy_matmuls.xplane.pb'))
    for key in ('busy_s', 'window_s', 'idle_share', 'idle_total_s'):
        assert got[key] == pytest.approx(want[key], rel=1e-9), key
    assert got['breakdown']['device_ops'] == want['breakdown']['device_ops']
    assert got['breakdown']['idle_gaps'][0][0] == 'deliberate_sleep' and got['breakdown']['idle_gaps'][0][1] > 0.015
    assert got['busy_s'] < got['window_s'] and 0.5 < got['idle_share'] < 1.0
    # per-op totals may overlap (the sum of all ops is at least the union), the union may not double-count
    assert sum(got['op_seconds'].values()) >= got['busy_s'] * (1 - 1e-9)


def test_overlapping_events_are_not_counted_twice():
    r = trace.reduce_device([('a', 0, 10), ('b', 5, 20), ('a', 30, 40)], (0, 50))
    assert r['busy_s'] == pytest.approx(30e-9) and r['idle_share'] == pytest.approx(0.4)
    assert r['op_seconds'] == {'a': pytest.approx(20e-9), 'b': pytest.approx(15e-9)}
    assert r['gaps_ns'] == [(20, 30), (40, 50)]
    assert trace.label_gap((20, 30), [('x', 0, 22), ('y', 22, 60)], 'host') == 'y'
    assert trace.label_gap((20, 30), [], 'host') == 'host'


# -- the references against the program, small, float32, on the CPU ------------------

def _compare(name, sizes, reference, x, target):
    """-> f(to_bf16) giving the program's numbers against the float32
    reference's; each side is one jitted program, compiled once."""
    import jax
    import jax.numpy as jnp
    from flax import nnx

    import timm_tpu
    from benchmarks.harness import program, weights
    from benchmarks.reference import train_step

    params = weights.make(11, reference.init_spec(sizes))
    model = timm_tpu.create_model(name, seed=0)
    model.eval()
    graphdef, state, rest = nnx.split(model, nnx.Param, ...)

    @jax.jit
    def program_side(state):
        def loss_fn(st):
            logits = nnx.merge(graphdef, st, rest)(x)
            return train_step.soft_target_cross_entropy_sum(logits, target) / x.shape[0], logits
        (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(state)
        return loss, logits, jax.tree.map(jnp.linalg.norm, grads)

    ref_loss, ref_grads = train_step.loss_and_grads(
        reference.forward, train_step.hashable(sizes), params, x, target, {}, rows=x.shape[0])
    ref_norms = {k: float(jnp.linalg.norm(v)) for k, v in ref_grads.items()}
    ref_logits = jax.jit(lambda p: reference.forward(sizes, p, x))(params)

    def numbers(to_bf16):
        given = {k: v.astype(jnp.bfloat16).astype(jnp.float32) for k, v in params.items()} if to_bf16 else params
        program.load_weights(model, given)
        loss, logits, norms = program_side(nnx.state(model, nnx.Param))
        return {'logits_rel_err': float(jnp.linalg.norm(logits - ref_logits) / jnp.linalg.norm(ref_logits)),
                'loss_gap': abs(float(loss) - float(ref_loss)),
                'grad_norm_gap': check.worst_leaf_gap(
                    {k: float(v) for k, v in program.named_leaves(norms).items()}, ref_norms)[0]}

    return numbers


@pytest.mark.parametrize('name,ref,sizes,side', [
    ('test_vit', 'vit', TOY_SIZES, 160),
    ('test_convnext', 'convnext', {'img_size': 64, 'in_chans': 3, 'depths': [1, 2, 4, 2], 'dims': [24, 32, 48, 64],
                                   'kernel_size': 7, 'mlp_ratio': 4, 'ls_init_value': 1e-6, 'num_classes': 1000}, 64)])
def test_reference_matches_the_program_and_bfloat16_does_not(name, ref, sizes, side):
    import jax.numpy as jnp
    from benchmarks.harness.manifest import reference_module
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, side, side, 3)), jnp.float32)
    target = jnp.asarray(rng.dirichlet(np.ones(1000) * 0.05, 4), jnp.float32)
    numbers = _compare(name, sizes, reference_module(ref), x, target)
    # float32 vs float32 differ by summation order: 1e-6 was seen; weights rounded to bfloat16 (2^-8 relative
    # a weight) move logits by 1e-3 and more. 1e-4 sits a decade from both.
    tol = 1e-4
    sound = numbers(to_bf16=False)
    assert sound['logits_rel_err'] < tol and sound['loss_gap'] < tol and sound['grad_norm_gap'] < tol, sound
    lower = numbers(to_bf16=True)
    assert lower['logits_rel_err'] > tol or lower['grad_norm_gap'] > tol, lower


# -- a run end to end at toy size, from files only added --------------------------------

@pytest.fixture(scope='module')
def toy(tmp_path_factory):
    """A temporary copy of the benchmark's data with a configuration, a cell
    and a per-layer metric ADDED as new files and appended manifest entries —
    no existing file is touched."""
    tmp = tmp_path_factory.mktemp('toybench')
    bench = tmp / 'benchmarks'
    for d in ('configs', 'workloads', 'layer_metrics'):
        shutil.copytree(os.path.join(BENCH_DIR, d), bench / d)
    before = {p: p.read_bytes() for p in bench.rglob('*') if p.is_file()}
    man = load_json(os.path.join(ROOT, 'BENCHMARK.json'))
    (bench / 'configs' / 'toy_vit.json').write_text(json.dumps({
        'name': 'toy_vit', 'source': 'test', 'model': 'test_vit', 'reference': 'vit', 'sizes': TOY_SIZES,
        'drop_path_rate': 0.1,
        'recipe': {'clip_grad': 1.0, 'weight_decay': 0.05, 'ema_decay': 0.9998, 'mixup': 0.8, 'cutmix': 1.0, 'smoothing': 0.1},
        'train_args': ['-b', '8', '--opt', 'adamw', '--weight-decay', '0.05', '--model-ema', '--clip-grad', '1.0',
                       '--drop-path', '0.1', '--mixup', '0.8', '--cutmix', '1.0', '--smoothing', '0.1'],
        'reduced': [], 'reference_rows': 4, 'limits': TOY_LIMITS}))
    (bench / 'workloads' / 'toy_vit_train.json').write_text(json.dumps({
        'config': 'toy_vit', 'runner': 'train', 'chips': 1,
        'train_args': ['--reprob', '0.25', '--device-augment', '--device-prefetch', '2'],
        'traffic': {'warmup_steps': 4, 'image_folder': {'name': 'toy', 'data_seed': 1, 'files': 16, 'links': 30,
                                                        'classes': 4, 'width': 96, 'height': 80}}}))
    (bench / 'layer_metrics' / 'toy_steps.py').write_text(
        "LAYER = 'step'\nUNIT = 'count'\nMOVES = 'train_img_per_s'\n\n\ndef read(run):\n    return run.get('steps')\n")
    man['configs'].append({'name': 'toy_vit', 'source': 'test', 'file': 'benchmarks/configs/toy_vit.json',
                           'reduced': [], 'why': 'test'})
    man['workloads'].append({'name': 'toy_vit_train', 'config': 'toy_vit', 'traffic': 'toy_train', 'chips': 1, 'why': 'test'})
    for m in man['end_to_end'] + man['per_layer']:
        if 'workloads' in m:
            m['workloads'].append('toy_vit_train')
    man['per_layer'].append({'name': 'toy_steps', 'unit': 'count', 'better': 'higher', 'source': 'program_counter',
                             'layer': 'step', 'moves': 'train_img_per_s', 'workloads': ['toy_vit_train']})
    (tmp / 'BENCHMARK.json').write_text(json.dumps(man))
    assert all(p.read_bytes() == content for p, content in before.items())
    return Manifest(bench_dir=str(bench), manifest_path=str(tmp / 'BENCHMARK.json')), str(tmp / 'scratch')


def _run(toy, seconds, **kw):
    import time
    m, scratch = toy
    cell = m.cell('toy_vit_train')
    lines = []
    record = runner_module(cell['runner']).run(
        cell, m.config(cell['config']), seed=2 ** 31 + 11, seconds=seconds, trace=False,
        process_start=time.perf_counter(), scratch=scratch, log=lines.append, **kw)
    return record, lines


@pytest.fixture(scope='module')
def toy_train(toy):
    return _run(toy, 0.4, control_precision='bfloat16')


def test_a_cell_added_by_files_alone_runs_and_prints_the_contracts_line(toy, toy_train):
    from benchmarks import run as bench_run
    record, lines = toy_train
    cell_name = 'toy_vit_train'
    assert record['correct'] and record['failed'] == 0 and record['attempted'] > 0 and record['compiles_in_window'] == 0
    compared = [l.split()[1].rstrip(':') for l in lines if l.startswith('check ') and (' limit ' in l or ' within ' in l)]
    assert {'loss_gap_step3', 'first_grad_norm_gap', 'param_change_norm_gap', 'ema_change_norm_gap', 'feed_repeated_rows',
            'feed_hard_targets', 'feed_never_mixed', 'rng_counts_off', 'first_loss', 'compiles_in_window'} <= set(compared)
    device = {'platform': 'cpu', 'kind': 'cpu', 'count': 1}
    line = json.loads(json.dumps(bench_run.result_line(toy[0], cell_name, record, device, trace=False)))
    assert set(line) == RESULT_KEYS and set(line['device']) == {'platform', 'kind', 'count', 'memory_peak_bytes'}
    assert set(line['metrics']) == set(toy[0].metrics_of(cell_name, 'end_to_end')) and 'setup_s' in line['metrics']
    assert all(set(v) == {'value', 'unit'} and v['value'] > 0 for v in line['metrics'].values())
    # the traced line: per-layer metrics from the readers' own files, the added one among them; a reader
    # that finds nothing to read is left out
    traced = dict(record, device_kind='TPU v5 lite', trace={
        'busy_s': 0.5, 'window_s': 1.0, 'idle_share': 0.5, 'work': 5, 'breakdown': {'device_ops': [], 'idle_gaps': []}})
    line = bench_run.result_line(toy[0], cell_name, traced, device, trace=True)
    assert set(line) == RESULT_KEYS | {'breakdown'} and line['device']['busy_s'] == 0.5
    assert set(line['metrics']) <= set(toy[0].metrics_of(cell_name, 'per_layer'))
    assert line['metrics']['toy_steps']['value'] == record['steps'] and 'hbm_peak_gb.train' not in line['metrics']
    assert line['metrics']['step_wall_ms.train']['value'] > line['metrics']['dispatch_host_ms.train']['value'] > 0
    assert line['metrics']['step_device_ms.train']['value'] == pytest.approx(100.0)


def test_every_number_compared_stands_beside_its_limit_for_the_result_line(toy, toy_train):
    """`run.py` closes its result line with `checks` and its stderr with the same: what `check.judge`,
    `judge_exact` and the runner's own comparisons put into the record as data (`check.compared`), not what their
    printed lines say. The control's numbers, judged without an `into`, are not the run's."""
    from benchmarks import run as bench_run
    record, lines = toy_train
    checks = record['checks']
    assert set(checks) == {l.split()[1].rstrip(':') for l in lines if l.startswith('check ')} >= {
        'loss_gap_step1', 'loss_gap_step3', 'first_grad_norm_gap', 'param_change_norm_gap', 'ema_change_norm_gap',
        'feed_repeated_rows', 'rng_counts_off', 'first_loss', 'compiles_in_window'}
    assert all(set(c) == {'value', 'limit', 'how', 'ok'} and c['ok'] is True for c in checks.values())
    limits = TOY_LIMITS['train']
    assert checks['first_grad_norm_gap']['limit'] == limits['first_grad_norm_gap'] and checks['first_grad_norm_gap']['how'] == 'at most'
    assert checks['loss_gap_step3']['limit'] == limits['loss_gap'] and checks['feed_repeated_rows'] == {'value': 0.0, 'limit': 0, 'how': 'equal', 'ok': True}
    low, high = checks['first_loss']['limit']
    assert checks['first_loss']['how'] == 'within' and low < checks['first_loss']['value'] < high == pytest.approx(math.log(1000) + 0.5)
    # the line: `checks` comes last, and is strict JSON whatever a number reads
    line = bench_run.result_line(toy[0], 'toy_vit_train', record, {'platform': 'cpu', 'kind': 'cpu', 'count': 1}, trace=False)
    assert list(line)[-1] == 'checks' and line['checks'] == checks
    said = bench_run.check_lines(checks)
    assert len(said) == len(checks) and f'check first_grad_norm_gap: {checks["first_grad_norm_gap"]["value"]} at most {limits["first_grad_norm_gap"]} ok' in said
    into = {}
    assert not check.judge({'loss_gap_step1': (math.inf, ''), 'first_grad_norm_gap': (math.nan, '')}, limits, out=lambda s: None, into=into)
    assert into['loss_gap_step1'] == {'value': 'inf', 'limit': limits['loss_gap'], 'how': 'at most', 'ok': False}
    assert into['first_grad_norm_gap']['value'] == 'nan' and not into['first_grad_norm_gap']['ok']
    assert json.loads(json.dumps(into, allow_nan=False)) == into and bench_run.check_lines(into)[0].endswith(' NOT')
    assert not check.judge_exact({'x': (3, 0, 'note')}, out=lambda s: None, into=into) and into['x'] == {'value': 3.0, 'limit': 0, 'how': 'equal', 'ok': False}


def test_the_lower_precision_control_is_not_correct(toy, toy_train):
    """The reference computed in the next lower precision (bfloat16 for this
    float32 toy configuration), put in the program's place, fails a limit; so
    does the loss of a first step given half its batch."""
    record, _ = toy_train
    limits = toy[0].config('toy_vit')['limits']['train']
    sound = {k: (v, '') for k, v in record['numbers'].items()}
    control = {k: (v, '') for k, v in record['control_numbers'].items() if k != 'loss_gap_half_batch'}
    assert check.judge(sound, limits, out=lambda s: None)
    assert not check.judge(control, limits, out=lambda s: None)
    assert record['control_numbers']['loss_gap_half_batch'] > 10 * limits['loss_gap']


def _stuck(real):
    """Hands back metrics and leaves parameters, optimizer state, EMA and RNG counters alone."""
    import jax.numpy as jnp
    return lambda task, batch, lr, step=0: {'loss': jnp.float32(math.log(1000.0)), 'grad_norm': jnp.float32(1.0)}


def _half_batch(real):
    """Trains on the first half of every batch it is given."""
    return lambda task, batch, lr, step=0: real(task, {k: v[:len(v) // 2] for k, v in batch.items()}, lr, step)


def _ema_frozen(real):
    """The whole step, but the moving average is never updated."""
    import jax
    import jax.numpy as jnp

    def step(task, batch, lr, step=0):
        kept = jax.tree.map(jnp.copy, task.ema_params)  # a copy: the step donates its EMA
        metrics = real(task, batch, lr, step)
        task.ema_params = kept
        return metrics
    return step


@pytest.mark.parametrize('broken,over,reads', [
    (_stuck, {'param_change_norm_gap', 'ema_change_norm_gap', 'rng_counts_off', 'first_grad_norm_gap'}, 1.0),
    (_half_batch, {'loss_gap_step1'}, None),
    (_ema_frozen, {'ema_change_norm_gap'}, 1.0)])
def test_a_timed_path_broken_underneath_is_not_correct(toy, broken, over, reads):
    """The rest of a run with the program's step replaced under the wrapper."""
    from timm_tpu.task import ClassificationTask
    record, lines = _run(toy, 0.2, inner_step=broken(ClassificationTask.train_step))
    assert not record['correct'] and record['failed'] == 0
    found = {l.split()[1].rstrip(':') for l in lines if l.startswith('check ') and l.split('(')[0].rstrip().endswith('OVER')}
    assert over <= found, found
    if broken is _ema_frozen:
        assert found == over
    if reads is not None:
        assert record['numbers'][sorted(over & set(record['numbers']))[0]] == pytest.approx(reads)


def test_the_feed_and_counter_checks_see_what_they_are_there_for():
    rng = np.random.default_rng(0)

    def batch(mixed=True, smooth=0.1):
        t = np.full((4, 10), smooth / 10, np.float32)
        for r in range(4):
            a, b = rng.choice(10, 2, replace=False)
            t[r, a] += (1 - smooth) * (0.7 if mixed else 1.0)
            t[r, b] += (1 - smooth) * (0.3 if mixed else 0.0)
        return {'input': rng.standard_normal((4, 8, 8, 3)).astype(np.float32), 'target': t}

    recipe = {'mixup': 0.8, 'cutmix': 1.0, 'smoothing': 0.1}
    value = lambda numbers: {k: v[0] for k, v in numbers.items()}  # noqa: E731
    sound = [batch(), batch(mixed=False), batch()]
    assert value(check.feed_numbers(sound, recipe)) == {'feed_repeated_rows': 0, 'feed_hard_targets': 0, 'feed_never_mixed': 0}
    assert value(check.feed_numbers([sound[0], sound[1], sound[0]], recipe))['feed_repeated_rows'] == 4
    assert value(check.feed_numbers([batch(smooth=0.0, mixed=False)] + sound, recipe))['feed_hard_targets'] == 4
    assert value(check.feed_numbers([batch(mixed=False) for _ in range(3)], recipe))['feed_never_mixed'] == 1
    assert 'feed_never_mixed' not in check.feed_numbers(sound, {'smoothing': 0.1})
    before = {'blocks.0.drop_path1': 2, 'blocks.1.drop_path1': 2}
    assert value(check.rng_numbers(before, {k: v + 7 for k, v in before.items()}, 7)) == {'rng_counts_off': 0}
    assert value(check.rng_numbers(before, dict(before, **{'blocks.0.drop_path1': 9}), 7)) == {'rng_counts_off': 1}
    lines = []
    assert not check.judge_exact(check.rng_numbers(before, before, 7), out=lines.append) and 'OVER' in lines[0]


def test_run_py_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    done = subprocess.run([sys.executable, os.path.join(ROOT, 'benchmarks', 'run.py'), '--workload', 'vit_b16_train',
                           '--seed', '1', '--seconds', '1', '--trace', '0'], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode != 0 and 'need "tpu"' in done.stderr
    assert not any(l.lstrip().startswith('{') for l in done.stdout.splitlines())
