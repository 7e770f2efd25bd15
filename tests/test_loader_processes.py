"""The image loader's decode processes (`timm_tpu/data/loader.py` `_DecodePool`,
`timm_tpu/data/decode_worker.py`): their life cycle, the loader's semantics over
both decode stages, the counters, and what a worker imports.

Life-cycle cases run a child Python in a session of its own, so that whatever it
leaves behind can be listed from /proc by session id after it is gone.
"""
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest
from PIL import Image

from loader_datasets import Numbered

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
STAGES = ('threads', 'processes')


@pytest.fixture(autouse=True)
def time_limit():
    """Every case here ends within 120 s or fails: a loader that leaves something
    blocked must not hold the whole run."""
    def expired(signum, frame):
        raise TimeoutError('the test exceeded its 120 s')
    saved = signal.signal(signal.SIGALRM, expired)
    signal.alarm(120)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, saved)


@pytest.fixture(autouse=True)
def workers_can_import_the_test_datasets(monkeypatch):
    monkeypatch.setenv('PYTHONPATH', os.pathsep.join(filter(None, [HERE, os.environ.get('PYTHONPATH')])))


@pytest.fixture(scope='module')
def jpeg_root(tmp_path_factory):
    """96 JPEGs of 56 x 48, one class each for `train` (the target names the file), 8 for `val`."""
    root = tmp_path_factory.mktemp('jpegs')
    rng = np.random.RandomState(0)
    for split, n in (('train', 96), ('val', 8)):
        for i in range(n):
            d = root / split / f'c{i % 4}' if split == 'val' else root / split / f'c{i:03d}'
            d.mkdir(parents=True, exist_ok=True)
            Image.fromarray(rng.randint(0, 255, (48, 56, 3), np.uint8)).save(d / f'{i}.jpg')
    return str(root)


def _stat(pid):
    """(state, parent, session) of a process, or None when it is gone."""
    try:
        with open(f'/proc/{pid}/stat') as f:
            fields = f.read().rsplit(')', 1)[1].split()
    except OSError:
        return None
    return fields[0], int(fields[1]), int(fields[3])


def _alive_in_session(session):
    pids = []
    for name in os.listdir('/proc'):
        if name.isdigit():
            stat = _stat(int(name))
            if stat is not None and stat[2] == session and stat[0] != 'Z':
                pids.append(int(name))
    return pids


def _gone_within(session, seconds):
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        if not _alive_in_session(session):
            return True
        time.sleep(0.05)
    return False


PREAMBLE = '''
import json, os, sys, time
from timm_tpu.data import create_dataset, create_loader

def workers():
    """pids of this process's decode workers, and whether any holds something of /dev/shm"""
    found, shm = [], False
    for name in os.listdir('/proc'):
        if not name.isdigit():
            continue
        try:
            with open(f'/proc/{name}/stat') as f:
                parent = int(f.read().rsplit(')', 1)[1].split()[1])
            with open(f'/proc/{name}/cmdline') as f:
                cmdline = f.read()
            if parent == os.getpid() and 'decode_worker.py' in cmdline:
                found.append(int(name))
                with open(f'/proc/{name}/maps') as f:
                    shm = shm or '/dev/shm' in f.read()
                shm = shm or any('/dev/shm' in os.readlink(f'/proc/{name}/fd/{fd}') for fd in os.listdir(f'/proc/{name}/fd'))
        except OSError:
            pass
    return sorted(found), shm

def image_loader(size=224, workers=3):
    return create_loader(create_dataset('', root=sys.argv[1], split='train'), (3, size, size), 4, is_training=True,
                         num_workers=workers, auto_augment='rand-m9-mstd0.5-inc1')
'''


def _child(script, *args, **popen):
    env = dict(os.environ, JAX_PLATFORMS='cpu', PYTHONPATH=os.pathsep.join(filter(None, [ROOT, os.environ.get('PYTHONPATH')])))
    return subprocess.Popen([sys.executable, '-c', PREAMBLE + script, *args], cwd=ROOT, env=env, start_new_session=True,
                            stdout=subprocess.PIPE, text=True, **popen)


def test_the_benchmarks_way_out_leaves_no_process(jpeg_root):
    """An exception out of the middle of an epoch, nothing closed, then
    `os._exit`: what `benchmarks/run.py` does. The workers are alive (blocked on
    their full pipes) when the parent leaves, and end themselves."""
    child = _child('''
class WindowClosed(Exception):
    pass

loader = image_loader()
batches = iter(loader)  # held to the end: nothing finalises the pass, as when a traceback keeps its frames
def epoch():
    for i, batch in enumerate(batches):
        if i == 2:
            raise WindowClosed()
try:
    epoch()
except WindowClosed:
    pass
time.sleep(0.5)
pids, shm = workers()
print(json.dumps({'pids': pids, 'shm': shm}), flush=True)
os._exit(0)
''', jpeg_root)
    out, _ = child.communicate(timeout=90)
    report = json.loads(out.strip().splitlines()[-1])
    assert child.returncode == 0 and len(report['pids']) == 3 and not report['shm']
    assert _gone_within(child.pid, 5.0), _alive_in_session(child.pid)


def test_a_parent_killed_outright_leaves_no_process(jpeg_root):
    """SIGKILL in the middle of an epoch with the queues full and the workers
    blocked in their writes: nothing of the parent runs, the workers see their
    stdin close."""
    child = _child('''
loader = image_loader()
it = iter(loader)
next(it); next(it)
time.sleep(1.0)         # the queues fill, the workers block
pids, shm = workers()
print(json.dumps({'pids': pids, 'shm': shm}), flush=True)
time.sleep(60)
''', jpeg_root)
    try:
        report = json.loads(child.stdout.readline())
        assert len(report['pids']) == 3 and not report['shm']
        assert all(_stat(pid) is not None for pid in report['pids'])
    finally:
        os.kill(child.pid, signal.SIGKILL)
        child.wait(timeout=10)
    assert _gone_within(child.pid, 5.0), _alive_in_session(child.pid)


@pytest.fixture(scope='module')
def pool_report(jpeg_root):
    """One child: two whole epochs, an early break, close() twice, a new pass."""
    child = _child('''
loader = image_loader(size=32)
report = {'epochs': []}
for epoch in range(2):
    loader.set_epoch(epoch)
    n = sum(len(t) for _, t in loader)
    report['epochs'].append({'samples': n, 'pids': workers()[0]})
for batch in loader:
    break
report['after_break'] = workers()[0]
loader.close()
loader.close()
report['after_close'] = workers()[0]
from timm_tpu.utils import tracing
report['gauge_after_close'] = tracing.snapshot()['gauges']['loader.decode_procs'][-1][1]
report['again'] = {'samples': sum(len(t) for _, t in loader), 'pids': workers()[0]}
loader.close()
report['at_exit'] = workers()[0]
print(json.dumps(report), flush=True)
''', jpeg_root)
    out, _ = child.communicate(timeout=90)
    assert child.returncode == 0
    assert _gone_within(child.pid, 5.0)
    return json.loads(out.strip().splitlines()[-1])


def test_two_whole_epochs_reuse_the_same_workers(pool_report):
    first, second = pool_report['epochs']
    assert first['samples'] == second['samples'] == 96
    assert len(first['pids']) == 3 and first['pids'] == second['pids']


def test_close_after_an_early_break_is_idempotent_and_a_new_pass_works(pool_report):
    assert pool_report['after_break'] == []     # ending a pass early leaves nothing blocked, and nothing running
    assert pool_report['after_close'] == [] and pool_report['gauge_after_close'] == 0
    assert pool_report['again']['samples'] == 96 and len(pool_report['again']['pids']) == 3
    assert not set(pool_report['again']['pids']) & set(pool_report['epochs'][0]['pids'])
    assert pool_report['at_exit'] == []


TRAIN_ARGS = ['--model', 'test_vit', '--num-classes', '96', '-b', '8', '-j', '2', '--epochs', '1', '--log-interval', '1',
              '--aa', 'rand-m9-mstd0.5-inc1', '--device-augment', '--device-prefetch', '2']


def test_train_main_whose_step_raises_leaves_no_process(jpeg_root, tmp_path):
    child = _child('''
import train
from timm_tpu.task import ClassificationTask
inner, seen = ClassificationTask.train_step, []
def step(task, batch, lr, step=0):
    seen.append(workers()[0])
    if len(seen) == 3:
        raise RuntimeError('the third step fails')
    return inner(task, batch, lr, step)
ClassificationTask.train_step = step
try:
    train.main(['--data-dir', sys.argv[1], '--output', sys.argv[2], *sys.argv[3:]])
except RuntimeError as e:
    kept = e
print(json.dumps({'during': seen[1], 'after': workers()[0]}), flush=True)
os._exit(0)
''', jpeg_root, str(tmp_path), *TRAIN_ARGS)
    out, _ = child.communicate(timeout=110)
    report = json.loads(out.strip().splitlines()[-1])
    assert child.returncode == 0 and len(report['during']) == 2 and report['after'] == []
    assert _gone_within(child.pid, 5.0), _alive_in_session(child.pid)


# -- the loader's semantics, over both decode stages ----------------------------------------

def _loader(dataset, **kwargs):
    from timm_tpu.data.loader import ThreadedLoader
    kwargs.setdefault('num_workers', 3)
    kwargs.setdefault('seed', 3)
    return ThreadedLoader(dataset, **kwargs)


def _stage(loader):
    return 'threads' if loader._pool is None else 'processes'


@pytest.mark.parametrize('stage', STAGES)
@pytest.mark.parametrize('drop_last', (True, False))
def test_every_index_of_the_epoch_exactly_once(stage, drop_last):
    loader = _loader(Numbered(37, stage == 'processes'), batch_size=4, is_training=True, drop_last=drop_last)
    assert _stage(loader) == stage
    try:
        for epoch in range(2):
            loader.set_epoch(epoch)
            batches = list(loader)
            targets = np.concatenate([t for _, t in batches])
            assert all((x[:, 0, 0, 0] == t % 251).all() for x, t in batches)      # pixels and target travel together
            assert len(set(targets.tolist())) == len(targets) == (36 if drop_last else 37)
            assert len(batches) == len(loader)
    finally:
        loader.close()


def test_more_workers_than_cores_lose_and_double_nothing():
    loader = _loader(Numbered(1003, True), batch_size=7, is_training=True, num_workers=(os.cpu_count() or 4) + 3)
    try:
        for epoch in range(2):
            loader.set_epoch(epoch)
            targets = np.concatenate([t for _, t in loader])
            assert len(targets) == 1001 and len(set(targets.tolist())) == 1001
        assert loader._pool.alive() == loader.num_workers
    finally:
        loader.close()


@pytest.mark.parametrize('stage', STAGES)
def test_evaluation_batches_come_in_index_order(stage):
    loader = _loader(Numbered(37, stage == 'processes'), batch_size=5, is_training=False)
    try:
        assert [t.tolist() for _, t in loader] == [list(range(i, min(i + 5, 37))) for i in range(0, 37, 5)]
    finally:
        loader.close()


def test_evaluation_batches_of_a_jpeg_folder_are_the_thread_stages(jpeg_root):
    from timm_tpu.data import create_dataset, create_loader
    seen = {}
    for stage in STAGES:
        dataset = create_dataset('', root=jpeg_root, split='train')
        dataset.decodes_files = stage == 'processes'
        loader = create_loader(dataset, (3, 32, 32), 7, is_training=False, num_workers=3)
        assert _stage(loader) == stage
        seen[stage] = list(loader)
        loader.close()
    assert len(seen['threads']) == len(seen['processes']) == 14
    for (x, t), (y, u) in zip(seen['threads'], seen['processes']):
        assert x.dtype == y.dtype and np.array_equal(x, y) and np.array_equal(t, u)
    assert np.concatenate([t for _, t in seen['processes']]).tolist() == list(range(96))


@pytest.mark.parametrize('stage', STAGES)
def test_repeated_augmentation_gives_its_duplicates(stage):
    loader = _loader(Numbered(300, stage == 'processes'), batch_size=4, is_training=True, num_aug_repeats=3, seed=0)
    try:
        expected = loader._shard_indices(shuffled=True)
        targets = np.concatenate([t for _, t in loader])
        assert sorted(targets.tolist()) == sorted(expected[:len(targets)].tolist()) and len(targets) == 256
        assert max(np.bincount(targets)) == 3
    finally:
        loader.close()


@pytest.mark.parametrize('stage', STAGES)
def test_a_poisoned_sample_is_skipped_within_the_budget_and_fails_the_epoch_beyond_it(stage, monkeypatch):
    from timm_tpu.resilience import TooManyBadSamples
    monkeypatch.setenv('TIMM_TPU_POISON_BUDGET', '2')
    loader = _loader(Numbered(24, stage == 'processes', poison={3, 17}), batch_size=4, is_training=False)
    try:
        assert np.concatenate([t for _, t in loader]).tolist() == [i for i in range(24) if i not in (3, 17)]
    finally:
        loader.close()
    loader = _loader(Numbered(24, stage == 'processes', poison={3, 9, 17}), batch_size=4, is_training=False)
    try:
        with pytest.raises(TooManyBadSamples, match='poison budget of 2'):
            list(loader)
    finally:
        loader.close()


@pytest.mark.parametrize('stage', STAGES)
def test_a_transient_oserror_rides_the_retry_and_the_injectors_tick_fires(stage):
    from timm_tpu.resilience import TooManyBadSamples, set_fault_injector
    loader = _loader(Numbered(16, stage == 'processes', flaky={2, 11}), batch_size=4, is_training=False)
    try:
        assert np.concatenate([t for _, t in loader]).tolist() == list(range(16))     # nothing skipped
    finally:
        loader.close()
    set_fault_injector('io_error%1')        # every read raises: the retries run out, every sample is poison
    try:
        loader = _loader(Numbered(64, stage == 'processes'), batch_size=4, is_training=False)
        with pytest.raises(TooManyBadSamples, match='fault-inject'):
            list(loader)
    finally:
        set_fault_injector(None)
        loader.close()


def test_a_worker_killed_from_outside_fails_the_epoch_by_name():
    from timm_tpu.data.loader import DecodeWorkerDied
    from timm_tpu.utils import tracing
    loader = _loader(Numbered(400_000, True), batch_size=8, is_training=False)
    exits = tracing.snapshot()['counters'].get('loader.worker_exits', 0)
    try:
        it = iter(loader)
        next(it)
        victim = loader._pool.procs[1].pid
        os.kill(victim, signal.SIGKILL)
        with pytest.raises(DecodeWorkerDied, match=f'decode worker 1 .pid {victim}. ended before the loader closed it'):
            for _ in it:
                pass
        assert tracing.snapshot()['counters']['loader.worker_exits'] == exits + 1
        assert loader._pool.alive() == 0        # the failed pass ended the others too
        assert next(iter(loader))[1].tolist() == list(range(8))     # a new pass starts new workers
    finally:
        loader.close()


def test_workers_draw_their_own_streams_and_the_seed_decides_them():
    def draws(seed, epoch):
        loader = _loader(Numbered(64, True, draws=True), batch_size=8, is_training=True, seed=seed)
        loader.set_epoch(epoch)
        try:
            return {int(t): tuple(x.ravel()) for xs, ts in loader for x, t in zip(xs, ts)}
        finally:
            loader.close()
    first = draws(7, 0)
    assert len(first) == 64 and len(set(first.values())) == 64                 # no two samples share a draw
    assert len({a for a, _ in first.values()}) == 64 and len({b for _, b in first.values()}) == 64
    assert draws(7, 0) == first                                                # (seed, epoch, worker) repeats
    assert not set(draws(7, 1).values()) & set(first.values())                 # another epoch, other draws
    assert not set(draws(8, 0).values()) & set(first.values())


def test_a_token_feed_starts_no_process(tmp_path):
    from timm_tpu.data.dataset import TokenWindows
    np.arange(64 * 20, dtype='<i4').tofile(tmp_path / 'train.bin')
    loader = _loader(TokenWindows(str(tmp_path / 'train.bin'), 64), batch_size=4, is_training=True)
    assert loader._pool is None
    it = iter(loader)
    next(it)
    children = [name for name in os.listdir('/proc') if name.isdigit() and (_stat(int(name)) or (0, 0))[1] == os.getpid()]
    assert not any('decode_worker' in open(f'/proc/{pid}/cmdline').read() for pid in children)
    assert sum(len(t) for _, t in it) == 16
    loader.close()      # nothing to end: a no-op


# -- counters, and what a worker imports -----------------------------------------------------

def test_the_workers_reports_land_in_the_main_process_counters(jpeg_root, tmp_path, caplog):
    import logging

    import train
    from timm_tpu.utils import tracing
    before = tracing.snapshot()
    with caplog.at_level(logging.INFO):
        train.main(['--data-dir', jpeg_root, '--output', str(tmp_path), *TRAIN_ARGS])
    after = tracing.snapshot()
    did = {k: after['counters'].get(k, 0) - before['counters'].get(k, 0) for k in
           ('loader.samples', 'loader.decode_busy_ns', 'loader.batches', 'loader.worker_exits')}
    assert did['loader.samples'] >= 96 + 8 and did['loader.batches'] >= 12 + 1      # the epoch and its evaluation
    assert did['loader.decode_busy_ns'] > did['loader.samples'] * 1e5               # over 0.1 ms a decoded sample
    assert did['loader.worker_exits'] == 0
    since = before['gauges'].get('loader.decode_procs', [(0, 0)])[-1][0]
    procs = [v for t, v in after['gauges']['loader.decode_procs'] if t > since]
    assert max(procs) == 2 and procs[-1] == 0                                       # -j 2; 0 once train.main has closed them
    lines = [r.getMessage() for r in caplog.records if 'host ms/step' in r.getMessage()]
    assert len(lines) == 12 and all(line.endswith(' procs 2 exits 0') for line in lines), lines[:2]
    assert any('decode processes, first batch' in r.getMessage() for r in caplog.records)


def test_both_names_are_declared_with_the_input_layer():
    from timm_tpu.utils import tracing
    assert tracing.SPANS['loader.decode_procs'][0] == tracing.SPANS['loader.worker_exits'][0] == 'input'
    assert tracing.SPANS['loader.decode_procs'][1].startswith('gauge') and tracing.SPANS['loader.worker_exits'][1].startswith('counter')


def test_a_worker_imports_no_jax(jpeg_root):
    """The worker's entry module by its path, as the pool runs it, then what
    unpickling an image dataset with its transform pulls in: well under the
    seconds `import timm_tpu` costs, and without a device runtime."""
    script = '''
import importlib.util, sys, time
start = time.perf_counter()
spec = importlib.util.spec_from_file_location('decode_worker', sys.argv[1])
worker = importlib.util.module_from_spec(spec)
spec.loader.exec_module(worker)
worker.install_package_stubs()
import timm_tpu.data.dataset, timm_tpu.data.readers_streaming, timm_tpu.data.transforms_factory
took = time.perf_counter() - start
heavy = sorted(m for m in sys.modules if m.split('.')[0] in ('jax', 'jaxlib', 'flax', 'optax', 'libtpu'))
print(repr((took, heavy)))
'''
    from timm_tpu.data import decode_worker
    out = subprocess.run([sys.executable, '-c', script, decode_worker.__file__], capture_output=True, text=True,
                         timeout=60, cwd=ROOT, check=True).stdout
    took, heavy = eval(out.strip().splitlines()[-1])
    assert heavy == [] and took < 5.0, (took, heavy)
