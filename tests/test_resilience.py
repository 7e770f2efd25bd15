"""Fault-tolerance subsystem tests (timm_tpu/resilience): durable checkpoint
verification + fallback, recovery ordering, non-finite sentinel, reader
retry/skip policy, fault injection, elastic rescale planning, the async
checkpoint writer, and the SIGTERM→`--resume auto` parity drill on a tiny
CPU model."""
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from timm_tpu.resilience import (
    AsyncCheckpointWriter, CorruptCheckpointError, FaultInjector, GracefulShutdown,
    NonFiniteError, SkipBudget, TooManyBadSamples, atomic_write_npz, backoff_delays,
    capture_host_rng, convert_loader_position, fault_selftest, find_checkpoints,
    load_with_fallback, plan_elastic_resume, rescale_for_devices, resolve_auto_resume,
    restore_host_rng, retry_io, set_durable_write_listener, set_fault_injector,
    snapshot_to_host, verify_checkpoint,
)

pytestmark = pytest.mark.resilience

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- durable checkpoints -----------------------------------------------------

def test_atomic_write_verify_roundtrip(tmp_path):
    path = str(tmp_path / 'last.npz')
    arrays = {'state_dict.w': np.arange(16.0).reshape(4, 4), 'epoch': np.asarray(3)}
    atomic_write_npz(path, arrays, meta={'epoch': 3})
    ok, reason = verify_checkpoint(path)
    assert ok, reason
    state, meta, used = load_with_fallback(path)
    assert used == path and meta['epoch'] == 3
    np.testing.assert_array_equal(state['state_dict.w'], arrays['state_dict.w'])
    # no temp litter from the atomic write
    assert not [n for n in os.listdir(tmp_path) if n.endswith('.tmp')]


def test_manifest_detects_bit_corruption(tmp_path):
    """A flipped byte INSIDE a structurally-valid zip only the manifest catches."""
    path = str(tmp_path / 'last.npz')
    atomic_write_npz(path, {'w': np.zeros(64, np.float32)}, meta={})
    data = bytearray(open(path, 'rb').read())
    # flip a byte in the middle of the (uncompressed) array payload
    data[len(data) // 2] ^= 0xFF
    open(path, 'wb').write(bytes(data))
    ok, reason = verify_checkpoint(path)
    assert not ok and ('sha256' in reason or 'unreadable' in reason)


def test_truncated_checkpoint_falls_back_to_newest_valid(tmp_path):
    older = str(tmp_path / 'checkpoint-0.npz')
    newest = str(tmp_path / 'checkpoint-1.npz')
    atomic_write_npz(older, {'w': np.ones(8)}, meta={'epoch': 0})
    atomic_write_npz(newest, {'w': np.full(8, 2.0)}, meta={'epoch': 1})
    with open(newest, 'r+b') as f:
        f.truncate(os.path.getsize(newest) // 2)
    ok, _ = verify_checkpoint(newest)
    assert not ok
    state, _meta, used = load_with_fallback(newest, search_dir=str(tmp_path))
    assert used == older
    np.testing.assert_array_equal(state['w'], np.ones(8))
    with pytest.raises(CorruptCheckpointError):
        with open(older, 'r+b') as f:
            f.truncate(8)
        load_with_fallback(newest, search_dir=str(tmp_path))


def test_checkpoint_ordering_numeric_not_lexicographic(tmp_path):
    # the seed bug: sorted() ranked recovery-1-999 above recovery-1-1000
    for epoch, batch in [(1, 999), (1, 1000), (0, 5)]:
        atomic_write_npz(str(tmp_path / f'recovery-{epoch}-{batch}.npz'),
                         {'w': np.asarray(float(batch))}, meta={'epoch': epoch})
    names = [os.path.basename(p) for p in find_checkpoints(str(tmp_path))]
    assert names[0] == 'recovery-1-1000.npz'
    assert names.index('recovery-1-1000.npz') < names.index('recovery-1-999.npz')
    # a completed epoch 1 outranks any mid-epoch-1 recovery
    atomic_write_npz(str(tmp_path / 'last.npz'),
                     {'w': np.asarray(0.0), 'epoch': np.asarray(1)}, meta={'epoch': 1})
    assert os.path.basename(find_checkpoints(str(tmp_path))[0]) == 'last.npz'
    assert resolve_auto_resume(str(tmp_path)).endswith('last.npz')


def test_saver_find_recovery_and_startup_cleanup(tmp_path):
    from timm_tpu.utils import CheckpointSaver
    d = str(tmp_path)
    atomic_write_npz(os.path.join(d, 'recovery-1-999.npz'), {'w': np.asarray(1.0)})
    atomic_write_npz(os.path.join(d, 'recovery-1-1000.npz'), {'w': np.asarray(2.0)})
    # orphaned tmp artifacts + a corrupt recovery file from a "crash"
    open(os.path.join(d, 'tmp.npz'), 'wb').write(b'partial')
    open(os.path.join(d, '.last.npz.123.tmp'), 'wb').write(b'partial')
    open(os.path.join(d, 'recovery-1-2000.npz'), 'wb').write(b'torn write')
    saver = CheckpointSaver(task=None, checkpoint_dir=d, recovery_dir=d)
    names = set(os.listdir(d))
    assert 'tmp.npz' not in names and '.last.npz.123.tmp' not in names
    assert 'recovery-1-2000.npz' not in names  # corrupt → swept
    assert saver.find_recovery().endswith('recovery-1-1000.npz')


# -- non-finite sentinel -----------------------------------------------------

@pytest.fixture(scope='module')
def tiny_task(mesh8):
    import timm_tpu
    from timm_tpu.loss import LabelSmoothingCrossEntropy
    from timm_tpu.optim import create_optimizer_v2
    from timm_tpu.task import ClassificationTask
    model = timm_tpu.create_model('test_vit', num_classes=10, img_size=32)
    opt = create_optimizer_v2(model, opt='adamw', lr=1e-3)
    return ClassificationTask(
        model, optimizer=opt, mesh=mesh8,
        train_loss_fn=LabelSmoothingCrossEntropy(0.1), nonfinite_tolerance=3)


def _batch(mesh, nan=False, seed=0):
    import jax.numpy as jnp
    from timm_tpu.parallel import shard_batch
    rng = np.random.RandomState(seed)
    x = rng.rand(8, 32, 32, 3).astype(np.float32)
    if nan:
        x = x * np.nan
    return shard_batch({'input': jnp.asarray(x), 'target': jnp.asarray(rng.randint(0, 10, 8))},
                       mesh)


def test_nonfinite_step_commits_nothing(mesh8, tiny_task):
    import jax
    from flax import nnx
    tiny_task.reset_nonfinite()
    tiny_task.train_step(_batch(mesh8), lr=1e-3, step=0)
    before = [np.asarray(p) for p in jax.tree.leaves(nnx.state(tiny_task.model, nnx.Param))]
    opt_before = [np.asarray(l) for l in jax.tree.leaves(tiny_task.opt_state)]
    metrics = tiny_task.train_step(_batch(mesh8, nan=True), lr=1e-3, step=1)
    assert int(metrics['nonfinite_count']) == 1 and int(metrics['nonfinite_total']) == 1
    after = [np.asarray(p) for p in jax.tree.leaves(nnx.state(tiny_task.model, nnx.Param))]
    opt_after = [np.asarray(l) for l in jax.tree.leaves(tiny_task.opt_state)]
    assert all(np.array_equal(a, b) for a, b in zip(before, after))
    assert all(np.array_equal(a, b) for a, b in zip(opt_before, opt_after))
    # a good step resets the consecutive counter (total stays)
    metrics = tiny_task.train_step(_batch(mesh8), lr=1e-3, step=2)
    assert int(metrics['nonfinite_count']) == 0 and int(metrics['nonfinite_total']) == 1


def test_nonfinite_tolerance_aborts(mesh8, tiny_task):
    tiny_task.reset_nonfinite()
    with pytest.raises(NonFiniteError) as ei:
        for step in range(5):
            tiny_task.train_step(_batch(mesh8, nan=True), lr=1e-3, step=step)
    assert ei.value.consecutive == 3  # tolerance from the fixture
    tiny_task.reset_nonfinite()


# -- the loop around the lagged read (train.main in-process) ------------------

@pytest.fixture
def loop_journal(monkeypatch):
    """What `train.main` did, in order: ('step', n) for a `train_step(step=n)`,
    ('drain',) for a `drain()` that had a step to read, ('tripped', step of the
    counters) where either raised, ('recovery', update) and ('checkpoint', epoch)
    where the saver was asked to write."""
    from timm_tpu.task import ClassificationTask
    from timm_tpu.utils.checkpoint_saver import CheckpointSaver
    journal = []

    def watched(inner, entry):
        def call(self, *args, **kwargs):
            what = entry(self, *args, **kwargs)
            if what:
                journal.append(what)
            try:
                return inner(self, *args, **kwargs)
            except NonFiniteError as e:
                journal.append(('tripped', e.step, e.consecutive))
                raise
        return call

    monkeypatch.setattr(ClassificationTask, 'train_step', watched(
        ClassificationTask.train_step, lambda self, batch, lr, step=0: ('step', step)))
    monkeypatch.setattr(ClassificationTask, 'drain', watched(
        ClassificationTask.drain, lambda self: ('drain',) if self._unread is not None else None))
    monkeypatch.setattr(CheckpointSaver, 'save_recovery', watched(
        CheckpointSaver.save_recovery, lambda self, epoch, batch_idx=0, **kw: ('recovery', batch_idx)))
    monkeypatch.setattr(CheckpointSaver, 'save_checkpoint', watched(
        CheckpointSaver.save_checkpoint, lambda self, epoch, metric=None: ('checkpoint', epoch)))
    yield journal
    set_fault_injector('')


def _main_argv(out_dir, *extra):
    return ['--synthetic-data', '--model', 'test_vit', '--img-size', '32', '-b', '8', '--synthetic-len', '64',
            '--opt', 'sgd', '--lr', '0.05', '--sched', 'cosine', '--warmup-epochs', '0', '--workers', '1',
            '--log-interval', '50', '--output', str(out_dir), '--experiment', 'lag', '--nonfinite-tolerance', '3', *extra]


@pytest.mark.parametrize('recovery_interval', [0, 1], ids=['in_the_next_call', 'in_the_drain_before_a_recovery_save'])
def test_the_loop_aborts_one_enqueued_step_after_the_tripping_update_and_saves_nothing_between(
        tmp_path, loop_journal, recovery_interval):
    """Updates 2, 3, 4 are NaN at tolerance 3. The host reads one step behind:
    the abort is for update 4 with 3 consecutive, out of the call that has
    enqueued update 5 or, where a recovery file is due after every update, out of
    the drain before update 4's. Nothing is written after update 4 ran."""
    import train
    with pytest.raises(SystemExit) as ei:
        train.main(_main_argv(tmp_path, '--epochs', '1', '--fault-inject', 'nan_grads@2:3',
                              '--recovery-interval', str(recovery_interval)))
    assert ei.value.code == 3
    steps = [e[1] for e in loop_journal if e[0] == 'step']
    assert loop_journal[-1] == ('tripped', 4, 3)
    if recovery_interval:
        assert steps == [0, 1, 2, 3, 4] and loop_journal[-3:-1] == [('step', 4), ('drain',)]
        assert [e[1] for e in loop_journal if e[0] == 'recovery'] == [0, 1, 2, 3]
    else:
        assert steps == [0, 1, 2, 3, 4, 5] and loop_journal[-2] == ('step', 5)
    after = loop_journal[loop_journal.index(('step', 4)):]
    assert not [e for e in after if e[0] in ('recovery', 'checkpoint')]
    names = os.listdir(tmp_path / 'lag')
    assert 'recovery-0-4.npz' not in names and not [n for n in names if n.startswith(('checkpoint-', 'last'))]
    for name in names:
        if name.endswith('.npz'):
            assert verify_checkpoint(str(tmp_path / 'lag' / name))[0], name


@pytest.mark.parametrize('recovery_interval', [0, 1], ids=['in_the_next_call', 'in_the_drain_before_a_recovery_save'])
def test_the_loop_rolls_back_one_enqueued_step_after_the_tripping_update_and_goes_on(
        tmp_path, loop_journal, caplog, monkeypatch, recovery_interval):
    """`--nonfinite-rollback`, two epochs of 8 updates, updates 10, 11, 12 NaN:
    the rollback is for update 12, out of the call that enqueued update 13 (whose
    batch is dropped with it: the next call is update 13 again) or out of the
    drain before update 12's recovery file; it loads the newest file written
    BEFORE the tripping step, once the writer thread has written it, and the run
    ends as a sound one does."""
    import logging

    import train
    from timm_tpu.resilience import AsyncCheckpointWriter
    waited, writer_drain = [], AsyncCheckpointWriter.drain

    def drain(self, *args, **kwargs):       # where in the journal the writer was waited for
        waited.append(len(loop_journal))
        return writer_drain(self, *args, **kwargs)

    monkeypatch.setattr(AsyncCheckpointWriter, 'drain', drain)
    with caplog.at_level(logging.WARNING):
        train.main(_main_argv(tmp_path, '--epochs', '2', '--fault-inject', 'nan_grads@10:3', '--nonfinite-rollback',
                              '--recovery-interval', str(recovery_interval)))
    rolled = [r.getMessage() for r in caplog.records if 'rolled back to' in r.getMessage()]
    assert len(rolled) == 1 and 'at update 12:' in rolled[0]
    # update 11's recovery file, or one of epoch 0's three names for the same state
    assert any(n in rolled[0] for n in (('recovery-1-3.npz',) if recovery_interval
                                        else ('checkpoint-0.npz', 'model_best.npz', 'last.npz'))), rolled
    at = loop_journal.index(('tripped', 12, 3))
    assert at + 1 in waited     # the rollback waits for the writer: update 11's file may still be with it
    steps = [e[1] for e in loop_journal if e[0] == 'step']
    if recovery_interval:
        assert loop_journal[at - 2:at] == [('step', 12), ('drain',)] and loop_journal[at + 1] == ('recovery', 4)
        assert steps == list(range(16))
    else:
        assert loop_journal[at - 1] == ('step', 13) and loop_journal[at + 1] == ('step', 13)
        assert steps == list(range(14)) + [13, 14]                       # one batch went with the enqueued step
    between = loop_journal[loop_journal.index(('step', 12)):at]
    assert not [e for e in between if e[0] in ('recovery', 'checkpoint')]
    assert loop_journal[-1] == ('checkpoint', 1) and ('drain',) in loop_journal[at:]   # the epoch's last step is read before it
    assert 'checkpoint-1.npz' in os.listdir(tmp_path / 'lag')


def test_a_preemption_reads_the_newest_step_before_its_recovery_file_and_nothing_after(tmp_path, loop_journal):
    """SIGTERM after update 3: the drain comes before the recovery save, the
    epoch then ends by `TrainingPreempted` and no further read is made."""
    import train
    with pytest.raises(SystemExit) as ei:
        train.main(_main_argv(tmp_path, '--epochs', '1', '--fault-inject', 'sigterm@3'))
    assert ei.value.code == 0
    assert loop_journal[-3:] == [('step', 3), ('drain',), ('recovery', 3)]
    assert 'recovery-0-3.npz' in os.listdir(tmp_path / 'lag')


def test_the_sentinel_reads_every_step_it_is_handed_and_has_no_stride(monkeypatch):
    """`observe` takes the state array or the pair of counters out of a step's
    metrics, reads both every time (`check_every` and its environment variable
    are gone: a read of a finished step is no sync to avoid), names the step it is
    GIVEN, and `reset()` starts both counts again."""
    import inspect

    import jax.numpy as jnp

    from timm_tpu.resilience import NonFiniteSentinel
    from timm_tpu.utils import tracing
    monkeypatch.setenv('TIMM_TPU_NONFINITE_CHECK_EVERY', '4')
    assert list(inspect.signature(NonFiniteSentinel).parameters) == ['tolerance']
    sentinel = NonFiniteSentinel(2)
    polls = tracing.snapshot()['counters'].get('task.sentinel_polls', 0)
    assert sentinel.observe(jnp.asarray([0, 0], jnp.int32), step=5) is False
    assert sentinel.observe((jnp.asarray(1, jnp.int32), jnp.asarray(1, jnp.int32)), step=6) is True
    with pytest.raises(NonFiniteError) as ei:
        sentinel.observe([jnp.asarray(2, jnp.int32), jnp.asarray(2, jnp.int32)], step=7)
    assert (ei.value.consecutive, ei.value.total, ei.value.step) == (2, 2, 7) and 'at update 7' in str(ei.value)
    assert tracing.snapshot()['counters']['task.sentinel_polls'] - polls == 3
    sentinel.reset()
    assert (sentinel.consecutive, sentinel.total) == (0, 0)
    assert sentinel.observe(jnp.asarray([1, 1], jnp.int32), step=8) is True       # fresh device counters warn again
    for path in ('train.py', 'README.md', 'timm_tpu/resilience/sentinel.py', 'timm_tpu/resilience/__init__.py',
                 'timm_tpu/task/task.py'):
        assert 'CHECK_EVERY' not in open(os.path.join(REPO_ROOT, path)).read(), path


# -- retry / skip policy -----------------------------------------------------

def test_retry_io_backoff_then_success():
    sleeps = []
    calls = {'n': 0}

    def flaky():
        calls['n'] += 1
        if calls['n'] < 3:
            raise IOError('transient')
        return 'ok'

    assert retry_io(flaky, retries=3, base_delay=0.1, jitter=0.5,
                    sleep=sleeps.append) == 'ok'
    assert calls['n'] == 3 and len(sleeps) == 2
    # jittered exponential: each delay within ±50% of base*2^i, capped
    assert 0.05 <= sleeps[0] <= 0.15 and 0.1 <= sleeps[1] <= 0.3


def test_retry_io_exhaustion_and_poison_passthrough():
    with pytest.raises(IOError):
        retry_io(lambda: (_ for _ in ()).throw(IOError('down')),
                 retries=2, base_delay=0.0, sleep=lambda s: None)
    calls = {'n': 0}

    def poison():
        calls['n'] += 1
        raise ValueError('bad record')

    with pytest.raises(ValueError):
        retry_io(poison, retries=3, base_delay=0.0, sleep=lambda s: None)
    assert calls['n'] == 1  # non-transient: no retries


def test_backoff_delays_bounded():
    ds = list(backoff_delays(6, base_delay=0.1, max_delay=1.0, jitter=0.0))
    assert ds == [0.1, 0.2, 0.4, 0.8, 1.0, 1.0]


def test_skip_budget():
    b = SkipBudget(budget=2)
    b.record(ValueError('x'), 'a')
    b.record(ValueError('x'), 'b')
    with pytest.raises(TooManyBadSamples):
        b.record(ValueError('x'), 'c')


class _FlakyDataset:
    """Map-style dataset where some indices are poison (undecodable)."""

    def __init__(self, n=12, bad=()):
        self.n, self.bad = n, set(bad)

    def __len__(self):
        return self.n

    def __getitem__(self, idx):
        if idx in self.bad:
            raise ValueError(f'undecodable sample {idx}')
        return np.full((4, 4, 3), idx, np.float32), idx


def test_loader_skips_poison_within_budget(monkeypatch):
    from timm_tpu.data.loader import ThreadedLoader
    monkeypatch.setenv('TIMM_TPU_POISON_BUDGET', '4')
    loader = ThreadedLoader(_FlakyDataset(12, bad={3, 7}), batch_size=4,
                            is_training=False, num_workers=2)
    batches = list(loader)
    got = sorted(int(t) for _x, ts in batches for t in ts)
    assert got == [i for i in range(12) if i not in (3, 7)]  # order kept, poison dropped


def test_loader_budget_exhaustion_fails_loudly(monkeypatch):
    from timm_tpu.data.loader import ThreadedLoader
    monkeypatch.setenv('TIMM_TPU_POISON_BUDGET', '1')
    loader = ThreadedLoader(_FlakyDataset(12, bad={1, 2, 5}), batch_size=4,
                            is_training=False, num_workers=2)
    with pytest.raises(TooManyBadSamples):
        list(loader)


# -- fault injection ----------------------------------------------------------

def test_fault_injector_spec_parse():
    fi = FaultInjector('truncate_ckpt, nan_grads@4:2, sigterm@9, io_error%3')
    assert fi.take('truncate_ckpt') and not fi.take('truncate_ckpt')
    assert not fi.nan_at(3) and fi.nan_at(4) and fi.nan_at(5) and not fi.nan_at(6)
    assert fi.sigterm_at(9) and not fi.sigterm_at(9)
    assert [fi.io_error_tick() for _ in range(6)] == [False, False, True, False, False, True]
    assert not FaultInjector('')
    with pytest.raises(ValueError):
        FaultInjector('explode@3')


def test_fault_selftest_all_checks_pass(tmp_path):
    result = fault_selftest('truncate_ckpt,nan_grads@1,io_error%2',
                            tmp_dir=str(tmp_path))
    assert result['ok'], result


def test_fault_selftest_full_spec_smoke():
    """Every fault kind in one spec, the multi-update and resize forms among
    them, through the injection hooks in tier-1 without a slow run."""
    result = fault_selftest('truncate_ckpt,io_error%2,nan_grads@1:2,sigterm@3,resize@5:4')
    assert result['ok'], result


def test_resize_fault_spec():
    fi = FaultInjector('resize@4:2')
    assert fi.resize_devices == 2
    assert not fi.resize_at(3) and fi.resize_at(4) and not fi.resize_at(4)  # fires once
    with pytest.raises(ValueError, match='resize fault needs a device count'):
        FaultInjector('resize@4')  # the :D suffix is mandatory


# -- elastic rescale planning --------------------------------------------------

def test_rescale_holds_global_batch_constant():
    # 8->4 devices, global batch 256: keep the loader batch if it still shards
    assert rescale_for_devices(256, 4, prefer_batch_size=32) == (32, 8)
    # loader batch no longer divisible -> nearest shardable batch wins
    # (ties break toward the smaller batch: 8 and 16 are both 4 away from 12)
    assert rescale_for_devices(256, 8, prefer_batch_size=12) == (8, 32)
    assert rescale_for_devices(256, 8, prefer_batch_size=13) == (16, 16)
    # exact fit, no accum
    assert rescale_for_devices(64, 8, prefer_batch_size=64) == (64, 1)
    for g, n in ((256, 4), (96, 6), (512, 8)):
        bs, accum = rescale_for_devices(g, n)
        assert bs * accum == g and bs % n == 0


def test_rescale_refuses_with_nearest_legal_suggestion():
    # 100 is not a multiple of 8: no loader batch can shard evenly
    with pytest.raises(ValueError) as ei:
        rescale_for_devices(100, 8)
    msg = str(ei.value)
    assert 'Nearest legal global batch: 96 or 104' in msg
    assert 'multiples of the mesh batch-shard count 8' in msg
    # the accum cap shapes the solution: a tiny preferred batch is pushed up
    # to the smallest batch whose accum still fits the cap
    assert rescale_for_devices(1024, 2, prefer_batch_size=2, max_accum=4) == (256, 4)


def test_convert_loader_position():
    assert convert_loader_position(10, 32, 32) == (10, True)
    assert convert_loader_position(10, 32, 16) == (20, True)   # samples invariant
    assert convert_loader_position(5, 24, 16) == (7, False)    # 120 samples, inexact
    with pytest.raises(ValueError):
        convert_loader_position(1, 0, 16)


def test_plan_elastic_resume_from_checkpoint(tmp_path):
    # the dead run: 8 devices, batch 32 x accum 8 = global 256
    ckpt = str(tmp_path / 'recovery-0-3.npz')
    atomic_write_npz(ckpt, {
        'state_dict.w': np.zeros(4),
        '_resume.batch_size': np.asarray(32),
        '_resume.global_batch': np.asarray(256),
        '_resume.device_count': np.asarray(8),
    }, meta={'epoch': 0})
    # restart on 4 devices with the same flags: global batch held at 256
    plan = plan_elastic_resume(devices=4, batch_size=32, grad_accum=8,
                               fsdp=8, resume=ckpt)
    assert plan.global_batch == 256 and plan.batch_size * plan.grad_accum == 256
    assert plan.batch_size % 4 == 0
    assert plan.fsdp == 4  # clamped to what divides the live topology
    assert plan.source == ckpt
    assert any('clamped' in n for n in plan.notes)
    # fresh start (no resume): plan only validates the fresh configuration
    fresh = plan_elastic_resume(devices=4, batch_size=32, grad_accum=1)
    assert (fresh.batch_size, fresh.grad_accum, fresh.source) == (32, 1, '')


def test_resolve_elastic_axes_clamps_to_divisors():
    from timm_tpu.parallel import create_mesh, resolve_elastic_axes
    assert resolve_elastic_axes(8, fsdp=4) == (4, None)
    assert resolve_elastic_axes(4, fsdp=8) == (4, None)     # clamp down
    assert resolve_elastic_axes(6, fsdp=4) == (3, None)     # largest divisor <= 4
    assert resolve_elastic_axes(8, fsdp=4, tp=4) == (2, 4)  # tp wins the factor
    assert resolve_elastic_axes(5, fsdp=4, tp=2) == (None, None)  # prime: no axes
    # the contract: create_mesh always accepts the clamped result
    import jax
    devs = jax.devices()
    for n in (1, 2, 4, 8):
        fsdp, tp = resolve_elastic_axes(n, fsdp=4, tp=2)
        create_mesh(devices=devs[:n], fsdp=fsdp, tp=tp)


# -- async checkpoint writer ---------------------------------------------------

def test_async_writer_supersede_and_ordering():
    w = AsyncCheckpointWriter()
    started, release = threading.Event(), threading.Event()
    ran = []

    def blocker():
        started.set()
        release.wait(10)
        ran.append('first')

    try:
        w.submit(blocker, label='first', key='recovery')
        assert started.wait(10)
        w.submit(lambda: ran.append('stale'), label='stale', key='recovery')
        w.submit(lambda: ran.append('ckpt'), label='ckpt', key='checkpoint')
        w.submit(lambda: ran.append('newest'), label='newest', key='recovery')
        assert w.superseded == 1  # 'stale' replaced before it ever ran
        release.set()
        w.drain()
    finally:
        release.set()
        w.close()
    # supersede re-queues at the tail; distinct keys keep submission order
    assert ran == ['first', 'ckpt', 'newest']


def test_async_writer_drain_ordering_and_error_propagation():
    w = AsyncCheckpointWriter()
    ran = []
    for i in range(3):
        w.submit(lambda i=i: ran.append(i), label=f'op-{i}', key=f'k{i}')
    w.drain()
    assert ran == [0, 1, 2]
    # a persistent (non-transient) failure re-raises on the caller thread
    w.submit(lambda: (_ for _ in ()).throw(ValueError('disk gone')), key='bad')
    with pytest.raises(ValueError, match='disk gone'):
        w.drain()
    w.close()
    with pytest.raises(RuntimeError, match='closed'):
        w.submit(lambda: None)


def test_async_writer_retries_transient_io_error():
    """io_error%M must exercise the ASYNC durable path: the injected OSError
    fires inside the retried closure and the backoff rides through it."""
    set_fault_injector('io_error%2')
    try:
        w = AsyncCheckpointWriter(base_delay=0.0)
        ran = []
        for i in range(4):  # every 2nd closure attempt hits the injected fault
            w.submit(lambda i=i: ran.append(i), label=f'op-{i}', key=f'k{i}')
        w.close()
        assert ran == [0, 1, 2, 3]
    finally:
        set_fault_injector('')


def test_async_save_keeps_durable_writes_off_step_thread(tmp_path, mesh8):
    """The instrumentation hook the acceptance criteria name: every durable
    write of an async save runs on the writer thread, never the step thread —
    and the npz bytes + SHA-256 manifest are byte-identical to a sync save."""
    import jax.numpy as jnp
    from timm_tpu.resilience.durable import read_manifest

    state = {'state_dict.w': jnp.arange(64.0).reshape(8, 8),
             'epoch': np.asarray(0)}
    sync_path = str(tmp_path / 'sync.npz')
    async_path = str(tmp_path / 'async.npz')
    atomic_write_npz(sync_path, state, meta={'epoch': 0})

    writes = []
    prev = set_durable_write_listener(lambda path, thread: writes.append((path, thread.name)))
    try:
        w = AsyncCheckpointWriter()
        host = snapshot_to_host(state)  # step-thread half: gather only, no I/O
        w.submit(lambda: atomic_write_npz(async_path, host, meta={'epoch': 0}),
                 key='ckpt')
        w.close()
    finally:
        set_durable_write_listener(prev)
    assert writes and all(t == AsyncCheckpointWriter.THREAD_NAME for _p, t in writes), writes

    msync, masync = read_manifest(sync_path), read_manifest(async_path)
    assert {k: v['sha256'] for k, v in msync['arrays'].items()} == \
           {k: v['sha256'] for k, v in masync['arrays'].items()}
    assert open(sync_path, 'rb').read() == open(async_path, 'rb').read()


def test_saver_async_matches_sync_save(tmp_path, mesh8):
    """CheckpointSaver in async mode: save_recovery/save_checkpoint produce
    byte-identical npz + manifests to sync mode, all durable writes stay on
    the writer thread, and no staging litter survives."""
    import jax.numpy as jnp
    from timm_tpu.utils import CheckpointSaver

    class _Task:
        def get_checkpoint_state(self):
            return {'state_dict.w': jnp.full((4, 4), 7.0),
                    'optimizer.m': jnp.zeros(4)}

    def run(d, writer):
        saver = CheckpointSaver(task=_Task(), checkpoint_dir=d, recovery_dir=d,
                                async_writer=writer)
        saver.save_recovery(0, 3, extra_state={'_resume.num_updates': np.asarray(3)})
        saver.save_checkpoint(0, metric=1.0)
        if writer is not None:
            writer.close()
        return saver

    d_sync, d_async = str(tmp_path / 'sync'), str(tmp_path / 'async')
    os.makedirs(d_sync), os.makedirs(d_async)
    run(d_sync, None)
    writes = []
    prev = set_durable_write_listener(lambda path, thread: writes.append(thread.name))
    try:
        run(d_async, AsyncCheckpointWriter())
    finally:
        set_durable_write_listener(prev)
    assert writes and set(writes) == {AsyncCheckpointWriter.THREAD_NAME}

    sync_names = sorted(os.listdir(d_sync))
    assert sorted(os.listdir(d_async)) == sync_names  # incl. NO .async-stage-* dir
    for name in sync_names:
        a, b = os.path.join(d_sync, name), os.path.join(d_async, name)
        if name.endswith('.npz'):
            assert open(a, 'rb').read() == open(b, 'rb').read(), name


def test_saver_sweeps_orphaned_async_staging_dir(tmp_path):
    """Regression: a writer killed mid-write leaves `.async-stage-<pid>/` with
    temp litter; the next process's startup sweep must reap it wholesale."""
    from timm_tpu.utils import CheckpointSaver
    d = str(tmp_path)
    stage = os.path.join(d, '.async-stage-99999')  # "killed" writer's pid
    os.makedirs(stage)
    open(os.path.join(stage, '.last.npz.123.tmp'), 'wb').write(b'partial')
    atomic_write_npz(os.path.join(d, 'last.npz'), {'w': np.ones(4)}, meta={'epoch': 0})
    CheckpointSaver(task=None, checkpoint_dir=d, recovery_dir=d)
    assert not os.path.exists(stage)
    ok, reason = verify_checkpoint(os.path.join(d, 'last.npz'))
    assert ok, reason  # the sweep never touches committed checkpoints


def test_saver_async_staging_dir_killed_writer_subprocess(tmp_path):
    """End-to-end injected kill: a child process starts an async save and is
    SIGKILLed while the writer holds the temp file open; the parent's startup
    sweep reaps the orphaned staging dir."""
    import signal
    child = f'''
import os, sys, threading, numpy as np
sys.path.insert(0, {repr(REPO_ROOT)})
import jax; jax.config.update('jax_platforms', 'cpu')
from timm_tpu.resilience import AsyncCheckpointWriter
from timm_tpu.utils import CheckpointSaver

class T:
    def get_checkpoint_state(self):
        return {{'state_dict.w': np.zeros((256, 256), np.float32)}}

d = {repr(str(tmp_path))}
hold = threading.Event()
w = AsyncCheckpointWriter()
saver = CheckpointSaver(task=T(), checkpoint_dir=d, recovery_dir=d, async_writer=w)
# wedge the writer AFTER the staging dir exists so the kill lands mid-flight
w.submit(lambda: hold.wait(30), key='wedge')
saver.save_recovery(0, 1, extra_state={{'_resume.num_updates': np.asarray(1)}})
open(os.path.join(d, 'ready'), 'w').write('1')
hold.clear()
import time; time.sleep(30)
'''
    proc = subprocess.Popen([sys.executable, '-c', child],
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    try:
        for _ in range(600):
            if os.path.exists(tmp_path / 'ready'):
                break
            import time
            time.sleep(0.05)
        else:
            raise AssertionError(proc.stderr.read().decode()[-2000:])
        proc.send_signal(signal.SIGKILL)
    finally:
        proc.wait(timeout=30)
    stages = [n for n in os.listdir(tmp_path) if n.startswith('.async-stage-')]
    assert stages  # the kill really orphaned a staging dir
    from timm_tpu.utils import CheckpointSaver
    CheckpointSaver(task=None, checkpoint_dir=str(tmp_path), recovery_dir=str(tmp_path))
    assert not [n for n in os.listdir(tmp_path) if n.startswith('.async-stage-')]


# -- graceful shutdown install/uninstall ---------------------------------------

def test_graceful_shutdown_install_idempotent_and_finally_safe():
    import signal
    before = {s: signal.getsignal(s) for s in (signal.SIGTERM, signal.SIGINT)}
    sd = GracefulShutdown()
    assert sd.install() is sd
    assert sd.install() is sd  # second install: no-op, does NOT record itself
    try:
        assert signal.getsignal(signal.SIGTERM) is not before[signal.SIGTERM]
    finally:
        sd.uninstall()
    for s, h in before.items():
        assert signal.getsignal(s) is h, f'handler for {s} not restored'
    sd.uninstall()  # idempotent: already uninstalled is a no-op


# -- host RNG capture ---------------------------------------------------------

def test_host_rng_capture_restore_bit_identical():
    np.random.seed(123)
    import random as pyrandom
    pyrandom.seed(321)
    np.random.rand(7)  # advance the streams off the seed point
    pyrandom.random()
    snap = capture_host_rng()
    expect_np = np.random.rand(16)
    expect_py = [pyrandom.random() for _ in range(4)]
    np.random.rand(99)  # diverge
    pyrandom.random()
    assert restore_host_rng(snap)
    np.testing.assert_array_equal(np.random.rand(16), expect_np)
    assert [pyrandom.random() for _ in range(4)] == expect_py


def test_load_state_dict_rejects_corrupt_npz(tmp_path):
    from timm_tpu.models import load_checkpoint
    import timm_tpu
    path = str(tmp_path / 'weights.npz')
    atomic_write_npz(path, {'w': np.ones(4)})
    with open(path, 'r+b') as f:
        f.truncate(16)
    model = timm_tpu.create_model('test_vit', num_classes=5)
    with pytest.raises(CorruptCheckpointError):
        load_checkpoint(model, path)


# -- end-to-end CPU drills (subprocess train.py) ------------------------------

def _train_cmd(out_dir, experiment, *extra):
    return [
        sys.executable, os.path.join(REPO_ROOT, 'train.py'),
        '--synthetic-data', '--model', 'test_vit', '--img-size', '32', '-b', '8',
        '--synthetic-len', '64', '--epochs', '1', '--opt', 'sgd', '--lr', '0.05',
        '--sched', 'cosine', '--warmup-epochs', '0', '--workers', '1',
        '--log-interval', '50', '--output', str(out_dir), '--experiment', experiment,
        *extra,
    ]


def _run(cmd):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    return subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=REPO_ROOT, timeout=240)


def _params(path):
    with np.load(path, allow_pickle=False) as d:
        return {k: d[k] for k in d.files if k.startswith(('state_dict.', 'optimizer.'))}


def test_sigterm_resume_parity(tmp_path):
    """Acceptance drill (b): a run killed by SIGTERM mid-epoch and restarted
    with `--resume auto` ends bit-identical to an uninterrupted run."""
    r = _run(_train_cmd(tmp_path, 'base'))
    assert r.returncode == 0, r.stderr[-2000:]
    # interrupted run: injected SIGTERM after update 3 → recovery + exit 0
    r = _run(_train_cmd(tmp_path, 'pre', '--fault-inject', 'sigterm@3'))
    assert r.returncode == 0, r.stderr[-2000:]
    assert 'recovery-0-3.npz' in os.listdir(tmp_path / 'pre'), r.stderr[-2000:]
    r = _run(_train_cmd(tmp_path, 'pre', '--resume', 'auto'))
    assert r.returncode == 0, r.stderr[-2000:]
    assert 'Resumed mid-epoch' in r.stderr

    base = _params(tmp_path / 'base' / 'last.npz')
    resumed = _params(tmp_path / 'pre' / 'last.npz')
    assert set(base) == set(resumed)
    mismatched = [k for k in base if not np.array_equal(base[k], resumed[k])]
    assert not mismatched, f'{len(mismatched)} tensors differ after resume: {mismatched[:5]}'
    # end-of-epoch checkpoint supersedes the mid-epoch recovery file
    assert not [n for n in os.listdir(tmp_path / 'pre') if n.startswith('recovery-')]


def test_nan_abort_exit_code_and_intact_checkpoint(tmp_path):
    """Acceptance drill (c): K consecutive injected NaN steps abort with a
    non-zero exit while the committed checkpoints stay valid."""
    r = _run(_train_cmd(tmp_path, 'nanabort',
                        '--fault-inject', 'nan_grads@2:3', '--nonfinite-tolerance', '3'))
    assert r.returncode == 3, (r.returncode, r.stderr[-2000:])
    assert 'consecutive non-finite' in r.stderr
    # no checkpoint was committed this epoch — but nothing half-written either
    litter = [n for n in os.listdir(tmp_path / 'nanabort') if n.endswith('.tmp')]
    assert not litter
    for name in os.listdir(tmp_path / 'nanabort'):
        if name.endswith('.npz'):
            ok, reason = verify_checkpoint(str(tmp_path / 'nanabort' / name))
            assert ok, (name, reason)
