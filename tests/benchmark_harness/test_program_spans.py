"""The program's spans as the benchmark reads them (`harness/program_spans.py`,
the 18 readers beside it, `tools/span_report.py`), at `test_vit` size on the
CPU. One file, like `test_harness.py`, whose toy cell it borrows.
"""
import importlib.util
import os
import statistics
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import program_spans as ps  # noqa: E402
from benchmarks.harness.manifest import BENCH_DIR, Manifest  # noqa: E402

_spec = importlib.util.spec_from_file_location('bench_test_harness', os.path.join(HERE, 'test_harness.py'))
harness_tests = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(harness_tests)
toy = harness_tests.toy   # the fixture: a configuration, a cell and a metric added by files alone

RING = ['step_state_split_ms.train', 'step_scalars_put_ms.train', 'step_call_ms.train', 'step_state_update_ms.train',
        'step_sentinel_poll_ms.train', 'step_host_cpu_share.train', 'input_batch_wait_ms.train',
        'input_prepare_ms.train', 'input_decode_busy_share.train', 'loop_bookkeeping_ms.train',
        'setup_model_build_s', 'setup_data_build_s', 'setup_step_program_s', 'setup_compile_s']
TRACE = ['device_idle_in_step_ms.train', 'device_idle_in_input_ms.train', 'device_idle_in_loop_ms.train',
         'device_idle_attributed_share.train']
CHILDREN = ('task.state_split', 'task.scalars_put', 'task.step_call', 'task.state_update', 'task.sentinel_poll')
# What a step of `task.train_step` may spend under none of its five children. Between five spans in a row lie six
# gaps of a span's exit, a line or two of Python and the next span's entry: 70-85 us a step here on an idle machine,
# 70-145 us beside five other workers (medians of 42 toy runs; a span costs 14 us on the chip machine, PERF.md
# section 6, PR 24). 0.3 ms is twice the worst of those. A share of the call alone cannot be the bound: the toy's
# call is 25-30 ms here because the poll waits 20 ms for the step, and with nothing in it waiting a call can be a
# millisecond (PR 25's lagged poll: ROADMAP S2), 5 % of which is less than the clock reads. The smallest child this
# still misses is one shorter than the bound (`task.state_split`, 0.02 ms here).
UNCOVERED_MS = 0.3


def test_the_manifest_names_exactly_these_readers_beside_the_eight_it_had():
    m = Manifest()
    assert [x['name'] for x in m.data['per_layer']][8:26] == RING[:10] + TRACE + RING[10:]   # later entries are appended
    for name in RING + TRACE:
        entry = m.per_layer[name]
        assert callable(m.reader(name))   # LAYER, UNIT, MOVES of the file agree with the entry
        assert entry['source'] == ('device_trace' if name in TRACE else 'program_counter')
        assert ('workloads' in entry) == (entry['moves'] == 'train_img_per_s')


@pytest.fixture(scope='module')
def sound(toy):
    return harness_tests._run(toy, 0.4)[0]


@pytest.fixture(scope='module')
def replaced(toy):
    from timm_tpu.task import ClassificationTask
    return harness_tests._run(toy, 0.2, inner_step=harness_tests._stuck(ClassificationTask.train_step))[0]


@pytest.mark.parametrize('name', RING)
def test_train_main_fills_the_ring_and_the_reader_reads_a_number(toy, sound, name):
    value = toy[0].reader(name)(sound)
    assert isinstance(value, float) and value >= 0.0
    if name.endswith('share.train'):
        assert 0.0 < value <= 100.0 * (os.cpu_count() if 'cpu' in name else 1)
    if name == 'setup_step_program_s':
        assert value > toy[0].reader('step_call_ms.train')(sound) / 1e3    # the first call traces and compiles


def _uncovered_ms(w, children):
    """(ms of a `task.train_step` call under none of `children`, the most it may be): both by the MEDIAN step of
    the window. Work that no child holds is there in every step; a worker taken off its core between two spans'
    clocks (2-7 ms, one step in forty under `-n 6`) is the machine's, and a sum over the window would carry it."""
    whole, parts = ps.per_step(w, 'task.train_step'), ps.per_step(w, *children)
    assert all(p <= a for p, a in zip(parts, whole))                 # children lie inside their parent, every step
    return statistics.median(a - p for a, p in zip(whole, parts)), max(UNCOVERED_MS, 0.05 * statistics.median(whole))


def test_the_window_is_the_wrappers_and_the_children_cover_the_call(sound):
    w = ps.window(sound)
    assert len(w['roots']) == sound['steps'] and [r.step for r in w['roots']] == sorted(r.step for r in w['roots'])
    assert w['roots'][-1].failed and not w['roots'][0].failed       # the window closes by raising through the last root
    uncovered, bound = _uncovered_ms(w, CHILDREN)
    assert 0.0 <= uncovered <= bound, (uncovered, bound)
    # the wrapper's two clocks sit right around the program's own span
    whole = ps.per_step(w, 'task.train_step')
    outside = [d * 1e3 for d in sound['spans']['train_step_dispatch_s']]
    # (every step, as before PR 35, but for ONE step of the window: a worker taken off its core between the wrapper's
    # clock and the span's loses 2-7 ms once in forty steps under `-n 6`, and a window here has a dozen)
    gaps = [o - i for o, i in zip(outside, whole)]
    assert all(g >= 0.0 for g in gaps) and sum(g >= 5.0 for g in gaps) <= 1, gaps
    # set-up is what ended before the window's first root, compilations and the first step among it
    names = [s.name for s in ps.setup(w)]
    assert {'setup.model_build', 'setup.task_build', 'setup.data_build', 'xla.backend_compile', 'task.step_call'} <= set(names)
    assert max(s.end_ns for s in ps.setup(w)) <= w['roots'][0].start_ns


def test_a_call_with_a_child_span_removed_is_not_covered(sound):
    """What the cover is there to catch, work inside `task.train_step` that no child span holds: the same window
    read without its longest child (the poll while it waits for the step, the dispatch once it does not; of five
    children that cover the call, at least a fifth of it) is over the bound."""
    w = ps.window(sound)
    longest = max(CHILDREN, key=lambda c: statistics.median(ps.per_step(w, c)))
    assert longest in ('task.sentinel_poll', 'task.step_call')
    uncovered, bound = _uncovered_ms(w, [c for c in CHILDREN if c != longest])
    assert uncovered > bound, (longest, uncovered, bound)


def test_the_traced_line_carries_the_ring_metrics_and_leaves_the_trace_ones_out(toy, sound):
    from benchmarks import run as bench_run
    traced = dict(sound, device_kind='TPU v5 lite', trace={
        'busy_s': 0.5, 'window_s': 1.0, 'idle_share': 0.5, 'work': 5, 'idle_total_s': 0.5,
        'breakdown': {'device_ops': [], 'idle_gaps': []}})
    line = bench_run.result_line(toy[0], 'toy_vit_train', traced, {'platform': 'cpu', 'kind': 'cpu', 'count': 1}, trace=True)
    assert set(RING) <= set(line['metrics']) and not set(TRACE) & set(line['metrics'])   # no trace file of that cell
    plain = bench_run.result_line(toy[0], 'toy_vit_train', sound, {'platform': 'cpu', 'kind': 'cpu', 'count': 1}, trace=False)
    assert set(plain['metrics']) == {'train_img_per_s', 'setup_s'}


def test_span_report_prints_the_split_and_the_checks(sound):
    spec = importlib.util.spec_from_file_location('span_report', os.path.join(BENCH_DIR, 'tools', 'span_report.py'))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    lines = []
    tool.report(sound, out=lines.append)
    text = '\n'.join(lines)
    assert 'children cover task.train_step' in text and 'task.sentinel_poll' in text and 'spans a step' in text
    assert 'compilations by the span that asked for them' in text
    assert 0 < tool.span_cost_ns(2000) < 1e6


# the readers take the ring's LAST window: from here on that is the replaced run's, so what reads `sound` stays above

@pytest.mark.parametrize('name', RING + TRACE)
def test_a_reader_returns_none_where_the_inner_step_was_replaced(toy, replaced, name):
    assert toy[0].reader(name)(replaced) is None
    assert toy[0].reader(name)({}) is None


# -- the same spans in a profiler trace ------------------------------------------------------

def test_a_profiler_session_holds_the_programs_spans_nested_on_the_traces_clock(tmp_path):
    """Two real `task.train_step` calls under a CPU profiler session started the
    way the harness starts it: the program's span names are in the host plane,
    each child inside its parent."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import timm_tpu
    from benchmarks.harness import trace
    from timm_tpu.optim import create_optimizer_v2
    from timm_tpu.task import ClassificationTask
    from timm_tpu.utils import tracing

    model = timm_tpu.create_model('test_vit', num_classes=10, seed=0)
    task = ClassificationTask(model, optimizer=create_optimizer_v2(model, opt='adamw', lr=1e-3))
    rng = np.random.default_rng(0)
    batch = {'input': jnp.asarray(rng.standard_normal((2, 160, 160, 3)), jnp.float32),
             'target': jnp.asarray(rng.integers(0, 10, 2))}
    jax.block_until_ready(task.train_step(batch, lr=1e-3, step=0))     # compiled outside the session
    mark = tracing.now_ns()
    trace.start(str(tmp_path))
    try:
        for step in (1, 2):
            with tracing.span('train.step', step=step):
                metrics = task.train_step(batch, lr=1e-3, step=step)
        jax.block_until_ready(metrics)
    finally:
        trace.stop()
    path = trace.newest_xplane(str(tmp_path))
    gaps, spans = ps._trace_of(path, os.path.getmtime(path))
    assert gaps == []                                                   # no TPU plane in a CPU trace
    want = {'train.step', 'task.train_step', 'task.state_split', 'task.scalars_put', 'task.step_call',
            'task.state_update', 'task.sentinel_poll'}
    assert want <= set(spans) and all(len(spans[n]) == 2 for n in want)
    for n in want - {'train.step', 'task.train_step'}:
        assert all(p0 <= c0 <= c1 <= p1 for (c0, c1), (p0, p1) in zip(sorted(spans[n]), sorted(spans['task.train_step'])))
    assert all(p0 <= c0 <= c1 <= p1 for (c0, c1), (p0, p1) in zip(sorted(spans['task.train_step']), sorted(spans['train.step'])))
    # the ring and the trace time the same two calls alike
    ring = [ps.wall_ms(s) for s in tracing.snapshot()['spans'] if s.name == 'task.train_step' and s.start_ns >= mark]
    traced = [(e - s) / 1e6 for s, e in sorted(spans['task.train_step'])]
    assert len(ring) == 2 and sum(traced) == pytest.approx(sum(ring), rel=0.05)


def test_idle_gaps_are_laid_over_the_spans_they_fall_under():
    ms = 1_000_000
    spans = {'train.step': [(0, 100 * ms), (100 * ms, 200 * ms)],
             'train.loader_next': [(0, 10 * ms), (100 * ms, 110 * ms)],
             'train.batch_to_device': [(10 * ms, 12 * ms)],
             'task.train_step': [(12 * ms, 90 * ms), (112 * ms, 190 * ms)],
             'train.bookkeeping': [(90 * ms, 95 * ms), (190 * ms, 230 * ms)]}   # the last root never closed in the trace
    gaps = [(5 * ms, 20 * ms),        # straddles input (5 of loader_next, 2 of placing) and step (8)
            (80 * ms, 105 * ms),      # step 10, loop 10 (5 bookkeeping + 5 bare root), input 5
            (195 * ms, 240 * ms),     # loop 5 under the root + 30 under the bookkeeping span alone, 10 under nothing
            (300 * ms, 310 * ms)]     # under no span at all
    got = ps.attribute(gaps, spans)
    assert got == {'step': 18 * ms, 'input': 12 * ms, 'loop': 45 * ms, 'outside': 20 * ms}
    assert sum(got.values()) == sum(e - s for s, e in gaps)
    assert ps.attribute(gaps, {}) == {'step': 0, 'input': 0, 'loop': 0, 'outside': 95 * ms}
    assert ps.attribute([], spans) == {'step': 0, 'input': 0, 'loop': 0, 'outside': 0}
    assert ps.overlap((5, 20), [[0, 10], [15, 30]]) == 10
