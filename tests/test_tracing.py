"""`timm_tpu/utils/tracing.py`: the program's one tracing mechanism.

The ring is process-wide and other tests of the same worker write to it, so
each case reads only what it recorded itself (spans that started after its own
mark, names nothing else uses at that moment).
"""
import inspect
import os
import re
import sys
import threading

import pytest

from timm_tpu.utils import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _since(mark_ns, name=None):
    return [s for s in tracing.snapshot()['spans'] if s.start_ns >= mark_ns and (name is None or s.name == name)]


def test_nesting_and_parent_links_across_two_threads():
    mark = tracing.now_ns()
    seen = {}

    def worker():
        with tracing.span('loader.h2d') as outer:
            with tracing.span('loader.sample_params') as inner:
                seen['thread'] = (outer.id, inner.id)

    with tracing.span('train.step', step=7) as root:
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        with tracing.span('task.train_step') as call:
            with tracing.span('task.step_call') as leaf:
                pass
    by_id = {s.id: s for s in _since(mark)}
    assert by_id[leaf.id].parent == call.id and by_id[call.id].parent == root.id and by_id[root.id].parent == 0
    outer_id, inner_id = seen['thread']
    # the other thread has its own stack: its outer span has no parent and no step, whatever the main thread has open
    assert by_id[outer_id].parent == 0 and by_id[inner_id].parent == outer_id
    assert by_id[outer_id].thread == by_id[inner_id].thread != by_id[root.id].thread
    assert by_id[outer_id].step is None and by_id[leaf.id].step == 7
    # a child lies inside its parent on both clocks
    for child, parent in ((leaf, call), (call, root)):
        c, p = by_id[child.id], by_id[parent.id]
        assert p.start_ns <= c.start_ns <= c.end_ns <= p.end_ns and p.cpu_start_ns <= c.cpu_start_ns <= c.cpu_end_ns <= p.cpu_end_ns


def test_a_step_root_hands_its_step_down_and_marks_the_counters():
    mark = tracing.now_ns()
    for step in (41, 42):
        with tracing.span('train.step', step=step):
            tracing.count('loader.batches', 3)
            with tracing.span('train.loader_next'):
                with tracing.span('loader.batch_wait'):
                    pass
    with tracing.span('train.bookkeeping'):   # outside any root: no step
        pass
    assert [s.step for s in _since(mark, 'loader.batch_wait')] == [41, 42]
    assert [s.step for s in _since(mark, 'train.bookkeeping')] == [None]
    marks = [c for t, c in tracing.snapshot()['marks'] if t >= mark]
    assert len(marks) == 2 and marks[1]['loader.batches'] - marks[0]['loader.batches'] == 3


def test_a_span_is_recorded_when_its_block_raises():
    mark = tracing.now_ns()

    class Closed(Exception):
        pass

    with pytest.raises(Closed):
        with tracing.span('train.step', step=1):
            with tracing.span('task.train_step'):
                raise Closed()
    got = {s.name: s for s in _since(mark)}
    assert got['task.train_step'].failed and got['train.step'].failed
    assert got['task.train_step'].parent == got['train.step'].id
    with tracing.span('task.scalars_put') as after:   # the stack was unwound: nothing is left open
        pass
    assert _since(mark, 'task.scalars_put')[0].parent == 0 and not _since(mark, 'task.scalars_put')[0].failed
    assert after.step is None


def test_the_ring_is_bounded_and_large_enough():
    assert tracing.RING >= 16384
    for _ in range(tracing.RING + 100):
        with tracing.span('train.log_sync'):
            pass
    spans = tracing.snapshot()['spans']
    assert len(spans) == tracing.RING and spans[-1].id - spans[0].id >= tracing.RING - 1


@pytest.mark.parametrize('record', [lambda n: tracing.span(n), lambda n: tracing.count(n), lambda n: tracing.busy(n),
                                    lambda n: tracing.gauge(n, 1)], ids=['span', 'count', 'busy', 'gauge'])
def test_a_name_that_is_not_declared_is_refused(record):
    with pytest.raises(KeyError, match='not declared'):
        record('task.made_up')


def test_every_compilation_is_a_span_under_whatever_was_open():
    import jax
    import jax.numpy as jnp
    mark = tracing.now_ns()
    x, k = jnp.ones(3), mark % 977   # a constant no cached program holds
    with tracing.span('train.step', step=5):
        with tracing.span('task.step_call') as call:
            jax.jit(lambda x: x * 3 + k)(x).block_until_ready()
    built = _since(0, 'xla.backend_compile')
    mine = [s for s in built if s.parent == call.id]
    assert len(mine) == 1 and mine[0].step == 5 and mine[0].end_ns > mine[0].start_ns >= mark
    jax.jit(lambda x: x * 5 + k)(x).block_until_ready()
    outside = [s for s in _since(0, 'xla.backend_compile') if s.id > mine[0].id]
    assert outside and outside[-1].parent == 0 and outside[-1].step is None


def test_summary_gives_medians_and_sums_per_name():
    S = tracing.Span
    ms = 1_000_000
    spans = [S(1, 0, 'task.step_call', 1, 0, 0, 10 * ms, 0, 1 * ms, False),
             S(2, 0, 'task.step_call', 1, 1, 20 * ms, 50 * ms, 0, 3 * ms, False),
             S(3, 0, 'task.step_call', 1, 2, 60 * ms, 80 * ms, 0, 8 * ms, False),
             S(4, 0, 'task.state_split', 1, 2, 90 * ms, 94 * ms, 0, 4 * ms, False)]
    got = tracing.summary(spans=spans)
    assert got['task.step_call'] == {'n': 3, 'wall_ms_median': 20.0, 'wall_ms_sum': 60.0,
                                     'cpu_ms_median': 3.0, 'cpu_ms_sum': 12.0}
    assert tracing.summary(since_ns=20 * ms, spans=spans)['task.step_call']['n'] == 2
    assert set(tracing.summary(since_ns=85 * ms, spans=spans)) == {'task.state_split'}
    mark = tracing.now_ns()
    with tracing.span('task.state_update'):
        pass
    assert tracing.summary(mark)['task.state_update']['n'] == 1


def test_counters_lose_no_update_under_contending_threads():
    """More threads than cores, a short switch interval: a read-modify-write
    without the lock would lose adds."""
    threads, adds = 4 * (os.cpu_count() or 4), 2000
    before = tracing.snapshot()['counters'].get('loader.samples', 0)
    busy_before = tracing.snapshot()['counters'].get('loader.decode_busy_ns', 0)

    def worker():
        for _ in range(adds):
            with tracing.busy('loader.decode_busy_ns'):
                tracing.count('loader.samples')

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        pool = [threading.Thread(target=worker) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in pool)
    after = tracing.snapshot()['counters']
    assert after['loader.samples'] - before == threads * adds
    assert after['loader.decode_busy_ns'] > busy_before


def test_a_gauge_keeps_a_bounded_series_on_the_rings_clock():
    mark = tracing.now_ns()
    for depth in (2, 0, 1):
        tracing.gauge('loader.batch_q_depth', depth)
    series = [(t, v) for t, v in tracing.snapshot()['gauges']['loader.batch_q_depth'] if t >= mark]
    assert [v for _, v in series] == [2, 0, 1] and series[0][0] <= series[-1][0] <= tracing.now_ns()
    for _ in range(tracing.SERIES + 10):
        tracing.gauge('loader.batch_q_depth', 1)
    assert len(tracing.snapshot()['gauges']['loader.batch_q_depth']) == tracing.SERIES


# -- the lint: names are the contract ------------------------------------------------------

CALL = re.compile(r"tracing\.(?:span|count|busy|gauge|scope|device_counter)\(\s*(['\"])([^'\"]+)\1")


def _program_sources():
    paths = [os.path.join(ROOT, 'train.py')]
    for folder, _, files in os.walk(os.path.join(ROOT, 'timm_tpu')):
        paths += [os.path.join(folder, f) for f in files if f.endswith('.py')]
    return {p: open(p).read() for p in paths}


def test_every_recorded_name_is_declared_and_every_declared_name_is_recorded_and_read():
    import train
    sources = _program_sources()
    sites = {}
    for path, text in sources.items():
        for _, name in CALL.findall(text):
            sites.setdefault(name, []).append(os.path.relpath(path, ROOT))
    sites.setdefault('xla.backend_compile', []).append('timm_tpu/utils/tracing.py')   # the listener's own record
    assert "'xla.backend_compile'" in sources[os.path.join(ROOT, 'timm_tpu', 'utils', 'tracing.py')]
    assert set(sites) == set(tracing.SPANS), (set(sites) ^ set(tracing.SPANS))
    # nothing records under a computed name, and only tracing.py knows TraceAnnotation
    for path, text in sources.items():
        if not path.endswith(os.path.join('utils', 'tracing.py')):
            assert 'TraceAnnotation' not in text, path
            for call in re.findall(r'tracing\.(?:span|count|busy|gauge|scope|device_counter)\(([^)]*)', text):
                assert call.lstrip()[:1] in ('\'', '"'), (path, call)
    # each name has a reader: a per-layer metric, the reduction behind them, or train.py's two log lines
    bench = os.path.join(ROOT, 'benchmarks')
    readers = [open(os.path.join(bench, 'layer_metrics', f)).read() for f in os.listdir(os.path.join(bench, 'layer_metrics'))]
    readers += [open(os.path.join(bench, 'harness', 'program_spans.py')).read(),
                open(os.path.join(bench, 'harness', 'device_scopes.py')).read(),     # device time by scope
                open(os.path.join(bench, 'harness', 'lm_readers.py')).read(),        # the LM cell's readings
                open(os.path.join(bench, 'harness', 'lm_train_runner.py')).read(),   # its `correct`: `moe.dropped_slots`
                open(os.path.join(bench, 'harness', 'swa_lm_readers.py')).read(),    # the window/full cell's readings: `swa.attn.*`, `attn.*`
                open(os.path.join(bench, 'harness', 'swa_lm_train_runner.py')).read(),
                open(os.path.join(bench, 'harness', 'bd_lm_readers.py')).read(),     # the block-diffusion cell's: `swa.attn.core_bd`, `attn.bd_blocks`
                open(os.path.join(bench, 'harness', 'bd_lm_train_runner.py')).read(),  # its `correct`: `lm.noised_masked`, `lm.masked_nll`
                open(os.path.join(bench, 'harness', 'cla_lm_readers.py')).read(),    # the chunk-pooled cell's: `evabyte.*`, `attn.eva_*`
                open(os.path.join(bench, 'harness', 'cla_lm_train_runner.py')).read(),  # its `correct`: `lm.head_nll`
                open(os.path.join(bench, 'harness', 'sconv_lm_readers.py')).read(),  # the short-convolution cell's: `sconv.proj, sconv.mix`
                open(os.path.join(bench, 'harness', 'sconv_lm_train_runner.py')).read(),  # its `correct`: `sconv.rows`
                open(os.path.join(bench, 'harness', 'kda_lm_readers.py')).read(),    # the delta-rule cell's: `kda.proj`, `kda.mix`, `kda.core`, `kda.rows`, `kda.chunks`
                open(os.path.join(bench, 'harness', 'step_scopes.py')).read(),       # every cell's `step.*`, the image cells' `img.*`: `img.block`
                inspect.getsource(train._host_line), inspect.getsource(train._setup_line)]
    unread = [name for name in tracing.SPANS if not any(f"'{name}'" in text for text in readers)]
    assert not unread, unread
    # the layers are the ones PERF.md section 3 and BENCHMARK.json name
    assert {layer for layer, _ in tracing.SPANS.values()} == {'entry and compile cache', 'input', 'step', 'attention', 'experts',
                                                             'feed-forward', 'short convolution', 'delta attention'}


@pytest.mark.parametrize('record', [lambda n: tracing.scope(n), lambda n: tracing.device_counter(n, 1)], ids=['scope', 'device_counter'])
def test_a_device_name_that_is_not_declared_is_refused(record):
    with pytest.raises(KeyError, match='not declared'):
        record('glm.made_up')


def test_a_device_scope_names_the_ops_traced_in_it_and_a_step_counter_rides_in_the_steps_output():
    """Declared, recorded, read: the scope is in the compiled program's op names, forward and backward (where
    `benchmarks/harness/device_scopes.py` reads it); the counter is a value of the program, not of the host."""
    import jax
    import jax.numpy as jnp
    before = tracing.snapshot()

    def loss(w, x):
        with tracing.scope('glm.dense_ffn'):
            y = jnp.tanh(x @ w)
        return y.sum(), {'lm.tokens': tracing.device_counter('lm.tokens', jnp.int32(x.shape[0]))}

    step = jax.jit(jax.value_and_grad(loss, has_aux=True))
    w, x = jnp.ones((4, 4)), jnp.ones((3, 4))
    text = step.lower(w, x).as_text(debug_info=True)
    assert 'glm.dense_ffn' in text and 'transpose(jvp(glm.dense_ffn))' in text
    (_, counters), _ = step(w, x)
    assert int(counters['lm.tokens']) == 3
    after = tracing.snapshot()
    assert len(after['spans']) - len(before['spans']) <= 1 and after['counters'] == before['counters']   # the ring is not theirs
    kinds = {name: what.split(':')[0] for name, (_, what) in tracing.SPANS.items() if name.startswith(('glm.', 'moe.', 'lm.'))}
    assert set(kinds.values()) == {'device scope', 'step counter', 'gauge'} and len(kinds) == 19   # two of the block-diffusion task, `lm.head_nll`, the route's two gauges
    # ONE kind of device scope: the window/full family's three were 'swa device scope' while `tracing.py` could not be
    # edited by the PRs that met `test_lm_harness.py`'s pin of the GLM reduction's nine (a superset since PR 35)
    swa = {name: what.split(':')[0] for name, (_, what) in tracing.SPANS.items() if name.startswith(('swa.', 'attn.'))}
    assert swa == {'swa.attn.proj': 'device scope', 'swa.attn.core_full': 'device scope',
                   'swa.attn.core_window': 'device scope', 'swa.attn.core_bd': 'device scope',
                   'attn.full_blocks': 'step counter', 'attn.window_blocks': 'step counter', 'attn.bd_blocks': 'step counter',
                   'attn.eva_blocks': 'step counter', 'attn.eva_pairs': 'step counter'}
    eva = {name: what.split(':')[0] for name, (_, what) in tracing.SPANS.items() if name.startswith('evabyte.')}
    assert eva == dict.fromkeys(('evabyte.attn.proj', 'evabyte.attn.summary', 'evabyte.attn.core', 'evabyte.ffn'), 'device scope')
    assert {what.split(':')[0] for _, what in tracing.SPANS.values() if 'scope' in what.split(':')[0]} == {'device scope'}
    assert all(tracing.SPANS[name][0] == 'attention' for name in swa)
    # the image models' and the step's own: seventeen, family-neutral, each under the layer its metric names
    ours = {name: layer for name, (layer, what) in tracing.SPANS.items() if name.startswith(('img.', 'step.'))}
    assert len(ours) == 17 and all(tracing.SPANS[name][1].startswith('device scope: ') for name in ours)
    assert {name for name, layer in ours.items() if layer == 'attention'} == {'img.attn.qkv', 'img.attn.core', 'img.attn.proj'}
    assert {layer for layer in ours.values()} == {'step', 'attention'}
    from benchmarks.harness import device_scopes
    assert all(device_scopes.SCOPE_TOKEN.fullmatch(name) for name in ours)               # the reduction's token: no digit, letters before the first dot
    assert set(ours) <= device_scopes.declared_scopes()                                  # all three families' reductions take them


def test_no_loader_worker_thread_opens_a_span():
    """Spans in the loader sit on the consuming (main) thread only; what runs on
    a decode thread, on the pool's reader thread or on the collator thread uses
    counters, and a decode process has no ring at all (it reports numbers)."""
    from timm_tpu.data import decode_worker
    from timm_tpu.data.loader import ThreadedLoader, _DecodePool
    collator = inspect.getsource(ThreadedLoader.__iter__)
    collator = collator[collator.index('def collator('):collator.index('exhausted = False')]
    for fn, body in (('worker', inspect.getsource(ThreadedLoader._decode_in_threads)),
                     ('deliver', inspect.getsource(ThreadedLoader._decode_in_processes)),
                     ('collator', collator)):
        assert 'tracing.span(' not in body, fn
        assert 'tracing.count(' in body or 'tracing.busy(' in body, fn
    assert 'tracing.span(' not in inspect.getsource(_DecodePool)
    assert 'tracing' not in inspect.getsource(decode_worker)


@pytest.mark.parametrize('metrics,starts', [({'moe.fallback_layers': 1, 'loss': 0.0}, 'fallback 1 of 8 layers host ms/step: next'),
                                            ({'loss': 0.0}, 'host ms/step: next'), (None, 'host ms/step: next')],
                         ids=['expert_layers', 'image_model', 'no_metrics'])
def test_the_log_line_names_the_expert_layers_that_fell_back_only_where_the_step_counts_them(metrics, starts):
    """`moe.fallback_layers` is read by `train.py`'s log line, before the host breakdown: `fallback F of L layers`."""
    import train
    text, counters = train._host_line(tracing.now_ns(), {}, metrics, 8)
    assert text.startswith(starts) and isinstance(counters, dict)


def test_the_log_line_names_the_attention_calls_traced_since_the_previous_line_and_the_core_each_took():
    """`attention.fused_calls` / `attention.plain_calls` are read by `train.py`'s log line, after `loop`: on a run's
    first line the step's `Attention` calls, on a later line nothing (nothing was traced since)."""
    import train
    before = tracing.snapshot()['counters']
    for name, n in (('attention.fused_calls', 2), ('attention.plain_calls', 1)):
        tracing.count(name, n)
    text, now = train._host_line(tracing.now_ns(), before)
    assert ' loop 0.0 attn fused 2 plain 1' in text and text.startswith('host ms/step: next')
    assert 'attn' not in train._host_line(tracing.now_ns(), now)[0]


PROGRAM = """HloModule jit_step

%fused_computation (param_0.2: bf16[65536,384], param_1.13: s32[32768]) -> bf16[32768,384] {
  %param_0.2 = bf16[65536,384]{1,0:T(8,128)(2,1)S(1)} parameter(0)
  %param_1.13 = s32[32768]{0:T(1024)S(1)} parameter(1)
  ROOT %gather.1 = bf16[32768,384]{1,0:T(8,128)(2,1)} gather(%param_0.2, %param_1.13), offset_dims={1}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1,384}, metadata={op_name="jit(step)/glm.moe.route/gather"}
}

%fused_computation.1 (param_0.3: bf16[65536,384], param_1.14: s32[32768]) -> bf16[32768,384] {
  %param_0.3 = bf16[65536,384]{1,0:T(8,128)(2,1)} parameter(0)
  %param_1.14 = s32[32768]{0:T(1024)S(1)} parameter(1)
  %gather.2 = bf16[32768,384]{1,0:T(8,128)(2,1)} gather(%param_0.3, %param_1.14), offset_dims={1}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1,384}, metadata={op_name="jit(step)/glm.moe.route/gather"}
  ROOT %select.1 = bf16[32768,384]{1,0:T(8,128)(2,1)} select(%gather.2, %gather.2, %gather.2), metadata={op_name="jit(step)/glm.moe.route/select_n"}
}

%fused_computation.2 (param_0.4: s32[32768]) -> s32[32768] {
  %param_0.4 = s32[32768]{0:T(1024)} parameter(0)
  ROOT %clamp.1 = s32[32768]{0:T(1024)S(1)} clamp(%param_0.4, %param_0.4, %param_0.4), metadata={op_name="jit(step)/glm.moe.route/gather"}
}

%fused_computation.3 (param_0.5: bf16[65536,64], param_1.15: s32[32768]) -> bf16[32768,64] {
  %param_0.5 = bf16[65536,64]{1,0:T(8,128)(2,1)S(1)} parameter(0)
  %param_1.15 = s32[32768]{0:T(1024)S(1)} parameter(1)
  ROOT %gather.3 = bf16[32768,64]{1,0:T(8,128)(2,1)} gather(%param_0.5, %param_1.15), offset_dims={1}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1,64}, metadata={op_name="jit(step)/glm.embed/gather"}
}

%branch_1 (arg: (bf16[65536,384], s32[32768])) -> bf16[32768,384] {
  %arg = (bf16[65536,384]{1,0:T(8,128)(2,1)}, s32[32768]{0:T(1024)}) parameter(0)
  %get-tuple-element.1 = bf16[65536,384]{1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=0
  %get-tuple-element.2 = s32[32768]{0:T(1024)} get-tuple-element(%arg), index=1
  %copy-done.3 = bf16[65536,384]{1,0:T(8,128)(2,1)S(1)} copy-done(%get-tuple-element.1)
  %broadcast_clamp_fusion = s32[32768]{0:T(1024)S(1)} fusion(%get-tuple-element.2), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/glm.moe.route/gather"}
  ROOT %fusion.7 = bf16[32768,384]{1,0:T(8,128)(2,1)} fusion(%copy-done.3, %broadcast_clamp_fusion), kind=kCustom, calls=%fused_computation, metadata={op_name="jit(step)/transpose(jvp(glm.moe.route))/gather"}
}

ENTRY %main (rows: bf16[65536,384], at: s32[32768], table: bf16[65536,64]) -> bf16[32768,384] {
  %rows = bf16[65536,384]{1,0:T(8,128)(2,1)} parameter(0)
  %at = s32[32768]{0:T(1024)} parameter(1)
  %table = bf16[65536,64]{1,0:T(8,128)(2,1)S(1)} parameter(2)
  %fusion.8 = bf16[32768,384]{1,0:T(8,128)(2,1)} fusion(%rows, %at), kind=kCustom, calls=%fused_computation.1, metadata={op_name="jit(step)/glm.moe.route/select_n"}
  %fusion.9 = bf16[32768,64]{1,0:T(8,128)(2,1)} fusion(%table, %at), kind=kCustom, calls=%fused_computation.3, metadata={op_name="jit(step)/glm.embed/gather"}
  ROOT %gather.4 = bf16[32768,384]{1,0:T(8,128)(2,1)} gather(%copy-done.3, %at), offset_dims={1}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1,384}, metadata={op_name="jit(step)/glm.moe.route/gather"}
}
"""


def test_the_reader_of_a_scopes_gathers_counts_fusions_that_gather_and_those_whose_source_is_in_fast_memory():
    """`scope_gathers` on a few lines of a compiled program's text: under `glm.moe.route` a gather fusion inside a
    conditional's branch whose source is an `S(1)` copy (fast), one in the entry computation whose source is a
    parameter in HBM and whose root is a select (its `op_name` holds no 'gather': the fused computation does), and
    a gather that stands in no fusion (fast): 3 and 2. Not counted: the index fusion, which carries the gather's
    `op_name` and gathers nothing, the gathers inside the fused computations (their fusions are), and the
    embedding's gather under another scope, which is that scope's one."""
    assert tracing.scope_gathers(PROGRAM, 'glm.moe.route') == (3, 2)
    assert tracing.scope_gathers(PROGRAM, 'glm.embed') == (1, 1)
    assert tracing.scope_gathers(PROGRAM, 'glm.mla.proj') == (0, 0) == tracing.scope_gathers('', 'glm.moe.route')


PRODUCTS = """HloModule jit_step

%fused_computation.5 (param_0.9: bf16[128,64], param_1.9: bf16[64,160]) -> bf16[128,160] {
  %param_0.9 = bf16[128,64]{1,0:T(8,128)(2,1)} parameter(0)
  %param_1.9 = bf16[64,160]{1,0:T(8,128)(2,1)} parameter(1)
  %convolution.7 = bf16[128,160]{1,0:T(8,128)(2,1)} convolution(%param_0.9, %param_1.9), dim_labels=bf_io->bf, metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/evabyte.ffn/dot_general"}
  ROOT %multiply.3 = bf16[128,160]{1,0:T(8,128)(2,1)} multiply(%convolution.7, %convolution.7), metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/evabyte.ffn/mul"}
}

ENTRY %main (x: bf16[128,64], w: bf16[64,160], v: bf16[64,64]) -> bf16[128,160] {
  %x = bf16[128,64]{1,0:T(8,128)(2,1)} parameter(0)
  %w = bf16[64,160]{1,0:T(8,128)(2,1)} parameter(1)
  %v = bf16[64,64]{1,0:T(8,128)(2,1)} parameter(2)
  %convolution.8 = bf16[128,64]{1,0:T(8,128)(2,1)} convolution(%x, %v), dim_labels=bf_io->bf, metadata={op_name="jit(step)/jvp(evabyte.attn.proj)/dot_general"}
  %dot.1 = bf16[128,160]{1,0:T(8,128)(2,1)} dot(%convolution.8, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/jvp(evabyte.ffn)/dot_general"}
  %add.2 = bf16[128,160]{1,0:T(8,128)(2,1)} add(%dot.1, %dot.1), metadata={op_name="jit(step)/jvp(evabyte.ffn)/add"}
  ROOT %convolution_multiply_fusion = bf16[128,160]{1,0:T(8,128)(2,1)} fusion(%x, %w), kind=kOutput, calls=%fused_computation.5, metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/evabyte.ffn/mul"}
}
"""


def test_the_reader_of_a_scopes_products_counts_convolutions_and_dots_inside_a_fusion_or_not_and_no_fusion_twice():
    """`scope_products` on a few lines of a compiled program's text: under `evabyte.ffn` a `convolution` inside a
    fused computation (a rematerialised one, by its `op_name`) and a `dot` that stands in no fusion: 2. Not counted:
    the fusion that calls the first (its name and its `op_name` say `convolution` and `evabyte.ffn`; it is no
    product instruction), the `add` and `multiply` under the scope, and the product under `evabyte.attn.proj`, which
    is that scope's one. The gauge `ffn.products` is declared a gauge of the feed-forward layer."""
    assert tracing.scope_products(PRODUCTS, 'evabyte.ffn') == 2
    assert tracing.scope_products(PRODUCTS, 'rematted_computation/evabyte.ffn') == 1 == tracing.scope_products(PRODUCTS, 'evabyte.attn.proj')
    assert tracing.scope_products(PRODUCTS, '') == 3 and tracing.scope_products(PRODUCTS, 'glm.dense_ffn') == 0 == tracing.scope_products('', 'evabyte.ffn')
    assert tracing.scope_loops(PRODUCTS, '') == 0 == tracing.scope_products(PROGRAM, '')      # the other readers' text holds no product
    assert tracing.SPANS['ffn.products'][0] == 'feed-forward' and tracing.SPANS['ffn.products'][1].startswith('gauge: ')


@pytest.mark.parametrize('products', [None, 36], ids=['gauge-unset', 'gauge-set'])
def test_the_log_line_names_the_feed_forward_products_only_where_a_kept_step_program_set_the_gauge(products, monkeypatch):
    """`ffn.products` is read by `train.py`'s log line, after the host breakdown and before `kda scans` / `route
    gathers`: `ffn products N`, the newest value; a run that kept no step program (or whose program holds no
    product under `evabyte.ffn`) prints nothing of it."""
    import train
    snap = tracing.snapshot()
    gauges = {k: v for k, v in snap['gauges'].items() if k != 'ffn.products'}
    if products is not None:
        gauges.update({'ffn.products': [(1, 44), (2, products)], 'kda.core_scans': [(3, 6)]})
    monkeypatch.setattr(tracing, 'snapshot', lambda: dict(snap, gauges=gauges))
    text = train._host_line(tracing.now_ns(), {})[0]
    assert (' ffn products 36 kda scans 6' in text) if products else ('ffn products' not in text)
