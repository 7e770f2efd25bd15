"""`harness/step_scopes.py`: the step program's device time by device scope, in ms a traced step, for every cell
(ISSUE 39), and the seven per-layer metrics that read it. On the recorded chip trace under `benchmarks/fixtures/` with
a written text, on tuples, and on records written by hand: nothing here runs a model. What the manifest must have is
held as a subset of what it has, and the entries older than this file by name and shape only: a later PR appends
cells and metrics and may not edit this file.
"""
import hashlib
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import step_scopes, trace  # noqa: E402
from benchmarks.harness.manifest import BENCH_DIR, Manifest, load_json  # noqa: E402
from timm_tpu.utils import tracing  # noqa: E402

FIXTURE = os.path.join(BENCH_DIR, 'fixtures', 'toy_matmuls.xplane.pb')
VIT, CNX, GLM, SWA, BD = ('vit_b16_train', 'convnext_b_train', 'glm47_flash_ep8_train_8k',
                          'smallthinker_21b_ep8_train_16k', 'sdar_30b_a3b_ep8_train_bd4_8k')
FIVE = [VIT, CNX, GLM, SWA, BD]
# the two that every cell's step has list four: `test_bd_lm_harness.py` holds the fifth cell's per-layer metrics at 31 by
# count and may not be edited by the PR that brings these (PERF.md section 7); the readers read its records all the same
FOUR = [VIT, CNX, GLM, SWA]
SEVEN = {'img_attn_device_ms.train': [VIT], 'img_mlp_device_ms.train': [VIT, CNX], 'img_norm_device_ms.train': [VIT, CNX],
         'img_conv_dw_device_ms.train': [CNX], 'img_embed_head_device_ms.train': [VIT, CNX],
         'step_update_device_ms.train': FOUR, 'step_scope_cover.train': FOUR}
# what `scopes_of` would find in a cell's traced run, as far as the readers read it: ms a step by scope
STEP_ROWS = {'step.clip': 1.0, 'step.update': 4.0, 'step.guard': 2.0}
ROWS = {VIT: {'img.patch_embed': 1.5, 'img.block': 3.0, 'img.norm': 12.0, 'img.attn.qkv': 25.0, 'img.attn.core': 24.0,
              'img.attn.proj': 10.0, 'img.mlp': 60.0, 'img.head': 0.5, 'step.input': 0.25, 'step.loss': 0.25,
              'step.ema': 1.0, **STEP_ROWS},
        CNX: {'img.stem': 1.0, 'img.downsample': 2.0, 'img.block': 6.0, 'img.norm': 16.0, 'img.mlp': 80.0, 'img.conv_dw': 35.0,
              'img.head': 0.5, 'step.input': 0.25, 'step.loss': 0.25, 'step.ema': 1.0, **STEP_ROWS},
        GLM: {'glm.mla.core': 257.0, 'glm.moe.route': 47.0, **STEP_ROWS},
        SWA: {'swa.attn.proj': 163.0, 'glm.moe.route': 183.0, **STEP_ROWS},
        BD: {'swa.attn.core_bd': 278.0, 'glm.moe.route': 95.0, **STEP_ROWS}}
BEFORE = ['warm_compile_misses', 'input_host_ms.train', 'step_device_ms.train', 'step_wall_ms.train', 'dispatch_host_ms.train',
          'step_mfu.train', 'device_idle_share.train', 'hbm_peak_gb.train', 'step_state_split_ms.train',
          'step_scalars_put_ms.train', 'step_call_ms.train', 'step_state_update_ms.train', 'step_sentinel_poll_ms.train',
          'step_host_cpu_share.train', 'input_batch_wait_ms.train', 'input_prepare_ms.train', 'input_decode_busy_share.train',
          'loop_bookkeeping_ms.train', 'device_idle_in_step_ms.train', 'device_idle_in_input_ms.train',
          'device_idle_in_loop_ms.train', 'device_idle_attributed_share.train', 'setup_model_build_s', 'setup_data_build_s',
          'setup_step_program_s', 'setup_compile_s', 'moe_route_device_ms.train', 'moe_device_ms.train', 'moe_experts_mfu.train',
          'mla_device_ms.train', 'mla_core_mfu.train', 'attn_device_ms.train', 'attn_proj_mfu.train', 'attn_full_core_mfu.train',
          'attn_window_core_mfu.train', 'attn_window_block_fill.train', 'attn_bd_core_mfu.train', 'attn_bd_block_fill.train']


def found_for(cell: str) -> dict:
    rows = ROWS[cell]
    covered = sum(rows.values())
    return {'scope_ms': rows, 'busy_ms': covered + 1.0, 'covered_ms': covered, 'cover': 100 * covered / (covered + 1.0),
            'outside': [['fusion', 1.0]], 'other_programs_ms': 0.6 if cell in (VIT, CNX) else 0.0, 'holds': {}, 'families': {}}


def record(cell: str) -> dict:
    """A traced run's record as far as `result_line` and this file's readers read it."""
    return {'runner': 'train', 'cell': cell, 'correct': True, 'attempted': 10, 'failed': 0, 'memory_peak_bytes': 1,
            'device_kind': 'TPU v5 lite',
            'trace': {'busy_s': 1.5, 'window_s': 2.0, 'idle_share': 0.25, 'work': 10, 'breakdown': {'device_ops': [], 'idle_gaps': []}}}


def hlo(module: str, scope_of_instruction: dict) -> str:
    lines = [f'HloModule {module}, is_scheduled=true, entry_computation_layout={{()->f32[]}}', '', 'ENTRY %main {']
    lines += [f'  %{name} = bf16[2,2]{{1,0}} op(%p)' + (f', metadata={{op_name="jit(f)/{scope}/x"}}' if scope else '')
              for name, scope in scope_of_instruction.items()]
    return '\n'.join(lines + ['}'])


@pytest.fixture
def manifest():
    return Manifest()


@pytest.fixture
def found(monkeypatch):
    """`scopes_of` answers from `ROWS` by the record's cell: the readers on a record with scopes, no file behind it."""
    monkeypatch.setattr(step_scopes, 'scopes_of', lambda run: found_for(run['cell']) if run.get('cell') in ROWS and run.get('trace') else None)


def test_the_recorded_chip_trace_is_reduced_by_scope_in_ms_a_step():
    devices, spans = trace.read_planes(FIXTURE)
    ops = next(iter(devices.values()))
    modules = step_scopes.read_modules(FIXTURE)
    assert len(modules) == 40 and {n for n, _, _ in modules} == {'jit__lambda'}      # 40 calls of one program, 4 ops each
    window = next((s, e) for n, s, e in spans if n == 'window')
    want = load_json(os.path.join(BENCH_DIR, 'fixtures', 'toy_matmuls.expected.json'))
    scopes = {'fusion': 'img.mlp', 'fusion.1': 'img.attn.core', 'copy-start': 'step.update', 'copy-done': 'step.update'}
    got = step_scopes.reduce(ops, modules, 'jit__lambda', scopes, window, steps=40)
    assert got['busy_ms'] * 40 == pytest.approx(want['busy_s'] * 1e3, rel=1e-9) and got['cover'] == pytest.approx(100.0)
    assert got['other_programs_ms'] == 0 and got['outside'] == []
    assert set(got['scope_ms']) == {'img.mlp', 'img.attn.core', 'step.update'}
    assert sum(got['scope_ms'].values()) == pytest.approx(got['busy_ms'], rel=1e-6)
    assert got['scope_ms']['img.mlp'] == pytest.approx(got['scope_ms']['img.attn.core'], rel=0.05)     # two like products
    assert got['scope_ms']['img.mlp'] > 100 * got['scope_ms']['step.update'] > 0
    # one fusion left without a scope: it is what is outside, in ms a step, and the cover says how much
    del scopes['fusion.1']
    part = step_scopes.reduce(ops, modules, 'jit__lambda', scopes, window, steps=40)
    assert part['outside'] == [['fusion', pytest.approx(got['scope_ms']['img.attn.core'])]]
    assert part['cover'] == pytest.approx(100 * (1 - got['scope_ms']['img.attn.core'] / got['busy_ms']))
    assert part['covered_ms'] + part['outside'][0][1] == pytest.approx(part['busy_ms'])
    # per step, not per window: twice the steps, half the numbers
    assert step_scopes.reduce(ops, modules, 'jit__lambda', scopes, window, steps=80)['busy_ms'] == pytest.approx(part['busy_ms'] / 2)
    # a program the trace does not hold, and a trace without the module line
    assert step_scopes.reduce(ops, modules, 'jit_train_step', scopes, window, steps=40) is None
    assert step_scopes.reduce(ops, [], 'jit__lambda', scopes, window, steps=40) is None


def test_ops_are_split_between_two_programs_by_the_module_intervals():
    """The augment program's `fusion.3` is not the step's: only ops inside the step program's intervals are booked by
    instruction name, the others are `other programs`, in no scope and not in the cover."""
    modules = [('jit_train_step', 100, 200), ('jit_augment', 210, 230), ('jit_train_step', 300, 400), ('jit_augment', 410, 430)]
    ops = [('%fusion.3 = f32[2] fusion(%a)', 100, 180), ('%copy.1 = f32[2] copy(%a)', 180, 200),
           ('%fusion.3 = f32[2] fusion(%b)', 210, 225), ('%copy.1 = f32[2] copy(%b)', 225, 230),
           ('%fusion.3 = f32[2] fusion(%a)', 300, 380), ('%copy.1 = f32[2] copy(%a)', 380, 400),
           ('%fusion.3 = f32[2] fusion(%b)', 410, 425), ('%copy.1 = f32[2] copy(%b)', 425, 430)]
    mine, others = step_scopes.split_by_program(ops, modules, 'jit_train_step')
    assert [s for _, s, _ in mine] == [100, 180, 300, 380] and [s for _, s, _ in others] == [210, 225, 410, 425]
    got = step_scopes.reduce(ops, modules, 'jit_train_step', {'fusion.3': 'img.mlp'}, steps=2)
    assert got['scope_ms'] == {'img.mlp': pytest.approx(80e-6)} and got['busy_ms'] == pytest.approx(100e-6)
    assert got['other_programs_ms'] == pytest.approx(20e-6) and got['outside'] == [['copy', pytest.approx(20e-6)]]
    assert got['cover'] == pytest.approx(80.0)
    # the window clips: the first pair of programs only
    first = step_scopes.reduce(ops, modules, 'jit_train_step', {'fusion.3': 'img.mlp'}, window=(0, 250), steps=1)
    assert first['busy_ms'] == pytest.approx(100e-6) and first['other_programs_ms'] == pytest.approx(20e-6)


def test_the_cover_is_a_union_over_a_union_and_never_over_100():
    """Async copies of one scope run under another's ops, and a conditional's own event lies over its branches' ops:
    rows summed can pass the busy time, the cover cannot."""
    modules = [('jit_train_step', 0, 1000)]
    ops = [('%conditional.1 = f32[] conditional(%p)', 0, 600), ('%fusion.1 = f32[] fusion(%a)', 0, 300),
           ('%fusion.2 = f32[] fusion(%a)', 300, 600), ('%copy-start.1 = f32[] copy-start(%a)', 100, 700),
           ('%while.1 = f32[] while(%a)', 500, 900), ('%fusion.9 = f32[] fusion(%a)', 900, 1000)]
    scopes = {'conditional.1': 'glm.moe.route', 'fusion.1': 'glm.moe.experts', 'fusion.2': 'glm.moe.route', 'copy-start.1': 'step.update'}
    got = step_scopes.reduce(ops, modules, 'jit_train_step', scopes, steps=1)
    assert sum(got['scope_ms'].values()) > got['busy_ms'] and got['cover'] == pytest.approx(70.0) and got['cover'] <= 100
    assert got['outside'] == [['while', pytest.approx(200e-6)], ['fusion', pytest.approx(100e-6)]]   # what no scoped op covers
    assert got['covered_ms'] + sum(v for _, v in got['outside']) == pytest.approx(got['busy_ms'])


def test_a_row_says_which_other_scopes_its_fusions_hold():
    """XLA books a fusion under its root's scope: a LayerNorm fused into the product that reads it, an optimizer update
    fused into the weight gradient's product. The compiled text knows what was fused, nested computations too; the
    reduction re-books nothing and says how much of a row ran in such fusions."""
    meta = lambda scope: f', metadata={{op_name="jit(train_step)/{scope}/x"}}'  # noqa: E731
    text = '\n'.join([
        'HloModule jit_train_step, is_scheduled=true', '',
        '%inner (p: f32[2]) -> f32[2] {',
        '  %p = f32[2]{0} parameter(0)',
        '  ROOT %rsqrt.1 = f32[2]{0} rsqrt(%p)' + meta('jvp(img.block)/img.norm'), '}', '',
        '%fused_computation.1 (a: f32[2]) -> f32[2] {',
        '  %a = f32[2]{0} parameter(0)',
        '  %norm_fusion.1 = f32[2]{0} fusion(%a), kind=kLoop, calls=%inner' + meta('jvp(img.block)/img.norm'),
        '  ROOT %convolution.1 = f32[2]{0} convolution(%norm_fusion.1, %a)' + meta('jvp(img.block)/img.mlp'), '}', '',
        '%fused_computation.2 (a: f32[2]) -> f32[2] {',
        '  %a.1 = f32[2]{0} parameter(0)',
        '  %multiply.7 = f32[2]{0} multiply(%a.1, %a.1)' + meta('step.update'),
        '  ROOT %select.3 = f32[2]{0} select(%a.1, %multiply.7, %a.1)' + meta('step.guard'), '}', '',
        'ENTRY %main (x: f32[2]) -> f32[2] {',
        '  %x = f32[2]{0} parameter(0)',
        '  %fusion.1 = f32[2]{0} fusion(%x), kind=kOutput, calls=%fused_computation.1' + meta('jvp(img.block)/img.mlp'),
        '  %fusion.2 = f32[2]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2' + meta('step.guard'),
        '  ROOT %add.1 = f32[2]{0} add(%fusion.2, %x)' + meta('jvp(img.block)'), '}'])
    names = step_scopes.declared_scopes()
    fused = step_scopes.fused_scopes(text, names)
    assert fused == {'norm_fusion.1': {'img.norm'}, 'fusion.1': {'img.norm', 'img.mlp'}, 'fusion.2': {'step.update', 'step.guard'}}
    from benchmarks.harness import device_scopes
    scopes = device_scopes.instruction_scopes(text, names)
    assert (scopes['fusion.1'], scopes['fusion.2'], scopes['add.1']) == ('img.mlp', 'step.guard', 'img.block')
    modules = [('jit_train_step', 0, 1000)]
    ops = [('%fusion.1 = f32[2] fusion(%x)', 0, 600), ('%fusion.2 = f32[2] fusion(%fusion.1)', 600, 900), ('%add.1 = f32[2] add(..)', 900, 1000)]
    got = step_scopes.reduce(ops, modules, 'jit_train_step', scopes, steps=1, fused=fused)
    assert got['scope_ms'] == {'img.mlp': pytest.approx(600e-6), 'step.guard': pytest.approx(300e-6), 'img.block': pytest.approx(100e-6)}
    assert got['holds'] == {'img.mlp': {'img.norm': pytest.approx(600e-6)}, 'step.guard': {'step.update': pytest.approx(300e-6)}}
    assert got['families'] == {'img.mlp': {'fusion': pytest.approx(600e-6)}, 'step.guard': {'fusion': pytest.approx(300e-6)},
                               'img.block': {'add': pytest.approx(100e-6)}}
    assert step_scopes.reduce(ops, modules, 'jit_train_step', scopes, steps=1)['holds'] == {}


def test_a_record_with_a_trace_and_a_kept_text_is_read_through_one_path_and_cached(tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(step_scopes, 'REPO_ROOT', str(tmp_path))
    monkeypatch.setattr(tracing, '_programs', {})
    step_scopes._reduced.cache_clear()
    run = record(VIT)
    assert step_scopes.scopes_of(run) is None                           # no text: nobody lowered the step in this process
    text = hlo('jit__lambda', {'fusion': 'img.mlp', 'fusion.1': 'img.attn.core', 'copy-start': 'step.update', 'copy-done': None})
    tracing._programs['task.step_call'] = text
    assert step_scopes.scopes_of(run) is None                           # no trace: a hand-written record, no file behind it
    folder = tmp_path / 'output' / 'benchmarks' / 'trace' / VIT / 'plugins' / 'profile' / 'x'
    folder.mkdir(parents=True)
    shutil.copy(FIXTURE, folder / 't.xplane.pb')
    got = step_scopes.scopes_of(run)
    assert set(got['scope_ms']) == {'img.mlp', 'img.attn.core', 'step.update'} and 99.9 < got['cover'] < 100
    assert got['outside'][0][0] == 'copy-done' and got['busy_ms'] == pytest.approx(0.7230206, rel=1e-6)   # 7.23 ms over `work` = 10
    assert step_scopes.scopes_of(run) is got                            # cached by path, mtime, text and steps
    assert step_scopes.scope_ms(run, 'img.mlp', 'img.attn.core') == pytest.approx(got['scope_ms']['img.mlp'] + got['scope_ms']['img.attn.core'])
    assert step_scopes.scope_ms(run, 'img.conv_dw') is None and step_scopes.cover(run) == got['cover']
    assert any(line.startswith('step scope img.mlp: ') for line in step_scopes.table(run)) and 'other programs 0.000' in step_scopes.table(run)[-2]
    # nothing to read, and nothing raised: an untraced run, a record of no cell, an empty one, another cell's (no trace of its own)
    for thin in (dict(run, trace=None), {k: v for k, v in run.items() if k != 'cell'}, {}, dict(run, cell=CNX)):
        assert step_scopes.scopes_of(thin) is None and step_scopes.scope_ms(thin, 'img.mlp') is None and step_scopes.cover(thin) is None
    assert step_scopes.table({}) == ['step scopes: nothing to read']
    # a text of a program the trace does not hold
    tracing._programs['task.step_call'] = hlo('jit_train_step', {'fusion': 'img.mlp'})
    assert step_scopes.scopes_of(run) is None and capsys.readouterr().out == ''
    # a text without a single declared scope (a cached executable keeps the op names it was compiled with): one plain line, once
    tracing._programs['task.step_call'] = hlo('jit__lambda', {'fusion': 'adamw', 'fusion.1': None})
    assert step_scopes.scopes_of(run) is None and step_scopes.scopes_of(run) is None
    said = capsys.readouterr().out.splitlines()
    assert len(said) == 1 and said[0].startswith("step scopes: the step program's text holds no declared scope: compiled before them?")
    # a program older than `tracing.program_text` (the benchmark's files over a parent's checkout)
    monkeypatch.delattr(tracing, 'program_text')
    assert step_scopes.scopes_of(run) is None
    step_scopes._reduced.cache_clear()


@pytest.mark.parametrize('name', list(SEVEN))
def test_each_of_the_seven_is_a_number_in_the_cells_that_list_it_and_none_elsewhere(manifest, found, name):
    read, entry = manifest.reader(name), manifest.per_layer[name]       # LAYER, UNIT, MOVES of the file agree with the entry
    assert set(SEVEN[name]) <= set(entry['workloads']) and not (set(FIVE) - set(SEVEN[name])) & set(entry['workloads'])
    assert (entry['source'], entry['moves']) == ('device_trace', 'train_img_per_s') and 'mfu' not in name and 'roofline' not in name
    assert (entry['unit'], entry['better']) == (('%', 'higher') if name == 'step_scope_cover.train' else ('ms', 'lower'))
    assert entry['layer'] == {'img_attn_device_ms.train': 'attention', 'step_scope_cover.train': 'device'}.get(name, 'step')
    for cell in FIVE:
        value = read(record(cell))
        if cell in SEVEN[name] or (cell == BD and SEVEN[name] == FOUR):
            assert isinstance(value, float) and 0 < value and (entry['unit'] != '%' or value <= 100), (cell, value)
        else:
            assert value is None, (cell, value)
    assert read({}) is None and read(dict(record(VIT), trace=None)) is None


def test_the_sums_are_the_issues(manifest, found):
    vit, cnx, glm = record(VIT), record(CNX), record(GLM)
    assert manifest.reader('img_attn_device_ms.train')(vit) == pytest.approx(59.0)
    assert manifest.reader('img_embed_head_device_ms.train')(vit) == pytest.approx(2.5)
    assert manifest.reader('img_embed_head_device_ms.train')(cnx) == pytest.approx(4.0)
    assert manifest.reader('step_update_device_ms.train')(vit) == pytest.approx(8.0)
    assert manifest.reader('step_update_device_ms.train')(glm) == pytest.approx(7.0)     # no EMA in the LM recipes
    assert manifest.reader('img_conv_dw_device_ms.train')(cnx) == pytest.approx(35.0)
    # `img.block` is a row of the table and of no metric
    assert not [n for n in SEVEN if "'img.block'" in open(os.path.join(BENCH_DIR, 'layer_metrics', n + '.py')).read()]
    assert step_scopes.PRINTED == ('img.block',)


@pytest.mark.parametrize('cell', FIVE)
def test_the_traced_line_carries_exactly_the_new_names_its_cell_lists(manifest, found, cell):
    device = {'platform': 'tpu', 'kind': 'TPU v5 lite', 'count': 1}
    line = json.loads(json.dumps(bench_run.result_line(manifest, cell, record(cell), device, trace=True)))
    got = {k: v['value'] for k, v in line['metrics'].items()}
    assert set(got) & set(SEVEN) == {n for n, cells in SEVEN.items() if cell in cells}
    if cell in FOUR:
        assert got['step_scope_cover.train'] <= 100 and line['metrics']['step_scope_cover.train']['unit'] == '%'
    # without scopes to read (a parent older than them under this PR's benchmark files) the line leaves the seven out
    bare = bench_run.result_line(manifest, cell, dict(record(cell), cell=None), device, trace=True)
    assert not set(bare['metrics']) & set(SEVEN)


def test_the_entries_are_appended_and_what_stood_stands(manifest):
    entries = manifest.data['per_layer']
    assert [m['name'] for m in entries[:38]] == BEFORE and [m['name'] for m in entries[38:45]] == list(SEVEN)
    shape = [{k: v for k, v in m.items() if k != 'workloads'} for m in entries[:38]]     # a later PR may append a cell to a list
    assert hashlib.sha256(json.dumps(shape, sort_keys=True).encode()).hexdigest() == \
        'c7d9966c5a941e10e4d3ec7ea202ef832d4a902e3fb14a0102b1856eeccc5081'
    for name in SEVEN:
        assert os.path.getsize(os.path.join(BENCH_DIR, 'layer_metrics', name + '.py')) < 1000
