"""The window/full language-model cell's part of the benchmark (`swa_lm_train_runner.py`, `swa_lm_flops.py`,
`swa_lm_readers.py`, the configuration and the cell ISSUE 31 brings), at `smallthinker_toy` size on the CPU.
One file, like its neighbours.

Eight of the readings are metrics of `BENCHMARK.json` since PR 35 (the five `attn_*` of this family, in
`swa_lm_readers.READERS`, and three `moe_*` of `lm_readers.READERS` that list both language-model cells under ONE
entry: no reader asks which family a record is of); the whole step's share of the peak is `step_mfu.train`'s, and
two stay free text (`lm_readers.PRINTED`). The toy manifest below lists its cell wherever the real cell is listed.
What the manifest must have is held as a subset of what it has: a later PR adds cells and metrics and may not
edit this file.
"""
import json
import math
import os
import shutil
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import check, device_scopes, lm_readers, swa_lm_flops, swa_lm_readers  # noqa: E402
from benchmarks.harness.manifest import BENCH_DIR, Manifest, load_json, runner_module  # noqa: E402

CELL, GLM_CELL, CONFIG = 'smallthinker_21b_ep8_train_16k', 'glm47_flash_ep8_train_8k', 'smallthinker_21b_ep8'
BOTH = ['moe_route_device_ms.train', 'moe_device_ms.train', 'moe_experts_mfu.train']     # `lm_readers.READERS`': both families'
OWN = ['attn_device_ms.train', 'attn_proj_mfu.train', 'attn_full_core_mfu.train', 'attn_window_core_mfu.train',
       'attn_window_block_fill.train']                                                      # `swa_lm_readers.READERS`'
METRICS = BOTH + OWN                                                                        # the manifest's order
COUNTED = list(lm_readers.PRINTED)
NOT_ITS = {'input_prepare_ms.train', 'input_decode_busy_share.train', 'mla_device_ms.train', 'mla_core_mfu.train'}
TOY_SIZES = dict(vocab_held=256, hidden_size=64, num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                 moe_ffn_hidden_size=32, moe_num_primary_experts=8, moe_num_active_primary_experts=2, experts_held=2,
                 expert_offset=0, rope_layout=[0, 1, 1, 1], sliding_window_layout=[0, 1, 1, 1], sliding_window_size=8,
                 rope_theta=1.5e6, rms_norm_eps=1e-6)
# float32 on both sides: summation order only (Adam's division makes 1e-4 of a change norm); float8 operands
# move every number by 1e-2 and more
TOY_LIMITS = {'loss_gap': 2e-4, 'first_grad_norm_gap': 2e-4, 'param_change_norm_gap': 2e-3, 'ema_change_norm_gap': 2e-3}
GLM_RECORD = {'runner': 'train', 'steps': 3, 'device_kind': 'TPU v5 lite', 'lm': {'seq_len': 64, 'sequences': 8},
              'sizes': dict(vocab_held=256, hidden_size=64, num_hidden_layers=3, num_attention_heads=4, q_lora_rank=24,
                            kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=160,
                            moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2, experts_held=2, n_shared_experts=1,
                            first_k_dense_replace=1, num_nextn_predict_layers=1),
              'counters': {'moe.local_slots': [700, 800], 'moe.load_max': [300, 310], 'moe.dropped_slots': [0, 0]},
              'needed_macs': {'mla_core': 4e9, 'moe_experts': 1e9, 'moe_route': 1e7},
              'trace': {'busy_s': 0.5, 'window_s': 1.0, 'idle_share': 0.5, 'work': 5, 'scopes': {
                  'scope_s': {'glm.mla.core': 0.2, 'glm.moe.route': 0.03, 'glm.moe.experts': 0.04}, 'busy_s': 0.5, 'unscoped': []}}}

def test_the_manifest_has_the_configuration_the_cell_and_its_readers_entries():
    m = Manifest()
    names = [x['name'] for x in m.data['per_layer']]
    assert set(swa_lm_readers.READERS) == set(OWN) and 'lm_step_mfu.train' not in names     # one share, one name
    assert [n for n in names[26:] if n in METRICS] == METRICS and not set(COUNTED) & set(names)
    assert [w['name'] for w in m.data['workloads']][2:4] == [GLM_CELL, CELL]                 # later cells come after
    assert [c['name'] for c in m.data['configs']][3] == CONFIG and m.data['run_seconds'] == 20
    # among the 26 entries older than the readings the cell is listed wherever the GLM cell is, after it; a reading
    # lists the cells of the families that have it
    for metric in m.data['end_to_end'] + m.data['per_layer'][:26]:
        cells = metric.get('workloads', [])
        assert (CELL in cells) == (GLM_CELL in cells) and (CELL not in cells or cells.index(CELL) == cells.index(GLM_CELL) + 1)
    assert not NOT_ITS & set(m.metrics_of(CELL, 'per_layer')) and m.metrics_of(CELL, 'end_to_end') == ['train_img_per_s', 'setup_s']
    assert set(OWN) <= set(m.metrics_of(CELL, 'per_layer')) - set(m.metrics_of(GLM_CELL, 'per_layer'))
    assert set(BOTH) <= set(m.metrics_of(CELL, 'per_layer')) & set(m.metrics_of(GLM_CELL, 'per_layer'))
    for name, r in swa_lm_readers.READERS.items():           # what their entries say
        entry = swa_lm_readers.entry(name, [CELL])
        assert entry == {'name': name, 'unit': r.unit, 'better': r.better, 'source': r.source, 'layer': r.layer,
                         'moves': 'train_img_per_s', 'workloads': [CELL]}
        assert dict(m.per_layer[name], workloads=[CELL]) == entry and CELL in m.per_layer[name]['workloads']
        assert r.layer == 'attention' and r.source in ('device_trace', 'program_counter')
        assert (r.unit, r.better) == (('ms', 'lower') if name.endswith('_ms.train') else ('%', 'higher'))
    cell, config = m.cell(CELL), m.config(CONFIG)
    assert cell['runner'] == 'swa_lm_train' and cell['chips'] == 1 and m.cells[CELL]['traffic'] == 'train_token_stream'
    stream = cell['traffic']['token_stream']
    assert (stream['tokens'], stream['validation_tokens'], stream['data_seed']) == (8_388_608, 32768, 20260929)
    assert cell['traffic']['warmup_steps'] == 6 and stream['tokens'] // 16384 == 512
    assert {'source', 'published', 'deployment', 'reduced', 'reduced_why', 'assumed', 'precision', 'sizes', 'limits',
            'limits_why'} <= set(config)
    assert config['reduced'] == ['num_hidden_layers', 'moe_num_primary_experts', 'vocab_size'] == list(config['reduced_why'])
    assert config['train_args'] == '-b 1 --amp --opt adamw --opt-betas 0.9 0.95 --weight-decay 0.1 --clip-grad 1.0 ' \
                                   '--grad-checkpointing --dataset tokens --seq-len 16384'.split()
    path = '/opt/skills/guides/model-configs/architectures.jsonl'
    for row in [json.loads(line) for line in open(path)] if os.path.exists(path) else []:
        if row['name'] == 'SmallThinker-21BA3B-Instruct':    # every published number under its key, but the three reduced
            assert config['source'] == row['source_url'] == m.data['configs'][3]['source']
            off = {k for k, v in row['config'].items() if config.get(k, 'missing') != v}
            assert off == set(config['reduced']) == set(config['published']) and all(
                config['published'][k] == row['config'][k] for k in off)
    sizes = config['sizes']
    assert (sizes['num_hidden_layers'], sizes['experts_held'], sizes['vocab_held']) == (8, 8, 18992) == (
        config['num_hidden_layers'], config['moe_num_primary_experts'], config['vocab_size'])
    assert all(sizes[k] == config[k] for k in ('hidden_size', 'num_attention_heads', 'num_key_value_heads', 'head_dim',
                                               'moe_ffn_hidden_size', 'moe_num_active_primary_experts', 'sliding_window_size',
                                               'rope_theta', 'rms_norm_eps'))
    assert sizes['moe_num_primary_experts'] == 64 and sizes['rope_layout'] == config['rope_layout'][:8] == [0, 1, 1, 1] * 2
    assert sizes['sliding_window_layout'] == config['sliding_window_layout'][:8] and len(config['rope_layout']) == 52
    assert set(config['limits']['swa_lm_train']) == set(TOY_LIMITS) and set(config['limits_why']) >= set(TOY_LIMITS) | {'route_agreement_min'}
    assert 0.9 <= config['limits_lm']['route_agreement_min'] < 1.0 and len(config['source']) <= 200
    # the held parameters, from the reference's own shapes: ISSUE 31's table
    from benchmarks.reference import smallthinker
    assert sum(math.prod(shape) for shape, _ in smallthinker.init_spec(sizes).values()) == 643_852_800


@pytest.mark.parametrize('seq,window', [(1, 1), (7, 3), (16, 16), (16, 40), (33, 8), (64, 1), (96, 32)])
def test_the_pair_counts_are_the_masks_own(seq, window):
    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    assert swa_lm_flops.causal_pairs(seq) == int((j <= i).sum())
    assert swa_lm_flops.window_pairs(seq, window) == int(((j <= i) & (i - j < window)).sum())


def test_needed_operations_are_the_issues_arithmetic():
    sizes = Manifest().config(CONFIG)['sizes']
    assert swa_lm_flops.causal_pairs(16384) == 134_225_920 and swa_lm_flops.window_pairs(16384, 4096) == 58_722_304
    assert swa_lm_flops.layer_kinds(sizes) == (2, 6)
    macs = swa_lm_flops.forward_macs(sizes, 16384, 1, local_slots=16384 * 6 * 8 / 64 * 8)          # even routing: 12288 a layer
    assert macs['attn_core_full'] + macs['attn_core_window'] == pytest.approx(4.45e12, rel=2e-3)
    assert macs['attn_proj'] == 16384 * 20_971_520 * 8 == pytest.approx(2.75e12, rel=2e-3)
    assert macs['head'] == pytest.approx(0.80e12, rel=5e-3) and macs['moe_experts'] == pytest.approx(0.58e12, rel=5e-3)
    assert macs['moe_route'] == pytest.approx(0.02e12, rel=0.1)
    total = sum(macs.values())
    assert total == pytest.approx(8.60e12, rel=2e-3) and swa_lm_flops.train_flops(macs) == pytest.approx(51.6e12, rel=2e-3)
    assert (macs['attn_core_full'] + macs['attn_core_window']) / total == pytest.approx(0.518, abs=0.002)
    # were every layer full the cores would need 7.70e12: the windows remove 42 % of the cores' work
    every_full = swa_lm_flops.causal_pairs(16384) * 8 * 28 * 256
    assert every_full == pytest.approx(7.70e12, rel=2e-3)
    assert 1 - (macs['attn_core_full'] + macs['attn_core_window']) / every_full == pytest.approx(0.42, abs=0.005)
    assert swa_lm_flops.forward_macs(sizes, 16384, 1, 0)['moe_experts'] == 0
    # below the window a window layer is a full one
    short = swa_lm_flops.forward_macs(sizes, 4096, 1, 0)
    assert short['attn_core_window'] == 3 * short['attn_core_full']


@pytest.fixture(scope='module')
def toy(tmp_path_factory):
    """A copy of the benchmark's data with a toy configuration and cell of the new runner added as files."""
    tmp = tmp_path_factory.mktemp('toyswa')
    bench = tmp / 'benchmarks'
    for d in ('configs', 'workloads', 'layer_metrics'):
        shutil.copytree(os.path.join(BENCH_DIR, d), bench / d)
    man = load_json(os.path.join(ROOT, 'BENCHMARK.json'))
    (bench / 'configs' / 'toy_swa.json').write_text(json.dumps({
        'name': 'toy_swa', 'source': 'test', 'model': 'smallthinker_toy', 'reference': 'smallthinker', 'sizes': TOY_SIZES,
        'recipe': {'clip_grad': 1.0, 'weight_decay': 0.1, 'betas': [0.9, 0.95]},
        'train_args': ['-b', '8', '--opt', 'adamw', '--opt-betas', '0.9', '0.95', '--weight-decay', '0.1', '--clip-grad', '1.0',
                       '--grad-checkpointing', '--dataset', 'tokens', '--seq-len', '32'],
        'reduced': [], 'reference_block_q': 8, 'limits': {'swa_lm_train': TOY_LIMITS}, 'limits_lm': {'route_agreement_min': 0.99}}))
    (bench / 'workloads' / 'toy_swa_train.json').write_text(json.dumps({
        'config': 'toy_swa', 'runner': 'swa_lm_train', 'chips': 1,
        'traffic': {'warmup_steps': 3, 'token_stream': {'name': 'toy', 'data_seed': 1, 'tokens': 32 * 8 * 400,
                                                        'validation_tokens': 32 * 8}}}))
    man['configs'].append({'name': 'toy_swa', 'source': 'test', 'file': 'benchmarks/configs/toy_swa.json', 'reduced': [], 'why': 'test'})
    man['workloads'].append({'name': 'toy_swa_train', 'config': 'toy_swa', 'traffic': 'toy_tokens', 'chips': 1, 'why': 'test'})
    for metric in man['end_to_end'] + man['per_layer']:
        if CELL in metric.get('workloads', ()):
            metric['workloads'].append('toy_swa_train')
    (tmp / 'BENCHMARK.json').write_text(json.dumps(man))
    return Manifest(bench_dir=str(bench), manifest_path=str(tmp / 'BENCHMARK.json')), str(tmp / 'scratch')


def _run(toy, seconds, **kw):
    m, scratch = toy
    cell = m.cell('toy_swa_train')
    lines = []
    record = runner_module(cell['runner']).run(cell, m.config(cell['config']), seed=2 ** 31 + 11, seconds=seconds, trace=False,
                                               process_start=time.perf_counter(), scratch=scratch, log=lines.append, **kw)
    return record, lines


@pytest.fixture(scope='module')
def sound(toy):
    return _run(toy, 0.4, control_precision='float8')


def test_the_new_runner_runs_a_cell_added_by_files_and_prints_the_contracts_line(toy, sound):
    from benchmarks import run as bench_run
    record, lines = sound
    assert record['correct'] and record['failed'] == 0 and record['attempted'] > 0 and record['compiles_in_window'] == 0
    assert record['runner'] == 'train' and record['batch_size'] == 8 and record['lm']['seq_len'] == 32
    compared = {l.split()[1].rstrip(':') for l in lines if l.startswith('check ')}
    assert {'loss_gap_step3', 'first_grad_norm_gap', 'param_change_norm_gap', 'feed_repeated_rows', 'feed_targets_off',
            'feed_negative_ids', 'moe_dropped_slots', 'step_counters_missing', 'route_agreement', 'first_loss',
            'compiles_in_window'} <= compared
    assert 'ema_change_norm_gap' not in compared and record['numbers']['route_agreement'] == 1.0
    steps = record['steps']
    assert all(len(record['counters'][k]) == steps for k in ('moe.local_slots', 'moe.load_max', 'moe.dropped_slots', 'lm.tokens',
                                                             'attn.full_blocks', 'attn.window_blocks'))
    assert set(record['counters']['lm.tokens']) == {8 * 32} and set(record['counters']['moe.dropped_slots']) == {0}
    assert set(record['counters']['attn.full_blocks']) == {8 * 10} and set(record['counters']['attn.window_blocks']) == {8 * 3 * 7}
    device = {'platform': 'cpu', 'kind': 'cpu', 'count': 1}
    plain = bench_run.result_line(toy[0], 'toy_swa_train', record, device, trace=False)
    assert set(plain['metrics']) == {'train_img_per_s', 'setup_s'} and plain['correct']
    # the traced line: the readers the other cells have read this run, the new ones read its counters and scopes
    scopes = {'scope_s': {'swa.attn.core_window': 0.12, 'swa.attn.core_full': 0.08, 'swa.attn.proj': 0.05, 'glm.moe.experts': 0.04,
                          'glm.moe.route': 0.03, 'glm.head_loss': 0.05, 'glm.embed': 0.001},
              'busy_s': 0.5, 'unscoped': [['fusion', 0.05]]}
    traced = dict(record, device_kind='TPU v5 lite', trace={
        'busy_s': 0.5, 'window_s': 1.0, 'idle_share': 0.5, 'work': 5, 'idle_total_s': 0.5, 'scopes': scopes,
        'breakdown': {'device_ops': [], 'idle_gaps': []}})
    line = json.loads(json.dumps(bench_run.result_line(toy[0], 'toy_swa_train', traced, device, trace=True)))
    got = {k: v['value'] for k, v in line['metrics'].items()}
    assert set(METRICS) | {'step_mfu.train'} <= set(got) and not (NOT_ITS | set(COUNTED)) & set(got)
    assert {'step_device_ms.train', 'step_wall_ms.train', 'dispatch_host_ms.train', 'input_host_ms.train', 'step_call_ms.train',
            'device_idle_share.train', 'input_batch_wait_ms.train', 'loop_bookkeeping_ms.train', 'setup_compile_s'} <= set(got)
    assert got['attn_device_ms.train'] == pytest.approx(50.0) and got['moe_device_ms.train'] == pytest.approx(14.0)
    assert got['moe_route_device_ms.train'] == pytest.approx(6.0)
    assert record['needed_macs'] == swa_lm_flops.forward_macs(TOY_SIZES, 32, 8, sum(record['counters']['moe.local_slots']) / steps)
    assert record['needed_step_flops'] == swa_lm_flops.train_flops(record['needed_macs']) and record['lm']['expert_layers'] == 4
    # the same numbers as data, beside their limits: what the result line ends with
    assert set(record['checks']) == compared and all(c['ok'] for c in record['checks'].values())
    slots = sum(record['counters']['moe.local_slots']) / steps
    macs = swa_lm_flops.forward_macs(TOY_SIZES, 32, 8, slots)
    assert got['step_mfu.train'] == pytest.approx(100 * swa_lm_flops.train_flops(macs) / 0.1 / 197e12)
    assert got['attn_full_core_mfu.train'] == pytest.approx(100 * 6 * macs['attn_core_full'] / 0.016 / 197e12)
    assert got['attn_window_core_mfu.train'] == pytest.approx(100 * 6 * macs['attn_core_window'] / 0.024 / 197e12)
    assert got['attn_proj_mfu.train'] == pytest.approx(100 * 6 * macs['attn_proj'] / 0.01 / 197e12)
    assert got['moe_experts_mfu.train'] == pytest.approx(100 * 6 * macs['moe_experts'] / 0.008 / 197e12)
    # tiles of 8 x 8 (from the full cores' own count): 228 needed pairs a window layer in 7 tiles of 64
    assert swa_lm_readers.block_side(traced) == pytest.approx(8.0) and swa_lm_flops.window_pairs(32, 8) == 228
    assert got['attn_window_block_fill.train'] == pytest.approx(100 * 228 / (7 * 64))
    table = device_scopes.scope_table(traced, swa_lm_readers.SCOPE_PARTS)
    assert any(l.startswith('device scopes cover 74.2 %') for l in table)
    assert any(l.startswith('device scope swa.attn.core_window: 24.00 ms a step, 24.0 % of busy, ') for l in table)
    # the two readings that are no metric, as the free text a traced run prints
    said = {l.split()[1].rstrip(':'): float(l.split()[2]) for l in lm_readers.lines(traced)}
    assert list(said) == COUNTED and said['moe_load_max_over_mean.train'] >= 1.0
    assert said['moe_slots_per_expert.train'] == pytest.approx(slots / (2 * 4), rel=1e-5)
    assert all('nothing to read' in l for l in lm_readers.lines({}))
    # a metric that lists both cells reads the GLM cell's record through the same file, and asks for no family
    glm = {n: toy[0].reader(n)(GLM_RECORD) for n in BOTH}
    assert glm['moe_route_device_ms.train'] == pytest.approx(6.0) and glm['moe_device_ms.train'] == pytest.approx(14.0)
    assert glm['moe_experts_mfu.train'] == pytest.approx(100 * 6 * 1e9 / 0.008 / 197e12)
    assert lm_readers.slots_per_expert(dict(GLM_RECORD, lm=dict(GLM_RECORD['lm'], expert_layers=3))) == pytest.approx(750 / 6)
    # and both definitions of the memory peak, until one is chosen
    assert any(l.startswith('memory_peak_bytes: ') and 'peaks.memory_peak_bytes' in l for l in lines)
    assert record['memory_peak_bytes'] <= record['memory_peak_bytes_summed']


@pytest.mark.parametrize('name', OWN + BOTH)
def test_a_new_reader_returns_none_where_there_is_nothing_to_read(name):
    """A parent without the scopes and counters, an image cell's run, an empty record: no value, no raise. The GLM
    cell's record: nothing for this family's own five, a number for what both have."""
    read = (swa_lm_readers.READERS if name in OWN else lm_readers.READERS)[name].read
    assert read({}) is None and (read(GLM_RECORD) is None) == (name in OWN)
    assert read({'runner': 'train', 'steps': 3, 'sizes': {'embed_dim': 768}, 'device_kind': 'TPU v5 lite',
                 'trace': {'busy_s': 0.5, 'window_s': 1.0, 'idle_share': 0.5, 'work': 5}}) is None
    # this family's sizes and nothing measured: still nothing
    assert read({'runner': 'train', 'sizes': TOY_SIZES, 'device_kind': 'TPU v5 lite', 'lm': {'seq_len': 32, 'sequences': 8}}) is None


def test_the_float8_control_is_not_correct(toy, sound):
    record, _ = sound
    limits = toy[0].config('toy_swa')['limits']['swa_lm_train']
    numbers = lambda d: {k: (v, '') for k, v in d.items() if k != 'route_agreement'}  # noqa: E731
    assert check.judge(numbers(record['numbers']), limits, out=lambda s: None)
    assert not check.judge(numbers(record['control_numbers']), limits, out=lambda s: None)
    assert record['control_correct'] is False and record['control_numbers']['first_grad_norm_gap'] > 10 * limits['first_grad_norm_gap']
    assert any(l.startswith('control float8 check ') and l.split('(')[0].rstrip().endswith('OVER') for l in sound[1])


def test_a_step_that_returns_its_state_unchanged_is_not_correct(toy):
    import jax.numpy as jnp
    stuck = lambda task, batch, lr, step=0: {'loss': jnp.float32(5.5), 'grad_norm': jnp.float32(1.0)}  # noqa: E731
    record, lines = _run(toy, 0.2, inner_step=stuck)
    over = {l.split()[1].rstrip(':') for l in lines if l.startswith('check ') and l.split('(')[0].rstrip().endswith('OVER')}
    assert not record['correct'] and {'param_change_norm_gap', 'first_grad_norm_gap', 'step_counters_missing'} <= over
    assert record['numbers']['param_change_norm_gap'] == pytest.approx(1.0) and 'attn.window_blocks' not in record['counters']


def test_device_time_is_reduced_by_the_families_scopes_too():
    names = swa_lm_readers.declared_scopes()
    assert names >= device_scopes.declared_scopes() | {'swa.attn.proj', 'swa.attn.core_full', 'swa.attn.core_window'}
    assert set(swa_lm_readers.SCOPE_PARTS) <= names and set(device_scopes.SCOPE_PARTS) <= device_scopes.declared_scopes()
    of = lambda op: device_scopes.scope_of(op, names)  # noqa: E731
    assert of('jit(train_step)/transpose(jvp(checkpoint))/swa.attn.core_window/vmap(jit(_splash_attention))/pallas_call') == 'swa.attn.core_window'
    assert of('jit(train_step)/jvp(swa.attn.proj)/dot_general') == 'swa.attn.proj' and of('jit(train_step)/adamw/mul') is None
    hlo = '\n'.join([
        '  %fusion = bf16[2048,2048]{1,0} fusion(%p), kind=kOutput, calls=%fc, metadata={op_name="jit(f)/jvp(swa.attn.proj)/dot_general" source_file="x.py"}',
        '  %ragged-dot-none.3 = bf16[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}',
        '  %splash_mqa_fwd.6 = (f32[2,8]{1,0}) custom-call(%q), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={',
        '"xprof_metadata":"{\\"block_q\\": 1024}"',
        '}}, metadata={op_name="jit(f)/jvp(swa.attn.core_full)/vmap(jit(_splash_attention))/pallas_call" stack_frame_id=2}, backend_config={}',
        '  %mul.2 = f32[] multiply(%a, %b)'])
    assert device_scopes.instruction_scopes(hlo, names) == {'fusion': 'swa.attn.proj', 'ragged-dot-none.3': 'glm.moe.experts',
                                                            'splash_mqa_fwd.6': 'swa.attn.core_full'}
    # on the recorded chip trace: its fusions under one core's scope, its copies under the other's; together the busy time
    from benchmarks.harness import trace
    path = os.path.join(BENCH_DIR, 'fixtures', 'toy_matmuls.xplane.pb')
    seen = {device_scopes.instruction_of(n) for ops in trace.read_planes(path)[0].values() for n, _, _ in ops}
    hlo = '\n'.join(f'  %{n} = bf16[2,2]{{1,0}} op(%p), metadata={{op_name="jit(f)/{"jvp(swa.attn.core_window)" if n.startswith("fusion") else "swa.attn.core_full"}/x"}}'
                    for n in sorted(seen))
    got = device_scopes.reduce_scopes(path, hlo, names)
    want = load_json(os.path.join(BENCH_DIR, 'fixtures', 'toy_matmuls.expected.json'))
    assert got['busy_s'] == pytest.approx(want['busy_s'], rel=1e-9) and got['unscoped'] == []
    assert got['scope_s']['swa.attn.core_window'] > 100 * got['scope_s']['swa.attn.core_full'] > 0
    assert got['scope_s']['swa.attn.core_window'] + got['scope_s']['swa.attn.core_full'] == pytest.approx(got['busy_s'], rel=1e-6)
    # a real step program's compiled text names the scopes (the CPU's here; the chip's in a traced run)
    import jax
    import timm_tpu
    from flax import nnx
    model = timm_tpu.create_model('smallthinker_toy', seed=0)
    graphdef, state = nnx.split(model)
    ids = jax.numpy.zeros((1, 32), 'int32')
    text = jax.jit(lambda st: nnx.merge(graphdef, st)(ids)).lower(state).compile().as_text()
    assert {'glm.embed', 'swa.attn.proj', 'swa.attn.core_full', 'swa.attn.core_window', 'glm.moe.route'} <= set(
        device_scopes.instruction_scopes(text, names).values())
