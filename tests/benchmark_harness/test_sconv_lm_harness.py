"""The short-convolution language-model cell's part of the benchmark (`sconv_lm_train_runner.py`, `sconv_lm_flops.py`,
`sconv_lm_readers.py`, the configuration and the cell ISSUE 43 brings), at `lfm2_moe_toy` size on the CPU. One file,
like its neighbours.

Six readings are metrics of `BENCHMARK.json` (`sconv_lm_readers.READERS`: three of the short convolution, three of the
dense SwiGLU and the head, which the GLM cell lists too); six accepted ones (`moe_*` of
`lm_readers.py`, `attn_*` of `swa_lm_readers.py`) read this family's records by the scopes and parts they name and
list its cell; the whole step's share of the peak is `step_mfu.train`'s. What the manifest must have is held as a
SUBSET of what it has, and the cell's place as "after the cells before it", never by count or by position from the
end: a later PR adds cells and metrics and may not edit this file.
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

from benchmarks.harness import check, device_scopes, lm_readers, sconv_lm_flops, sconv_lm_readers, swa_lm_readers  # noqa: E402
from benchmarks.harness.manifest import BENCH_DIR, Manifest, load_json, runner_module  # noqa: E402

CELL, CONFIG, BEFORE = 'lfm2_8b_a1b_ep4_train_8k', 'lfm2_8b_a1b_ep4', 'evabyte_6b5_hp2_train_16k'
SCONV = ['sconv_device_ms.train', 'sconv_proj_mfu.train', 'sconv_mix_hbm_share.train']
WITH_GLM = ['dense_ffn_device_ms.train', 'dense_ffn_mfu.train', 'head_device_ms.train']    # scopes and parts the GLM family has too
OWN = SCONV + WITH_GLM
GLM_CELL = 'glm47_flash_ep8_train_8k'
SHARED = ['moe_route_device_ms.train', 'moe_device_ms.train', 'moe_experts_mfu.train', 'attn_device_ms.train',
          'attn_proj_mfu.train', 'attn_full_core_mfu.train']                    # accepted metrics that take the cell
EVERY_TRAINING_CELLS = ['step_mfu.train', 'step_device_ms.train', 'step_wall_ms.train', 'dispatch_host_ms.train',
                        'input_host_ms.train', 'device_idle_share.train', 'hbm_peak_gb.train', 'step_call_ms.train',
                        'input_batch_wait_ms.train', 'loop_bookkeeping_ms.train', 'step_update_device_ms.train',
                        'step_scope_cover.train']
NOT_ITS = {'input_prepare_ms.train', 'input_decode_busy_share.train', 'mla_device_ms.train', 'mla_core_mfu.train',
           'attn_window_core_mfu.train', 'attn_window_block_fill.train', 'attn_bd_core_mfu.train', 'eva_device_ms.train',
           'ffn_device_ms.train'}
TOY_SIZES = dict(vocab_held=256, hidden_size=64, num_hidden_layers=5, layer_types=['conv', 'full_attention', 'conv', 'conv', 'conv'],
                 num_attention_heads=4, num_key_value_heads=2, head_dim=16, conv_L_cache=3, intermediate_size=160,
                 num_dense_layers=1, moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2, experts_held=2,
                 expert_offset=0, routed_scaling_factor=1.0, rope_theta=1e6, norm_eps=1e-5)
# float32 on both sides: summation order only (Adam's division makes 1e-4 of a change norm); float8 operands
# move every number by 1e-2 and more
TOY_LIMITS = {'loss_gap': 2e-4, 'first_grad_norm_gap': 2e-4, 'param_change_norm_gap': 2e-3, 'ema_change_norm_gap': 2e-3}
EVA_RECORD = {'runner': 'train', 'steps': 3, 'device_kind': 'TPU v5 lite', 'lm': {'seq_len': 128, 'sequences': 8},
              'sizes': dict(vocab_size=320, hidden_size=64, intermediate_size=160, num_hidden_layers=2, heads_held=4, head_dim=16,
                            window_size=32, chunk_size=4, num_pred_heads=8),
              'counters': {'attn.eva_blocks': [320], 'lm.tokens': [1024]}, 'needed_macs': {'ffn': 4e9, 'attn_core': 1e9},
              'trace': {'busy_s': 0.5, 'window_s': 1.0, 'idle_share': 0.5, 'work': 5, 'scopes': {
                  'scope_s': {'evabyte.ffn': 0.2, 'evabyte.attn.core': 0.03}, 'busy_s': 0.5, 'unscoped': []}}}
# a traced record of this family written by hand: 5 traced steps, seconds under each scope
HAND = {'runner': 'train', 'steps': 3, 'device_kind': 'TPU v5 lite', 'lm': {'seq_len': 32, 'sequences': 8, 'expert_layers': 4},
        'sizes': TOY_SIZES, 'counters': {'moe.local_slots': [500, 524], 'moe.load_max': [80, 90], 'moe.dropped_slots': [0, 0],
                                         'attn.full_blocks': [80, 80], 'sconv.rows': [1024, 1024]},
        'needed_macs': {'sconv_proj': 2e9, 'attn_proj': 1e9, 'attn_core_full': 4e9, 'moe_experts': 3e9, 'moe_route': 1e7,
                        'dense_ffn': 2.5e9, 'head': 1.5e9},
        'trace': {'busy_s': 0.5, 'window_s': 1.0, 'idle_share': 0.5, 'work': 5, 'scopes': {
            'scope_s': {'sconv.proj': 0.05, 'sconv.mix': 0.0005, 'swa.attn.proj': 0.03, 'swa.attn.core_full': 0.1,
                        'glm.moe.route': 0.04, 'glm.moe.experts': 0.06, 'glm.dense_ffn': 0.05, 'glm.head_loss': 0.03},
            'busy_s': 0.5, 'unscoped': []}}}


def test_the_manifest_has_the_configuration_the_cell_and_its_readers_entries():
    m = Manifest()
    names = [x['name'] for x in m.data['per_layer']]
    assert set(sconv_lm_readers.READERS) == set(OWN) <= set(names)
    assert CELL in m.cells and CONFIG in {c['name'] for c in m.data['configs']} and m.data['run_seconds'] == 20
    cells = [w['name'] for w in m.data['workloads']]
    assert cells.index(CELL) > cells.index(BEFORE)                                         # after the cells before it
    held = set(m.metrics_of(CELL, 'per_layer'))
    assert set(OWN) | set(SHARED) | set(EVERY_TRAINING_CELLS) <= held and not NOT_ITS & held   # a subset, never a count
    assert m.metrics_of(CELL, 'end_to_end') == ['train_img_per_s', 'setup_s']
    assert not set(OWN) & set(m.metrics_of(BEFORE, 'per_layer'))
    assert set(WITH_GLM) <= set(m.metrics_of(GLM_CELL, 'per_layer')) and not set(SCONV) & set(m.metrics_of(GLM_CELL, 'per_layer'))
    # wherever every other cell that trains through `train.main` is listed, this one is, after them; and on the six
    # accepted lists it joins, after the cells that were there
    for metric in m.data['end_to_end'] + m.data['per_layer']:
        listed = metric.get('workloads', [])
        if {'vit_b16_train', 'convnext_b_train', 'glm47_flash_ep8_train_8k', 'smallthinker_21b_ep8_train_16k'} <= set(listed):
            assert CELL in listed and listed.index(CELL) > listed.index('smallthinker_21b_ep8_train_16k'), metric['name']
        if metric['name'] in SHARED:
            assert CELL in listed and listed.index(CELL) > 0, metric['name']
    shares = [x['name'] for x in m.data['per_layer'] if x['layer'] == 'step' and 'mfu' in x['name'] and CELL in x.get('workloads', [CELL])]
    assert shares == ['step_mfu.train']                                                    # one share of the whole step's peak
    for name, r in sconv_lm_readers.READERS.items():           # what their entries say
        entry = sconv_lm_readers.entry(name, [CELL])
        assert entry == {'name': name, 'unit': r.unit, 'better': r.better, 'source': r.source, 'layer': r.layer,
                         'moves': 'train_img_per_s', 'workloads': [CELL]}
        assert dict(m.per_layer[name], workloads=[CELL]) == entry and CELL in m.per_layer[name]['workloads']
        assert r.layer == {'sconv': 'short convolution', 'dense': 'feed-forward', 'head': 'step'}[name.split('_')[0]] and r.source == 'device_trace'
        assert m.per_layer[name]['workloads'] == ([CELL] if name in SCONV else [GLM_CELL, CELL])
        assert (r.unit, r.better) == (('ms', 'lower') if name.endswith('_ms.train') else ('%', 'higher'))
        assert callable(m.reader(name))
    cell, config = m.cell(CELL), m.config(CONFIG)
    assert cell['runner'] == 'sconv_lm_train' and cell['chips'] == 1 and m.cells[CELL]['traffic'] == 'train_token_stream'
    stream = cell['traffic']['token_stream']
    assert (stream['name'], stream['tokens'], stream['validation_tokens'], stream['data_seed']) == ('uniform_16384_8m', 8_388_608, 32768, 20261003)
    assert cell['traffic']['warmup_steps'] == 6 and stream['tokens'] // 8192 == 1024        # 256 steps of 4 an epoch
    assert {'source', 'published', 'deployment', 'reduced', 'reduced_why', 'assumed', 'precision', 'recipe', 'sizes', 'limits',
            'limits_why'} <= set(config)
    assert config['reduced'] == ['num_hidden_layers', 'num_dense_layers', 'num_experts', 'vocab_size'] == list(config['reduced_why'])
    assert config['reduced'] == [c for c in m.data['configs'] if c['name'] == CONFIG][0]['reduced']
    assert '507,820,160 parameters x 16 B = 8.13 GB' in config['reduced_why']['num_hidden_layers'] and '4 chips' in config['deployment']
    assert config['train_args'] == '-b 4 --amp --opt adamw --opt-betas 0.9 0.95 --weight-decay 0.1 --clip-grad 1.0 ' \
                                   '--grad-checkpointing --dataset tokens --seq-len 8192'.split()
    assert {'tie_word_embeddings', 'weights', 'expert_bias', 'norm_eps_in_topk', 'conv_padding', 'qk_norm', 'recipe', 'dense_width'} <= set(config['assumed'])
    path = '/opt/skills/guides/model-configs/architectures.jsonl'
    for row in [json.loads(line) for line in open(path)] if os.path.exists(path) else []:
        if row['name'] == 'LFM2-8B-A1B':                     # every published number under its key, but the four reduced
            assert config['source'] == row['source_url'] == [c for c in m.data['configs'] if c['name'] == CONFIG][0]['source']
            off = {k for k, v in row['config'].items() if config.get(k, 'missing') != v}
            assert off == set(config['reduced']) == set(config['published']) and all(
                config['published'][k] == row['config'][k] for k in off)
            assert config['layer_types'] == row['config']['layer_types'] and len(config['layer_types']) == 24   # kept whole
    sizes = config['sizes']
    assert sizes['layer_types'] == config['layer_types'][1:6] == ['conv', 'full_attention', 'conv', 'conv', 'conv']
    assert (sizes['num_hidden_layers'], sizes['num_dense_layers'], sizes['experts_held'], sizes['vocab_held']) == (5, 1, 8, 16384) == (
        config['num_hidden_layers'], config['num_dense_layers'], config['num_experts'], config['vocab_size'])
    assert sizes['num_experts'] == config['published']['num_experts'] == 32 and sizes['expert_offset'] == 0
    assert all(sizes[k] == config[k] for k in ('hidden_size', 'intermediate_size', 'moe_intermediate_size', 'num_attention_heads',
                                               'num_key_value_heads', 'num_experts_per_tok', 'conv_L_cache', 'norm_eps',
                                               'rope_theta', 'routed_scaling_factor'))
    assert sizes['head_dim'] * config['num_attention_heads'] == config['hidden_size'] and sizes['head_dim'] == 64
    assert set(config['limits']['sconv_lm_train']) == set(TOY_LIMITS) and set(config['limits_why']) >= set(TOY_LIMITS) | {'route_agreement_min'}
    assert 0.9 <= config['limits_lm']['route_agreement_min'] < 1 and len(config['source']) <= 200
    # the held and the published parameters, from the reference's own shapes: ISSUE 43's table
    from benchmarks.reference import lfm2_moe
    count = lambda s: sum(math.prod(shape) for shape, _ in lfm2_moe.init_spec(s).values())  # noqa: E731
    assert count(sizes) == 507_820_160
    assert count(dict(sizes, num_hidden_layers=24, layer_types=config['layer_types'], num_dense_layers=2, experts_held=32, vocab_held=65536)) == 8_339_929_856


def test_needed_operations_and_bytes_are_the_issues_arithmetic():
    sizes = Manifest().config(CONFIG)['sizes']
    assert sconv_lm_flops.layer_kinds(sizes) == (4, 1, 1, 4) and sconv_lm_flops.causal_pairs(8192) == 33_558_528
    macs = sconv_lm_flops.forward_macs(sizes, 8192, 4, 131072)
    assert macs['sconv_proj'] == 4 * 32768 * 16_777_216 == pytest.approx(2.199e12, rel=1e-3)
    assert macs['moe_experts'] == 131072 * 11_010_048 == pytest.approx(1.443e12, rel=1e-3)
    assert macs['dense_ffn'] == 32768 * 44_040_192 == pytest.approx(1.443e12, rel=1e-3)
    assert macs['head'] == 32768 * 16384 * 2048 == pytest.approx(1.100e12, rel=1e-3)
    assert macs['attn_core_full'] == 4 * 33_558_528 * 32 * 128 == pytest.approx(5.498e11, rel=1e-3)
    assert macs['attn_proj'] == 32768 * 10_485_760 == pytest.approx(3.436e11, rel=1e-3) and macs['moe_route'] == pytest.approx(8.6e9, rel=2e-3)
    total = sum(macs.values())
    assert total == pytest.approx(7.087e12, rel=1e-3) and sconv_lm_flops.train_flops(macs) == pytest.approx(42.5e12, rel=1e-3)
    shares = {k: round(100 * v / total, 1) for k, v in macs.items()}
    assert shares == {'sconv_proj': 31.0, 'attn_proj': 4.8, 'attn_core_full': 7.8, 'dense_ffn': 20.4, 'moe_route': 0.1,
                      'moe_experts': 20.4, 'head': 15.5}
    # the middle's bytes: 2048 channels x 2 B x (4 forward + 7 backward) = 45,056 B a position and layer; 5.9 GB a step
    assert sconv_lm_flops.mix_bytes(1) == 45_056 and sconv_lm_flops.mix_bytes(4 * 32768) == pytest.approx(5.9e9, rel=2e-3)
    assert sconv_lm_flops.mix_bytes(4 * 32768) / 819e9 == pytest.approx(7.2e-3, rel=5e-3)
    runner, config = runner_module('sconv_lm_train'), Manifest().config(CONFIG)
    record = {'lm': {'seq_len': 8192, 'sequences': 4}, 'counters': {'moe.local_slots': [131072.0]}}
    assert runner.needed_work(config, record) == {'needed_macs': macs, 'needed_step_flops': 6 * total}
    assert runner.needed_work(config, dict(record, counters={})) == {}
    # a held expert's load: T / 8 of a step's tokens a layer, a quarter of all slots
    assert 131072 / (8 * 4) == 4096 and 32768 * 4 * 8 // 32 == 32768


def test_the_runs_bias_is_seeded_float32_and_goes_to_every_layer_that_routes():
    import timm_tpu
    runner = runner_module('sconv_lm_train')
    a, b, c = runner.expert_bias(2 ** 31 + 5, 32), runner.expert_bias(2 ** 31 + 5, 32), runner.expert_bias(6, 32)
    assert a == b != c and len(a) == 32 and all(isinstance(x, float) and abs(x) <= 0.05 for x in a) and max(map(abs, a)) > 0.03
    assert [float(np.float32(x)) for x in a] == a                                           # exact in float32: both sides get the same numbers
    model = timm_tpu.create_model('lfm2_moe_toy', seed=0)
    assert runner.place_expert_bias(model, a[:8]) == 4
    assert all(np.allclose(np.asarray(blk.mlp.score_bias[...]), a[:8], atol=0) for blk in model.blocks[1:])


@pytest.fixture(scope='module')
def toy(tmp_path_factory):
    """A copy of the benchmark's data with a toy configuration and cell of the new runner added as files."""
    tmp = tmp_path_factory.mktemp('toysconv')
    bench = tmp / 'benchmarks'
    for d in ('configs', 'workloads', 'layer_metrics'):
        shutil.copytree(os.path.join(BENCH_DIR, d), bench / d)
    man = load_json(os.path.join(ROOT, 'BENCHMARK.json'))
    (bench / 'configs' / 'toy_sconv.json').write_text(json.dumps({
        'name': 'toy_sconv', 'source': 'test', 'model': 'lfm2_moe_toy', 'reference': 'lfm2_moe', 'sizes': TOY_SIZES,
        'recipe': {'clip_grad': 1.0, 'weight_decay': 0.1, 'betas': [0.9, 0.95]},
        'train_args': ['-b', '8', '--opt', 'adamw', '--opt-betas', '0.9', '0.95', '--weight-decay', '0.1', '--clip-grad', '1.0',
                       '--grad-checkpointing', '--dataset', 'tokens', '--seq-len', '32'],
        'reduced': [], 'reference_block_q': 8, 'limits': {'sconv_lm_train': TOY_LIMITS}, 'limits_lm': {'route_agreement_min': 0.999}}))
    (bench / 'workloads' / 'toy_sconv_train.json').write_text(json.dumps({
        'config': 'toy_sconv', 'runner': 'sconv_lm_train', 'chips': 1,
        'traffic': {'warmup_steps': 3, 'token_stream': {'name': 'toy', 'data_seed': 1, 'tokens': 32 * 8 * 400,
                                                        'validation_tokens': 32 * 8}}}))
    man['configs'].append({'name': 'toy_sconv', 'source': 'test', 'file': 'benchmarks/configs/toy_sconv.json', 'reduced': [], 'why': 'test'})
    man['workloads'].append({'name': 'toy_sconv_train', 'config': 'toy_sconv', 'traffic': 'toy_tokens', 'chips': 1, 'why': 'test'})
    for metric in man['end_to_end'] + man['per_layer']:
        if CELL in metric.get('workloads', ()):
            metric['workloads'].append('toy_sconv_train')
    (tmp / 'BENCHMARK.json').write_text(json.dumps(man))
    return Manifest(bench_dir=str(bench), manifest_path=str(tmp / 'BENCHMARK.json')), str(tmp / 'scratch')


def _run(toy, seconds, **kw):
    m, scratch = toy
    cell = m.cell('toy_sconv_train')
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
    assert record['runner'] == 'train' and record['batch_size'] == 8 and record['lm']['seq_len'] == 32 and record['lm']['expert_layers'] == 4
    compared = {l.split()[1].rstrip(':') for l in lines if l.startswith('check ')}
    assert {'loss_gap_step3', 'first_grad_norm_gap', 'param_change_norm_gap', 'route_agreement', 'feed_repeated_rows', 'feed_targets_off',
            'feed_negative_ids', 'moe_dropped_slots', 'step_counters_missing', 'expert_bias_unplaced', 'first_loss',
            'compiles_in_window'} <= compared and 'ema_change_norm_gap' not in compared
    # the run's bias reached both sides: under it the program and the reference choose alike, and not as without it
    bias = record['lm']['expert_bias']
    assert len(bias) == 8 and record['numbers']['route_agreement'] == 1.0 and record['checks']['expert_bias_unplaced']['value'] == 0
    assert any(l.startswith('expert_bias: 8 values uniform in +-0.05') for l in lines)
    steps = record['steps']
    assert all(len(record['counters'][k]) == steps for k in ('lm.tokens', 'attn.full_blocks', 'sconv.rows', 'moe.local_slots'))
    assert set(record['counters']['lm.tokens']) == {8 * 32} and set(record['counters']['attn.full_blocks']) == {8 * 10}
    assert set(record['counters']['sconv.rows']) == {4 * 8 * 32} and sum(record['counters']['moe.dropped_slots']) == 0
    first = record['followed']['program']['losses'][0]
    assert abs(first - (math.log(256) + 64 * 0.02 ** 2 / 2)) < 0.5 and record['checks']['first_loss']['ok']
    device = {'platform': 'cpu', 'kind': 'cpu', 'count': 1}
    plain = bench_run.result_line(toy[0], 'toy_sconv_train', record, device, trace=False)
    assert set(plain['metrics']) == {'train_img_per_s', 'setup_s'} and plain['correct']
    # the traced line: the readers every training cell has read this run; the three new ones and the six accepted
    # ones that list the cell read its counters, its scopes and its runner's operation table
    scopes = {'scope_s': {'sconv.proj': 0.12, 'sconv.mix': 0.02, 'swa.attn.proj': 0.03, 'swa.attn.core_full': 0.05,
                          'glm.dense_ffn': 0.06, 'glm.moe.route': 0.04, 'glm.moe.experts': 0.07, 'glm.head_loss': 0.03, 'glm.embed': 0.001},
              'busy_s': 0.5, 'unscoped': [['fusion', 0.05]]}
    traced = dict(record, device_kind='TPU v5 lite', trace={
        'busy_s': 0.5, 'window_s': 1.0, 'idle_share': 0.5, 'work': 5, 'idle_total_s': 0.5, 'scopes': scopes,
        'breakdown': {'device_ops': [], 'idle_gaps': []}})
    line = json.loads(json.dumps(bench_run.result_line(toy[0], 'toy_sconv_train', traced, device, trace=True)))
    got = {k: v['value'] for k, v in line['metrics'].items()}
    assert set(OWN) | set(SHARED) | {'step_mfu.train'} <= set(got) and not NOT_ITS & set(got)
    assert {'step_device_ms.train', 'step_wall_ms.train', 'dispatch_host_ms.train', 'input_host_ms.train', 'step_call_ms.train',
            'device_idle_share.train', 'input_batch_wait_ms.train', 'loop_bookkeeping_ms.train', 'setup_compile_s'} <= set(got)
    slots = sum(record['counters']['moe.local_slots']) / steps
    macs = sconv_lm_flops.forward_macs(TOY_SIZES, 32, 8, slots)
    assert record['needed_macs'] == macs and record['needed_step_flops'] == sconv_lm_flops.train_flops(macs)
    assert got['sconv_device_ms.train'] == pytest.approx(28.0) and got['attn_device_ms.train'] == pytest.approx(16.0)
    assert got['moe_route_device_ms.train'] == pytest.approx(8.0) and got['moe_device_ms.train'] == pytest.approx(22.0)
    assert got['step_mfu.train'] == pytest.approx(100 * sconv_lm_flops.train_flops(macs) / 0.1 / 197e12)
    assert got['sconv_proj_mfu.train'] == pytest.approx(100 * 6 * macs['sconv_proj'] / 0.024 / 197e12)
    assert got['attn_full_core_mfu.train'] == pytest.approx(100 * 6 * macs['attn_core_full'] / 0.010 / 197e12)
    assert got['attn_proj_mfu.train'] == pytest.approx(100 * 6 * macs['attn_proj'] / 0.006 / 197e12)
    assert got['moe_experts_mfu.train'] == pytest.approx(100 * 6 * macs['moe_experts'] / 0.014 / 197e12)
    assert got['sconv_mix_hbm_share.train'] == pytest.approx(100 * sconv_lm_flops.mix_bytes(4 * 8 * 32, 64) / 0.004 / 819e9)
    assert got['dense_ffn_device_ms.train'] == pytest.approx(12.0) and got['head_device_ms.train'] == pytest.approx(6.0)
    assert got['dense_ffn_mfu.train'] == pytest.approx(100 * 6 * macs['dense_ffn'] / 0.012 / 197e12)
    assert all(0 < got[n] < 100 for n in OWN + SHARED if n.endswith(('_mfu.train', '_share.train')))
    table = device_scopes.scope_table(traced, sconv_lm_readers.SCOPE_PARTS)
    assert any(l.startswith('device scopes cover 84.2 %') for l in table)
    assert any(l.startswith('device scope sconv.proj: 24.00 ms a step, 24.0 % of busy, ') for l in table)
    assert any(l.startswith('device scope sconv.mix: 4.00 ms a step, 4.0 % of busy') and 'of peak' not in l for l in table)
    # the two readings that stay free text read this family's record too
    assert lm_readers.READERS['moe_slots_per_expert.train'].read(traced) == pytest.approx(slots / (2 * 4))
    # the same numbers as data, beside their limits: what the result line ends with
    assert set(record['checks']) == compared and all(c['ok'] for c in record['checks'].values())
    assert any(l.startswith('memory_peak_bytes: ') and 'peaks.memory_peak_bytes' in l for l in lines)
    assert record['memory_peak_bytes'] <= record['memory_peak_bytes_summed']


@pytest.mark.parametrize('name', OWN + SHARED + ['step_update_device_ms.train', 'step_scope_cover.train'])
def test_every_reader_that_lists_the_cell_reads_a_hand_written_record_of_it_or_finds_nothing(name):
    """The six accepted metrics that take the cell read a hand-written traced record of it (toy sizes) as they read
    their own families'; the two of `step_scopes.py` read a trace file and the step program's kept text, which a
    hand-written record has not: nothing, and no raise."""
    read = Manifest().reader(name)
    value = read(HAND)
    if name.startswith('step_'):
        assert value is None and read({}) is None
        return
    want = {'sconv_device_ms.train': 10.1, 'sconv_proj_mfu.train': 100 * 6 * 2e9 / 0.01 / 197e12,
            'sconv_mix_hbm_share.train': 100 * sconv_lm_flops.mix_bytes(1024, 64) / 1e-4 / 819e9,
            'dense_ffn_device_ms.train': 10.0, 'dense_ffn_mfu.train': 100 * 6 * 2.5e9 / 0.01 / 197e12, 'head_device_ms.train': 6.0,
            'moe_route_device_ms.train': 8.0, 'moe_device_ms.train': 20.0, 'moe_experts_mfu.train': 100 * 6 * 3e9 / 0.012 / 197e12,
            'attn_device_ms.train': 26.0, 'attn_proj_mfu.train': 100 * 6 * 1e9 / 0.006 / 197e12,
            'attn_full_core_mfu.train': 100 * 6 * 4e9 / 0.02 / 197e12}[name]
    assert value == pytest.approx(want) and (name.endswith('_ms.train') or 0 < value < 100)


@pytest.mark.parametrize('name,want', [('dense_ffn_device_ms.train', 47.08), ('dense_ffn_mfu.train', 66.7), ('head_device_ms.train', 62.99)])
def test_the_dense_and_head_readers_read_the_glm_cells_record_too(name, want):
    """The GLM cell lists the three: its runner's operation table has the parts (`lm_flops.forward_macs`) and its step
    the scopes; the seconds are its ten traced steps' (PERF.md section 5: 47.08 and 62.99 ms a step)."""
    from benchmarks.harness import lm_flops
    m = Manifest()
    sizes = m.config('glm47_flash_ep8')['sizes']
    run = {'runner': 'train', 'device_kind': 'TPU v5 lite', 'sizes': sizes, 'needed_macs': lm_flops.forward_macs(sizes, 8192, 2, 40960),
           'trace': {'work': 10, 'scopes': {'scope_s': {'glm.dense_ffn': 0.4708, 'glm.head_loss': 0.6299}, 'busy_s': 8.4, 'unscoped': []}}}
    assert m.reader(name)(run) == pytest.approx(want, abs=0.05)


@pytest.mark.parametrize('name', OWN)
def test_a_new_reader_returns_none_where_there_is_nothing_to_read(name):
    """A parent without the scopes and counters, an image cell's run, another family's record, an empty one: no
    value, no raise. And a middle whose scope holds less than its work reads OVER 100, which the driver refuses: a
    program that lets the middle fuse into the products again is seen, not hidden."""
    read = sconv_lm_readers.READERS[name].read
    assert read({}) is None and read(EVA_RECORD) is None          # EvaByte's dense SwiGLU runs under its own scope
    assert read({'runner': 'train', 'steps': 3, 'sizes': {'embed_dim': 768}, 'device_kind': 'TPU v5 lite',
                 'trace': {'busy_s': 0.5, 'window_s': 1.0, 'idle_share': 0.5, 'work': 5}}) is None
    # this family's sizes and nothing measured: still nothing
    assert read({'runner': 'train', 'sizes': TOY_SIZES, 'device_kind': 'TPU v5 lite', 'lm': {'seq_len': 32, 'sequences': 8}}) is None
    fused_away = json.loads(json.dumps(HAND))
    fused_away['trace']['scopes']['scope_s']['sconv.mix'] = 1e-7       # the gates went into a product's fusion
    assert sconv_lm_readers.mix_hbm_share(fused_away) > 105 and sconv_lm_readers.mix_hbm_share(HAND) < 100


def test_the_float8_control_is_not_correct(toy, sound):
    record, lines = sound
    limits = toy[0].config('toy_sconv')['limits']['sconv_lm_train']
    numbers = lambda d: {k: (v, '') for k, v in d.items() if k != 'route_agreement'}  # noqa: E731
    assert check.judge(numbers(record['numbers']), limits, out=lambda s: None)
    assert not check.judge(numbers(record['control_numbers']), limits, out=lambda s: None)
    assert record['control_correct'] is False and record['control_numbers']['first_grad_norm_gap'] > 10 * limits['first_grad_norm_gap']
    assert any(l.startswith('control float8 check ') and l.split('(')[0].rstrip().endswith('OVER') for l in lines)
    # the conv leaves by name: float8 operands in the two products and the taps move their first gradient
    program, control, reference = (record['followed'][k]['first_grad_norms'] for k in ('program', 'control', 'reference'))
    for leaf in ('blocks.2.conv.taps', 'blocks.2.conv.in_proj.kernel', 'blocks.2.conv.out_proj.kernel'):
        assert abs(control[leaf] - reference[leaf]) > 20 * abs(program[leaf] - reference[leaf]), leaf


def test_a_step_that_returns_its_state_unchanged_is_not_correct(toy):
    import jax.numpy as jnp
    stuck = lambda task, batch, lr, step=0: {'loss': jnp.float32(5.5), 'grad_norm': jnp.float32(1.0)}  # noqa: E731
    record, lines = _run(toy, 0.2, inner_step=stuck)
    over = {l.split()[1].rstrip(':') for l in lines if l.startswith('check ') and l.split('(')[0].rstrip().endswith('OVER')}
    assert not record['correct'] and {'param_change_norm_gap', 'first_grad_norm_gap', 'step_counters_missing'} <= over
    assert record['numbers']['param_change_norm_gap'] == pytest.approx(1.0) and 'sconv.rows' not in record['counters']


def test_a_run_whose_bias_the_program_does_not_hold_is_not_correct(toy, monkeypatch):
    """The reference chooses under the run's bias; a program left at the zero buffer chooses otherwise."""
    runner = runner_module('sconv_lm_train')
    monkeypatch.setattr(runner, 'place_expert_bias', lambda model, bias: 0)
    record, lines = _run(toy, 0.2)
    assert not record['correct'] and not record['checks']['expert_bias_unplaced']['ok']
    assert record['numbers']['route_agreement'] < 0.9 and not record['checks']['route_agreement']['ok']


def test_a_program_without_the_family_fails_before_any_work(toy, monkeypatch, tmp_path):
    """What the parent commit does when the driver asks it for the new cell: the import fails at once, before the
    stream is written or `train.main` is entered."""
    m, _ = toy
    cell = m.cell('toy_sconv_train')
    monkeypatch.setitem(sys.modules, 'timm_tpu.models.lfm2_moe', None)
    with pytest.raises(ImportError):
        runner_module('sconv_lm_train').run(cell, m.config('toy_sconv'), seed=1, seconds=0.1, trace=False,
                                            process_start=time.perf_counter(), scratch=str(tmp_path / 's'), log=lambda s: None)
    assert not (tmp_path / 's').exists()


def test_device_time_is_reduced_by_the_familys_scopes_too():
    names = sconv_lm_readers.declared_scopes()
    assert names >= device_scopes.declared_scopes() | {'sconv.proj', 'sconv.mix'} and names == swa_lm_readers.declared_scopes()
    assert set(sconv_lm_readers.SCOPE_PARTS) <= names
    of = lambda op: device_scopes.scope_of(op, names)  # noqa: E731
    assert of('jit(train_step)/transpose(jvp(checkpoint))/sconv.mix/mul') == 'sconv.mix'
    assert of('jit(train_step)/jvp(sconv.proj)/dot_general') == 'sconv.proj' and of('jit(train_step)/adamw/mul') is None
    # a real program's compiled text names the scopes (the CPU's here; the chip's in a traced run)
    import jax
    import timm_tpu
    from flax import nnx
    model = timm_tpu.create_model('lfm2_moe_toy', seed=0)
    graphdef, state = nnx.split(model)
    ids = jax.numpy.zeros((1, 32), 'int32')
    text = jax.jit(lambda st: nnx.merge(graphdef, st)(ids)).lower(state).compile().as_text()
    assert {'glm.embed', 'sconv.proj', 'sconv.mix', 'swa.attn.proj', 'swa.attn.core_full', 'glm.dense_ffn', 'glm.moe.route',
            'glm.moe.experts'} <= set(device_scopes.instruction_scopes(text, names).values())
