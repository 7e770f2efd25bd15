"""The delta-rule language-model cell's part of the benchmark (`table_lm_train_runner.py`, the runner in its folded form,
`kda_lm_flops.py`, `kda_lm_readers.py`, the configuration and the cell ISSUE 47 brings), at `solar_open2_toy` size on the
CPU. One file, like its neighbours.

Four readings are metrics of `BENCHMARK.json` (`kda_lm_readers.READERS`); the accepted `moe_*` (`lm_readers.py`), `attn_*`
(`swa_lm_readers.py`) and `head_device_ms.train` (`sconv_lm_readers.py`) read this family's records by the scopes and parts
they name and list its cell. What the manifest must have is held as a SUBSET of what it has, and the cell's place as "after
the cells before it", never by count or by position from the end: a later PR adds cells and metrics and may not edit this file.
"""
import inspect
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

from benchmarks.harness import check, device_scopes, kda_lm_flops, kda_lm_readers, lm_readers, swa_lm_readers  # noqa: E402
from benchmarks.harness.manifest import BENCH_DIR, Manifest, load_json, runner_module  # noqa: E402

CELL, CONFIG, BEFORE, RUNNER = 'solar_open2_250b_ep40_train_8k', 'solar_open2_250b_ep40', 'lfm2_8b_a1b_ep4_train_8k', 'table_lm_train'
OWN = ['kda_device_ms.train', 'kda_proj_mfu.train', 'kda_core_mfu.train', 'kda_mix_hbm_share.train']
SHARED = ['moe_route_device_ms.train', 'moe_device_ms.train', 'moe_experts_mfu.train', 'attn_device_ms.train',
          'attn_proj_mfu.train', 'attn_full_core_mfu.train', 'head_device_ms.train']                 # accepted metrics that take the cell
EVERY_TRAINING_CELLS = ['step_device_ms.train', 'step_wall_ms.train', 'dispatch_host_ms.train', 'step_mfu.train',
                        'input_host_ms.train', 'device_idle_share.train', 'hbm_peak_gb.train', 'step_call_ms.train',
                        'input_batch_wait_ms.train', 'loop_bookkeeping_ms.train', 'step_update_device_ms.train',
                        'step_scope_cover.train']
NOT_ITS = {'input_prepare_ms.train', 'input_decode_busy_share.train', 'mla_device_ms.train', 'mla_core_mfu.train',
           'attn_window_core_mfu.train', 'attn_bd_core_mfu.train', 'eva_device_ms.train', 'ffn_device_ms.train',
           'sconv_device_ms.train', 'sconv_mix_hbm_share.train', 'dense_ffn_device_ms.train', 'dense_ffn_mfu.train'}
TOY_SIZES = dict(vocab_held=256, hidden_size=64, num_hidden_layers=4, gqa_layers=[0], num_attention_heads=8, num_key_value_heads=4,
                 head_dim=16, heads_held=4, head_offset=0, short_conv_kernel_size=4, gate_rank=8, moe_intermediate_size=32,
                 n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1, experts_held=2, expert_offset=0,
                 routed_scaling_factor=1.0, rms_norm_eps=1e-5)
TOY_FAMILY = {'model_module': 'timm_tpu.models.solar_open2', 'flops': 'kda_lm_flops', 'readers': 'kda_lm_readers',
              'own_counters': ['attn.full_blocks', 'kda.rows', 'kda.chunks'], 'expert_bias': {'experts_key': 'n_routed_experts'},
              'first_loss_head_std': 0.02}
# float32 on both sides: summation order only (Adam's division makes 1e-4 of a change norm); float8 operands
# move every number by 1e-2 and more
TOY_LIMITS = {'loss_gap': 2e-4, 'first_grad_norm_gap': 2e-4, 'param_change_norm_gap': 2e-3, 'ema_change_norm_gap': 2e-3}
# a traced record of this family written by hand: 5 traced steps, seconds under each scope
HAND = {'runner': 'train', 'steps': 3, 'device_kind': 'TPU v5 lite', 'lm': {'seq_len': 32, 'sequences': 8, 'expert_layers': 4},
        'sizes': TOY_SIZES, 'counters': {'moe.local_slots': [500, 524], 'moe.load_max': [80, 90], 'moe.dropped_slots': [0, 0],
                                         'attn.full_blocks': [80, 80], 'kda.rows': [768, 768], 'kda.chunks': [192, 192]},
        'needed_macs': {'kda_proj': 2e9, 'kda_core': 1e8, 'attn_proj': 1e9, 'attn_core_full': 4e9, 'moe_experts': 3e9, 'moe_route': 1e7,
                        'moe_shared': 2e9, 'head': 1.5e9},
        'trace': {'busy_s': 0.5, 'window_s': 1.0, 'idle_share': 0.5, 'work': 5, 'scopes': {
            'scope_s': {'kda.proj': 0.05, 'kda.mix': 0.0005, 'kda.core': 0.1, 'swa.attn.proj': 0.03, 'swa.attn.core_full': 0.1,
                        'glm.moe.route': 0.04, 'glm.moe.experts': 0.06, 'glm.moe.shared': 0.02, 'glm.head_loss': 0.03},
            'busy_s': 0.5, 'unscoped': []}}}


def test_the_manifest_has_the_configuration_the_cell_and_its_readers_entries():
    m = Manifest()
    names = [x['name'] for x in m.data['per_layer']]
    assert set(kda_lm_readers.READERS) == set(OWN) <= set(names)
    assert CELL in m.cells and CONFIG in {c['name'] for c in m.data['configs']} and m.data['run_seconds'] == 20
    cells = [w['name'] for w in m.data['workloads']]
    assert cells.index(CELL) > cells.index(BEFORE)                                         # after the cells before it
    held = set(m.metrics_of(CELL, 'per_layer'))
    assert set(OWN) | set(SHARED) | set(EVERY_TRAINING_CELLS) <= held and not NOT_ITS & held   # a subset, never a count
    assert m.metrics_of(CELL, 'end_to_end') == ['train_img_per_s', 'setup_s']
    assert not any(set(OWN) & set(m.metrics_of(c, 'per_layer')) for c in cells[:cells.index(CELL)])
    # wherever every other language-model cell that trains through `train.main` is listed, this one is, after them
    for metric in m.data['end_to_end'] + m.data['per_layer']:
        listed = metric.get('workloads', [])
        if {'glm47_flash_ep8_train_8k', 'smallthinker_21b_ep8_train_16k', 'evabyte_6b5_hp2_train_16k', BEFORE} <= set(listed):
            assert CELL in listed and listed.index(CELL) > listed.index(BEFORE), metric['name']
        if metric['name'] in SHARED:
            assert CELL in listed and listed.index(CELL) > 0, metric['name']
    shares = [x['name'] for x in m.data['per_layer'] if x['layer'] == 'step' and 'mfu' in x['name'] and CELL in x.get('workloads', [CELL])]
    assert shares == ['step_mfu.train']                                                    # one share of the whole step's peak
    for name, r in kda_lm_readers.READERS.items():             # what their entries say
        entry = kda_lm_readers.entry(name, [CELL])
        assert entry == {'name': name, 'unit': r.unit, 'better': r.better, 'source': r.source, 'layer': r.layer,
                         'moves': 'train_img_per_s', 'workloads': [CELL]}
        assert dict(m.per_layer[name], workloads=[CELL]) == entry and m.per_layer[name]['workloads'][0] == CELL
        assert r.layer == 'delta attention' and r.source == 'device_trace'
        assert (r.unit, r.better) == (('ms', 'lower') if name.endswith('_ms.train') else ('%', 'higher'))
        assert callable(m.reader(name))
    cell, config = m.cell(CELL), m.config(CONFIG)
    assert cell['runner'] == RUNNER and cell['chips'] == 1 and m.cells[CELL]['traffic'] == 'train_token_stream'
    stream = cell['traffic']['token_stream']
    assert (stream['name'], stream['tokens'], stream['validation_tokens'], stream['data_seed']) == ('uniform_24576_8m', 8_388_608, 32768, 20261004)
    assert cell['traffic']['warmup_steps'] == 6 and stream['tokens'] // 8192 == 1024        # 1024 steps of 1 an epoch
    assert {'source', 'published', 'deployment', 'reduced', 'reduced_why', 'assumed', 'precision', 'recipe', 'sizes', 'limits',
            'limits_why', 'family'} <= set(config)
    assert config['reduced'] == ['num_hidden_layers', 'n_routed_experts', 'num_attention_heads', 'num_key_value_heads',
                                 'vocab_size'] == list(config['reduced_why'])
    assert config['reduced'] == [c for c in m.data['configs'] if c['name'] == CONFIG][0]['reduced']
    assert '840,871,320 parameters x 16 B = 13.45 GB' in config['reduced_why']['num_hidden_layers'] and config['deployment'].startswith('40 chips share each layer')
    assert config['train_args'] == '-b 1 --amp --opt adamw --opt-betas 0.9 0.95 --weight-decay 0.1 --clip-grad 1.0 ' \
                                   '--grad-checkpointing --dataset tokens --seq-len 8192'.split()
    assert {'scoring_func', 'kda_conv_activation', 'kda_qk_norm', 'kda_gate_rank', 'kda_decay', 'kda_beta', 'kda_output_norm',
            'kda_num_kv_heads', 'gqa_gate', 'weights', 'expert_bias', 'recipe'} <= set(config['assumed'])
    assert all('Alternative' in config['assumed'][k] for k in ('scoring_func', 'kda_conv_activation', 'kda_gate_rank', 'kda_beta', 'gqa_gate'))
    path = '/opt/skills/guides/model-configs/architectures.jsonl'
    for row in [json.loads(line) for line in open(path)] if os.path.exists(path) else []:
        if row['name'] == 'Solar-Open2-250B':               # every published number under its key, but the five reduced
            assert config['source'] == row['source_url'] == [c for c in m.data['configs'] if c['name'] == CONFIG][0]['source']
            off = {k for k, v in row['config'].items() if config.get(k, 'missing') != v}
            assert off == set(config['reduced']) == set(config['published']) and all(
                config['published'][k] == row['config'][k] for k in off)
            assert config['linear_attn_config'] == row['config']['linear_attn_config'] and config['gqa_layers'] == row['config']['gqa_layers']   # kept whole
    sizes = config['sizes']
    assert (sizes['num_hidden_layers'], sizes['experts_held'], sizes['heads_held'], sizes['vocab_held']) == (4, 8, 8, 24576) == (
        config['num_hidden_layers'], config['n_routed_experts'], config['num_attention_heads'], config['vocab_size'])
    assert kda_lm_flops.kv_heads_held(sizes) == config['num_key_value_heads'] == 1 and sizes['gqa_layers'] == [0] == config['gqa_layers'][:1]
    assert all(sizes[k] == config['published'][k] for k in ('n_routed_experts', 'num_attention_heads', 'num_key_value_heads'))
    assert all(sizes[k] == config[k] for k in ('hidden_size', 'head_dim', 'moe_intermediate_size', 'num_experts_per_tok', 'n_shared_experts',
                                               'rms_norm_eps', 'routed_scaling_factor'))
    assert sizes['short_conv_kernel_size'] == config['linear_attn_config']['short_conv_kernel_size'] and sizes['head_dim'] == config['linear_attn_config']['head_dim']
    assert set(config['limits'][RUNNER]) == set(TOY_LIMITS) and set(config['limits_why']) >= set(TOY_LIMITS) | {'route_agreement_min', 'readings'}
    assert 0.9 <= config['limits_lm']['route_agreement_min'] < 1 and len(config['source']) <= 200
    # the held and the published parameters, from the reference's own shapes: ISSUE 47's counts
    from benchmarks.reference import solar_open2
    count = lambda s: sum(math.prod(shape) for shape, _ in solar_open2.init_spec(s).values())  # noqa: E731
    assert count(sizes) == 840_871_320
    assert count(dict(sizes, num_hidden_layers=48, gqa_layers=config['gqa_layers'], experts_held=320, heads_held=64, vocab_held=196608)) == 250_287_794_944


def test_the_folded_runner_names_no_family_outside_its_table():
    """ROADMAP D19: everything a family differs in comes from the configuration's `family` block; the runner's source
    holds no model's, scope's or counter's name of this family or another."""
    runner = runner_module(RUNNER)
    source = inspect.getsource(runner.run) + inspect.getsource(runner.needed_work) + inspect.getsource(runner.counting)
    for word in ('solar', 'kda', 'lfm2', 'sconv', 'glm', 'swa', 'evabyte', 'sdar', 'smallthinker', 'attn.', 'full_blocks'):
        assert word not in source.lower(), word
    family = Manifest().config(CONFIG)['family']
    assert family == {'model_module': 'timm_tpu.models.solar_open2', 'flops': 'kda_lm_flops', 'readers': 'kda_lm_readers',
                      'own_counters': ['attn.full_blocks', 'kda.rows', 'kda.chunks'], 'expert_bias': {'experts_key': 'n_routed_experts'},
                      'first_loss_head_std': 0.02}
    sconv = runner_module('sconv_lm_train')
    assert runner.expert_bias is sconv.expert_bias and runner.BiasedLmStepWatcher is sconv.BiasedLmStepWatcher       # imported, not copied
    assert runner.LIMITS == RUNNER


def test_needed_operations_and_bytes_are_the_issues_arithmetic():
    sizes = Manifest().config(CONFIG)['sizes']
    assert kda_lm_flops.layer_kinds(sizes) == (3, 1, 4) and kda_lm_flops.expert_layers(sizes) == 4
    slots = 4 * 8192 * 8 * 8 / 320                                  # the even load: 205 slots a held expert a layer
    macs = kda_lm_flops.forward_macs(sizes, 8192, 1, slots)
    assert macs['kda_proj'] == 3 * 8192 * 18_120_704 and macs['kda_core'] == 3 * 8192 * 8 * 4 * 128 * 128 == 3 * 8192 * 524_288
    assert macs['attn_proj'] == 8192 * 13_631_488 and macs['attn_core_full'] == 33_558_528 * 8 * 256
    assert macs['moe_shared'] == 4 * 8192 * 15_728_640 and macs['moe_experts'] == slots * 15_728_640 and macs['moe_route'] == 4 * 8192 * 1_310_720
    assert macs['head'] == 8192 * 4096 * 24576
    total = sum(macs.values())
    assert total == pytest.approx(2.125e12, rel=1e-3) and kda_lm_flops.train_flops(macs) == pytest.approx(12.75e12, rel=1e-3)
    shares = {k: round(100 * v / total, 1) for k, v in macs.items()}
    assert shares == {'kda_proj': 21.0, 'kda_core': 0.6, 'attn_proj': 5.3, 'attn_core_full': 3.2, 'moe_route': 2.0, 'moe_shared': 24.3,
                      'moe_experts': 4.9, 'head': 38.8}
    # the middle's bytes: 1024 channels x 2 B x (12 forward + 18 backward) = 61,440 B a position and layer; 1.5 GB a step
    assert kda_lm_flops.mix_bytes(1, 1024) == 61_440 and kda_lm_flops.mix_bytes(3 * 8192, 1024) == pytest.approx(1.51e9, rel=2e-3)
    runner, config = runner_module(RUNNER), Manifest().config(CONFIG)
    record = {'lm': {'seq_len': 8192, 'sequences': 1}, 'counters': {'moe.local_slots': [slots]}}
    assert runner.needed_work(config, record) == {'needed_macs': macs, 'needed_step_flops': 6 * total}
    assert runner.needed_work(config, dict(record, counters={})) == {}
    assert round(8192 * 8 / 320) == 205 and 2 * 65536 * 8 // 320 == 3276                  # a held expert's slots a layer; the bounded buffer's rows before rounding


@pytest.fixture(scope='module')
def toy(tmp_path_factory):
    """A copy of the benchmark's data with a toy configuration and cell of the folded runner added as files."""
    tmp = tmp_path_factory.mktemp('toykda')
    bench = tmp / 'benchmarks'
    for d in ('configs', 'workloads', 'layer_metrics'):
        shutil.copytree(os.path.join(BENCH_DIR, d), bench / d)
    man = load_json(os.path.join(ROOT, 'BENCHMARK.json'))
    (bench / 'configs' / 'toy_kda.json').write_text(json.dumps({
        'name': 'toy_kda', 'source': 'test', 'model': 'solar_open2_toy', 'reference': 'solar_open2', 'family': TOY_FAMILY, 'sizes': TOY_SIZES,
        'recipe': {'clip_grad': 1.0, 'weight_decay': 0.1, 'betas': [0.9, 0.95]},
        'train_args': ['-b', '8', '--opt', 'adamw', '--opt-betas', '0.9', '0.95', '--weight-decay', '0.1', '--clip-grad', '1.0',
                       '--grad-checkpointing', '--dataset', 'tokens', '--seq-len', '32'],
        'reduced': [], 'reference_block_q': 8, 'limits': {RUNNER: TOY_LIMITS}, 'limits_lm': {'route_agreement_min': 0.999}}))
    (bench / 'workloads' / 'toy_kda_train.json').write_text(json.dumps({
        'config': 'toy_kda', 'runner': RUNNER, 'chips': 1,
        'traffic': {'warmup_steps': 3, 'token_stream': {'name': 'toy', 'data_seed': 1, 'tokens': 32 * 8 * 400, 'validation_tokens': 32 * 8}}}))
    man['configs'].append({'name': 'toy_kda', 'source': 'test', 'file': 'benchmarks/configs/toy_kda.json', 'reduced': [], 'why': 'test'})
    man['workloads'].append({'name': 'toy_kda_train', 'config': 'toy_kda', 'traffic': 'toy_tokens', 'chips': 1, 'why': 'test'})
    for metric in man['end_to_end'] + man['per_layer']:
        if CELL in metric.get('workloads', ()):
            metric['workloads'].append('toy_kda_train')
    (tmp / 'BENCHMARK.json').write_text(json.dumps(man))
    return Manifest(bench_dir=str(bench), manifest_path=str(tmp / 'BENCHMARK.json')), str(tmp / 'scratch')


def _run(toy, seconds, **kw):
    m, scratch = toy
    cell = m.cell('toy_kda_train')
    lines = []
    record = runner_module(cell['runner']).run(cell, m.config(cell['config']), seed=2 ** 31 + 11, seconds=seconds, trace=False,
                                               process_start=time.perf_counter(), scratch=scratch, log=lines.append, **kw)
    return record, lines


@pytest.fixture(scope='module')
def sound(toy):
    return _run(toy, 0.4, control_precision='float8')


def test_the_folded_runner_runs_a_cell_added_by_files_and_prints_the_contracts_line(toy, sound):
    from benchmarks import run as bench_run
    record, lines = sound
    assert record['correct'] and record['failed'] == 0 and record['attempted'] > 0 and record['compiles_in_window'] == 0
    assert record['runner'] == 'train' and record['batch_size'] == 8 and record['lm']['seq_len'] == 32 and record['lm']['expert_layers'] == 4
    compared = {l.split()[1].rstrip(':') for l in lines if l.startswith('check ')}
    assert {'loss_gap_step3', 'first_grad_norm_gap', 'param_change_norm_gap', 'route_agreement', 'feed_repeated_rows', 'feed_targets_off',
            'feed_negative_ids', 'moe_dropped_slots', 'step_counters_missing', 'expert_bias_unplaced', 'first_loss',
            'compiles_in_window'} <= compared and 'ema_change_norm_gap' not in compared
    bias = record['lm']['expert_bias']
    assert len(bias) == 8 and record['numbers']['route_agreement'] == 1.0 and record['checks']['expert_bias_unplaced']['value'] == 0
    assert any(l.startswith('expert_bias: 8 values uniform in +-0.05') for l in lines)
    steps = record['steps']
    assert all(len(record['counters'][k]) == steps for k in ('lm.tokens', 'attn.full_blocks', 'kda.rows', 'kda.chunks', 'moe.local_slots'))
    assert set(record['counters']['lm.tokens']) == {8 * 32} and set(record['counters']['attn.full_blocks']) == {8 * 10}
    assert set(record['counters']['kda.rows']) == {3 * 8 * 32} and set(record['counters']['kda.chunks']) == {3 * 8 * 4 * 2}
    assert sum(record['counters']['moe.dropped_slots']) == 0
    first = record['followed']['program']['losses'][0]
    assert abs(first - (math.log(256) + 64 * 0.02 ** 2 / 2)) < 0.5 and record['checks']['first_loss']['ok']
    # the two seeded vectors reached the program: its first gradient has them, and they are the reference's draws
    assert all(record['followed']['program']['first_grad_norms'][f'blocks.{i}.kda.{leaf}'] > 0 for i in (1, 2, 3) for leaf in ('A_log', 'dt_bias'))
    device = {'platform': 'cpu', 'kind': 'cpu', 'count': 1}
    plain = bench_run.result_line(toy[0], 'toy_kda_train', record, device, trace=False)
    assert set(plain['metrics']) == {'train_img_per_s', 'setup_s'} and plain['correct']
    scopes = {'scope_s': {'kda.proj': 0.12, 'kda.mix': 0.02, 'kda.core': 0.1, 'swa.attn.proj': 0.03, 'swa.attn.core_full': 0.05,
                          'glm.moe.route': 0.04, 'glm.moe.experts': 0.07, 'glm.moe.shared': 0.03, 'glm.head_loss': 0.03, 'glm.embed': 0.001},
              'busy_s': 0.6, 'unscoped': [['fusion', 0.05]]}
    traced = dict(record, device_kind='TPU v5 lite', trace={
        'busy_s': 0.6, 'window_s': 1.0, 'idle_share': 0.4, 'work': 5, 'idle_total_s': 0.4, 'scopes': scopes,
        'breakdown': {'device_ops': [], 'idle_gaps': []}})
    line = json.loads(json.dumps(bench_run.result_line(toy[0], 'toy_kda_train', traced, device, trace=True)))
    got = {k: v['value'] for k, v in line['metrics'].items()}
    assert set(OWN) | set(SHARED) | {'step_mfu.train'} <= set(got) and not NOT_ITS & set(got)
    assert {'step_device_ms.train', 'step_wall_ms.train', 'dispatch_host_ms.train', 'input_host_ms.train', 'step_call_ms.train',
            'device_idle_share.train', 'input_batch_wait_ms.train', 'loop_bookkeeping_ms.train', 'setup_compile_s'} <= set(got)
    slots = sum(record['counters']['moe.local_slots']) / steps
    macs = kda_lm_flops.forward_macs(TOY_SIZES, 32, 8, slots)
    assert record['needed_macs'] == macs and record['needed_step_flops'] == kda_lm_flops.train_flops(macs)
    assert got['kda_device_ms.train'] == pytest.approx(48.0) and got['attn_device_ms.train'] == pytest.approx(16.0)
    assert got['moe_route_device_ms.train'] == pytest.approx(8.0) and got['moe_device_ms.train'] == pytest.approx(28.0)
    assert got['head_device_ms.train'] == pytest.approx(6.0)
    assert got['step_mfu.train'] == pytest.approx(100 * kda_lm_flops.train_flops(macs) / 0.12 / 197e12)
    assert got['kda_proj_mfu.train'] == pytest.approx(100 * 6 * macs['kda_proj'] / 0.024 / 197e12)
    assert got['kda_core_mfu.train'] == pytest.approx(100 * 6 * macs['kda_core'] / 0.020 / 197e12)
    assert macs['kda_core'] == 3 * 8 * 32 * 4 * 4 * 16 * 16                                   # the recurrence's count: positions x layers x heads x 4 d_k d_v
    assert got['kda_mix_hbm_share.train'] == pytest.approx(100 * kda_lm_flops.mix_bytes(3 * 8 * 32, 64) / 0.004 / 819e9)
    assert got['attn_full_core_mfu.train'] == pytest.approx(100 * 6 * macs['attn_core_full'] / 0.010 / 197e12)
    assert got['moe_experts_mfu.train'] == pytest.approx(100 * 6 * macs['moe_experts'] / 0.014 / 197e12)
    assert all(0 < got[n] < 100 for n in OWN + SHARED if n.endswith(('_mfu.train', '_share.train')))
    table = device_scopes.scope_table(traced, kda_lm_readers.SCOPE_PARTS)
    assert any(l.startswith('device scope kda.core: 20.00 ms a step, 16.7 % of busy, ') for l in table)
    assert any(l.startswith('device scope kda.mix: 4.00 ms a step') and 'of peak' not in l for l in table)
    assert lm_readers.READERS['moe_slots_per_expert.train'].read(traced) == pytest.approx(slots / (2 * 4))
    assert kda_lm_readers.chunk_positions(traced) == 16 and kda_lm_readers.lines(traced) == ['reading kda_chunk_positions.train: 16 positions a chunk']
    assert kda_lm_readers.chunk_positions({}) is None and 'nothing to read' in kda_lm_readers.lines({})[0]
    assert set(record['checks']) == compared and all(c['ok'] for c in record['checks'].values())
    assert record['memory_peak_bytes'] <= record['memory_peak_bytes_summed']


@pytest.mark.parametrize('name', OWN + SHARED)
def test_every_reader_that_lists_the_cell_reads_a_hand_written_record_of_it(name):
    value = Manifest().reader(name)(HAND)
    want = {'kda_device_ms.train': 30.1, 'kda_proj_mfu.train': 100 * 6 * 2e9 / 0.01 / 197e12, 'kda_core_mfu.train': 100 * 6 * 1e8 / 0.02 / 197e12,
            'kda_mix_hbm_share.train': 100 * kda_lm_flops.mix_bytes(768, 64) / 1e-4 / 819e9, 'head_device_ms.train': 6.0,
            'moe_route_device_ms.train': 8.0, 'moe_device_ms.train': 24.0, 'moe_experts_mfu.train': 100 * 6 * 3e9 / 0.012 / 197e12,
            'attn_device_ms.train': 26.0, 'attn_proj_mfu.train': 100 * 6 * 1e9 / 0.006 / 197e12,
            'attn_full_core_mfu.train': 100 * 6 * 4e9 / 0.02 / 197e12}[name]
    assert value == pytest.approx(want) and (name.endswith('_ms.train') or 0 < value < 100)


@pytest.mark.parametrize('name', OWN)
def test_a_new_reader_returns_none_where_there_is_nothing_to_read(name):
    """A parent without the scopes and counters, an image cell's run, another family's record, an empty one: no value, no
    raise. And a middle whose scope holds less than its work reads OVER 100, which the driver refuses."""
    read = kda_lm_readers.READERS[name].read
    other = {'runner': 'train', 'steps': 3, 'device_kind': 'TPU v5 lite', 'lm': {'seq_len': 32, 'sequences': 8, 'expert_layers': 4},
             'sizes': dict(hidden_size=64, num_experts=8), 'counters': {'sconv.rows': [1024]}, 'needed_macs': {'sconv_proj': 2e9},
             'trace': {'busy_s': 0.5, 'window_s': 1.0, 'work': 5, 'scopes': {'scope_s': {'sconv.proj': 0.05, 'sconv.mix': 0.001}, 'busy_s': 0.5, 'unscoped': []}}}
    assert read({}) is None and read(other) is None
    assert read({'runner': 'train', 'steps': 3, 'sizes': {'embed_dim': 768}, 'device_kind': 'TPU v5 lite',
                 'trace': {'busy_s': 0.5, 'window_s': 1.0, 'idle_share': 0.5, 'work': 5}}) is None
    assert read({'runner': 'train', 'sizes': TOY_SIZES, 'device_kind': 'TPU v5 lite', 'lm': {'seq_len': 32, 'sequences': 8}}) is None
    fused_away = json.loads(json.dumps(HAND))
    fused_away['trace']['scopes']['scope_s']['kda.mix'] = 1e-7
    assert kda_lm_readers.mix_hbm_share(fused_away) > 105 and kda_lm_readers.mix_hbm_share(HAND) < 100


def test_the_float8_control_is_not_correct(toy, sound):
    record, lines = sound
    limits = toy[0].config('toy_kda')['limits'][RUNNER]
    numbers = lambda d: {k: (v, '') for k, v in d.items() if k != 'route_agreement'}  # noqa: E731
    assert check.judge(numbers(record['numbers']), limits, out=lambda s: None)
    assert not check.judge(numbers(record['control_numbers']), limits, out=lambda s: None)
    assert record['control_correct'] is False and record['control_numbers']['first_grad_norm_gap'] > 10 * limits['first_grad_norm_gap']
    assert any(l.startswith('control float8 check ') and l.split('(')[0].rstrip().endswith('OVER') for l in lines)
    # the KDA leaves by name: float8 operands in the products, the taps and the recurrence move their first gradient
    program, control, reference = (record['followed'][k]['first_grad_norms'] for k in ('program', 'control', 'reference'))
    for leaf in ('blocks.2.kda.q_taps', 'blocks.2.kda.k_proj.kernel', 'blocks.2.kda.o_proj.kernel', 'blocks.2.kda.A_log'):
        assert abs(control[leaf] - reference[leaf]) > 20 * abs(program[leaf] - reference[leaf]), leaf


def test_a_step_that_returns_its_state_unchanged_is_not_correct(toy):
    import jax.numpy as jnp
    stuck = lambda task, batch, lr, step=0: {'loss': jnp.float32(5.5), 'grad_norm': jnp.float32(1.0)}  # noqa: E731
    record, lines = _run(toy, 0.2, inner_step=stuck)
    over = {l.split()[1].rstrip(':') for l in lines if l.startswith('check ') and l.split('(')[0].rstrip().endswith('OVER')}
    assert not record['correct'] and {'param_change_norm_gap', 'first_grad_norm_gap', 'step_counters_missing'} <= over
    assert record['numbers']['param_change_norm_gap'] == pytest.approx(1.0) and 'kda.rows' not in record['counters']


def test_a_run_whose_bias_the_program_does_not_hold_is_not_correct(toy, monkeypatch):
    """The reference chooses under the run's bias; a program left at the zero buffer chooses otherwise."""
    monkeypatch.setattr(runner_module('sconv_lm_train'), 'place_expert_bias', lambda model, bias: 0)
    record, lines = _run(toy, 0.2)
    assert not record['correct'] and not record['checks']['expert_bias_unplaced']['ok']
    assert record['numbers']['route_agreement'] < 0.9 and not record['checks']['route_agreement']['ok']


def test_a_program_without_the_family_fails_before_any_work(toy, monkeypatch, tmp_path):
    """What the parent commit does when the driver asks it for the new cell: the import fails at once, before the
    stream is written or `train.main` is entered."""
    m, _ = toy
    cell = m.cell('toy_kda_train')
    monkeypatch.setitem(sys.modules, 'timm_tpu.models.solar_open2', None)
    with pytest.raises(ImportError):
        runner_module(RUNNER).run(cell, m.config('toy_kda'), seed=1, seconds=0.1, trace=False,
                                  process_start=time.perf_counter(), scratch=str(tmp_path / 's'), log=lambda s: None)
    assert not (tmp_path / 's').exists()


def test_device_time_is_reduced_by_the_familys_scopes_too():
    names = kda_lm_readers.declared_scopes()
    assert names >= device_scopes.declared_scopes() | {'kda.proj', 'kda.mix', 'kda.core'} and names == swa_lm_readers.declared_scopes()
    assert set(kda_lm_readers.SCOPE_PARTS) <= names
    of = lambda op: device_scopes.scope_of(op, names)  # noqa: E731
    assert of('jit(train_step)/transpose(jvp(checkpoint))/kda.core/while/body/dot_general') == 'kda.core'
    assert of('jit(train_step)/jvp(kda.proj)/dot_general') == 'kda.proj' and of('jit(train_step)/adamw/mul') is None
    import jax
    import timm_tpu
    from flax import nnx
    model = timm_tpu.create_model('solar_open2_toy', seed=0)
    graphdef, state = nnx.split(model)
    ids = jax.numpy.zeros((1, 32), 'int32')
    text = jax.jit(lambda st: nnx.merge(graphdef, st)(ids)).lower(state).compile().as_text()
    assert {'glm.embed', 'kda.proj', 'kda.mix', 'kda.core', 'swa.attn.proj', 'swa.attn.core_full', 'glm.moe.route', 'glm.moe.experts',
            'glm.moe.shared'} <= set(device_scopes.instruction_scopes(text, names).values())
    assert np.isfinite(kda_lm_flops.mix_bytes(1, 64))
