"""The chunk-pooled linear-attention language-model cell's part of the benchmark (`cla_lm_train_runner.py`,
`cla_lm_flops.py`, `cla_lm_readers.py`, the configuration and the cell ISSUE 41 brings), at `evabyte_toy` size on
the CPU. One file, like its neighbours.

Six readings are metrics of `BENCHMARK.json` (`cla_lm_readers.READERS`); the whole step's share of the peak is
`step_mfu.train`'s. What the manifest must have is held as a SUBSET of what it has, never by count or by position
from the end: a later PR adds cells and metrics and may not edit this file.
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

from benchmarks.harness import check, cla_lm_flops, cla_lm_readers, device_scopes  # noqa: E402
from benchmarks.harness.manifest import BENCH_DIR, Manifest, load_json, runner_module  # noqa: E402

CELL, SWA_CELL, CONFIG = 'evabyte_6b5_hp2_train_16k', 'smallthinker_21b_ep8_train_16k', 'evabyte_6b5_hp2'
OWN = ['eva_device_ms.train', 'eva_core_mfu.train', 'eva_block_fill.train', 'eva_summary_hbm_share.train',
       'ffn_device_ms.train', 'ffn_mfu.train']
EVERY_TRAINING_CELLS = ['step_mfu.train', 'step_device_ms.train', 'step_wall_ms.train', 'dispatch_host_ms.train',
                        'input_host_ms.train', 'device_idle_share.train', 'hbm_peak_gb.train', 'step_call_ms.train',
                        'input_batch_wait_ms.train', 'loop_bookkeeping_ms.train', 'step_update_device_ms.train',
                        'step_scope_cover.train']
NOT_ITS = {'input_prepare_ms.train', 'input_decode_busy_share.train', 'mla_device_ms.train', 'attn_device_ms.train',
           'moe_route_device_ms.train', 'moe_device_ms.train', 'moe_experts_mfu.train', 'attn_bd_core_mfu.train'}
TOY_SIZES = dict(vocab_size=320, hidden_size=64, intermediate_size=160, num_hidden_layers=2, num_attention_heads=4,
                 heads_held=4, head_offset=0, head_dim=16, window_size=32, chunk_size=4, num_pred_heads=8,
                 rope_theta=1e5, rms_norm_eps=1e-5)
# float32 on both sides: summation order only (Adam's division makes 1e-4 of a change norm); float8 operands
# move every number by 1e-2 and more
TOY_LIMITS = {'loss_gap': 2e-4, 'first_grad_norm_gap': 2e-4, 'param_change_norm_gap': 2e-3, 'ema_change_norm_gap': 2e-3}
SWA_RECORD = {'runner': 'train', 'steps': 3, 'device_kind': 'TPU v5 lite', 'lm': {'seq_len': 32, 'sequences': 8},
              'sizes': dict(vocab_held=256, hidden_size=64, num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
                            head_dim=16, sliding_window_size=8, sliding_window_layout=[0, 1, 1, 1]),
              'counters': {'moe.local_slots': [700, 800], 'attn.full_blocks': [80], 'attn.window_blocks': [168]},
              'needed_macs': {'attn_core_full': 4e9, 'attn_proj': 1e9},
              'trace': {'busy_s': 0.5, 'window_s': 1.0, 'idle_share': 0.5, 'work': 5, 'scopes': {
                  'scope_s': {'swa.attn.core_full': 0.2, 'swa.attn.proj': 0.03, 'glm.moe.route': 0.04}, 'busy_s': 0.5, 'unscoped': []}}}


def explicit_pairs(seq: int, window: int, chunk: int) -> np.ndarray:
    """(seq, seq // chunk + seq) booleans, summaries first, from the definition and with loops."""
    seen = np.zeros((seq, seq // chunk + seq), bool)
    for i in range(seq):
        for t in range(i // window * window, i + 1):
            seen[i, seq // chunk + t] = True
        for j in range(seq // chunk):
            if j * chunk // window < i // window:
                seen[i, j] = True
    return seen


def test_the_manifest_has_the_configuration_the_cell_and_its_readers_entries():
    m = Manifest()
    names = [x['name'] for x in m.data['per_layer']]
    assert set(cla_lm_readers.READERS) == set(OWN) <= set(names)
    assert CELL in m.cells and CONFIG in {c['name'] for c in m.data['configs']} and m.data['run_seconds'] == 20
    held = set(m.metrics_of(CELL, 'per_layer'))
    assert set(OWN) | set(EVERY_TRAINING_CELLS) <= held and not NOT_ITS & held           # a subset, never a count
    assert m.metrics_of(CELL, 'end_to_end') == ['train_img_per_s', 'setup_s']
    assert not set(OWN) & set(m.metrics_of(SWA_CELL, 'per_layer'))
    # wherever every other cell that trains through `train.main` is listed, this one is, after them
    for metric in m.data['end_to_end'] + m.data['per_layer']:
        cells = metric.get('workloads', [])
        if {'vit_b16_train', 'convnext_b_train', 'glm47_flash_ep8_train_8k', SWA_CELL} <= set(cells):
            assert CELL in cells and cells.index(CELL) > cells.index(SWA_CELL), metric['name']
    shares = [x['name'] for x in m.data['per_layer'] if x['layer'] == 'step' and 'mfu' in x['name'] and CELL in x.get('workloads', [CELL])]
    assert shares == ['step_mfu.train']                                                    # one share of the whole step's peak
    for name, r in cla_lm_readers.READERS.items():           # what their entries say
        entry = cla_lm_readers.entry(name, [CELL])
        assert entry == {'name': name, 'unit': r.unit, 'better': r.better, 'source': r.source, 'layer': r.layer,
                         'moves': 'train_img_per_s', 'workloads': [CELL]}
        assert dict(m.per_layer[name], workloads=[CELL]) == entry and CELL in m.per_layer[name]['workloads']
        assert r.layer == ('feed-forward' if name.startswith('ffn_') else 'attention')
        assert r.source in ('device_trace', 'program_counter')
        assert (r.unit, r.better) == (('ms', 'lower') if name.endswith('_ms.train') else ('%', 'higher'))
        assert callable(m.reader(name))
    cell, config = m.cell(CELL), m.config(CONFIG)
    assert cell['runner'] == 'cla_lm_train' and cell['chips'] == 1 and m.cells[CELL]['traffic'] == 'train_token_stream'
    stream = cell['traffic']['token_stream']
    assert (stream['name'], stream['tokens'], stream['validation_tokens'], stream['data_seed']) == ('uniform_320_8m', 8_388_608, 32768, 20261002)
    assert cell['traffic']['warmup_steps'] == 6 and stream['tokens'] // 16384 == 512
    assert {'source', 'published', 'deployment', 'reduced', 'reduced_why', 'assumed', 'precision', 'sizes', 'limits',
            'limits_why'} <= set(config)
    assert config['reduced'] == ['num_hidden_layers', 'num_attention_heads', 'num_key_value_heads'] == list(config['reduced_why'])
    assert '687,132,672 parameters x 16 B = 10.99 GB' in config['reduced_why']['num_hidden_layers']
    assert config['train_args'] == '-b 1 --amp --opt adamw --opt-betas 0.9 0.95 --weight-decay 0.1 --clip-grad 1.0 ' \
                                   '--grad-checkpointing --dataset tokens --seq-len 16384'.split()
    assert {'chunk_softmax_scale', 'learned_vectors_init', 'head_loss_weights', 'head_columns', 'summaries_seen'} <= set(config['assumed'])
    path = '/opt/skills/guides/model-configs/architectures.jsonl'
    for row in [json.loads(line) for line in open(path)] if os.path.exists(path) else []:
        if row['name'] == 'EvaByte':                         # every published number under its key, but the three reduced
            assert config['source'] == row['source_url'] == [c for c in m.data['configs'] if c['name'] == CONFIG][0]['source']
            off = {k for k, v in row['config'].items() if config.get(k, 'missing') != v}
            assert off == set(config['reduced']) == set(config['published']) and all(
                config['published'][k] == row['config'][k] for k in off)
    sizes = config['sizes']
    assert (sizes['num_hidden_layers'], sizes['heads_held'], sizes['head_offset']) == (4, 16, 0) == (
        config['num_hidden_layers'], config['num_attention_heads'], 0) and config['num_key_value_heads'] == 16
    assert sizes['num_attention_heads'] == config['published']['num_attention_heads'] == 32
    assert all(sizes[k] == config[k] for k in ('hidden_size', 'intermediate_size', 'window_size', 'chunk_size', 'num_pred_heads',
                                               'rope_theta', 'rms_norm_eps', 'vocab_size'))
    assert sizes['head_dim'] * config['published']['num_attention_heads'] == config['hidden_size']       # 128: no key of its own
    assert set(config['limits']['cla_lm_train']) == set(TOY_LIMITS) and set(config['limits_why']) >= set(TOY_LIMITS)
    assert 'limits_lm' not in config or 'route_agreement_min' not in config['limits_lm']                  # no router
    assert len(config['source']) <= 200
    # the held parameters, from the reference's own shapes: ISSUE 41's table
    from benchmarks.reference import evabyte
    assert sum(math.prod(shape) for shape, _ in evabyte.init_spec(sizes).values()) == 687_132_672
    assert sum(math.prod(shape) for shape, _ in evabyte.init_spec(dict(sizes, heads_held=32, num_hidden_layers=32)).values()) == 6_488_330_240


@pytest.mark.parametrize('seq,window,chunk', [(128, 32, 4), (96, 24, 3), (64, 64, 4), (256, 64, 16)])
def test_the_pair_and_tile_counts_are_the_masks_own(seq, window, chunk):
    seen, m = explicit_pairs(seq, window, chunk), seq // chunk
    assert cla_lm_flops.window_pairs(seq, window) == int(seen[:, m:].sum())
    assert cla_lm_flops.summary_pairs(seq, window, chunk) == int(seen[:, :m].sum())
    assert cla_lm_flops.core_pairs(seq, window, chunk) == int(seen.sum())
    for side in (4, 8, 16, 3):
        want = None if window % side or m % side else sum(
            part[i:i + side, j:j + side].any() for part in (seen[:, :m], seen[:, m:])
            for i in range(0, seq, side) for j in range(0, part.shape[1], side))
        assert cla_lm_flops.visited_tiles(seq, window, chunk, side) == want


def test_needed_operations_and_bytes_are_the_issues_arithmetic():
    sizes = Manifest().config(CONFIG)['sizes']
    assert cla_lm_flops.window_pairs(16384, 2048) == 16_785_408 and cla_lm_flops.summary_pairs(16384, 2048, 16) == 7_340_032
    assert cla_lm_flops.core_pairs(16384, 2048, 16) == 24_125_440 and 24_125_440 / 134_225_920 == pytest.approx(0.18, abs=0.002)
    assert cla_lm_flops.visited_tiles(16384, 2048, 16, 1024) == 38 == 24 + 14              # of 16 x 17 = 272
    assert 24_125_440 / (38 * 1024 * 1024) == pytest.approx(0.61, abs=0.006)               # ~61 % of their pairs needed
    macs = cla_lm_flops.forward_macs(sizes, 16384, 1)
    assert macs['ffn'] == 4 * 16384 * 135_266_304 == pytest.approx(8.865e12, rel=1e-3)
    assert macs['attn_proj'] == 4 * 16384 * 33_554_432 == pytest.approx(2.199e12, rel=1e-3)
    assert macs['attn_core'] == 4 * 16 * 24_125_440 * 256 == pytest.approx(3.953e11, rel=1e-3)
    assert macs['head'] == 16384 * 4096 * 2560 == pytest.approx(1.718e11, rel=1e-3) and macs['attn_summary'] == pytest.approx(4e8, rel=0.02)
    total = sum(macs.values())
    assert total == pytest.approx(1.1631e13, rel=1e-3) and cla_lm_flops.train_flops(macs) == pytest.approx(69.8e12, rel=1e-3)
    assert macs['ffn'] / total == pytest.approx(0.762, abs=0.001) and macs['attn_core'] / total == pytest.approx(0.034, abs=0.001)
    # the summaries' bytes: k and v (2 x 16 heads x 16384 x 128 bfloat16 = 134 MB) read and a sixteenth written
    # forward; read again with the summaries and their gradients, and dk, dv written, backward; 4 layers
    kv = 2 * 16 * 16384 * 128 * 2
    assert cla_lm_flops.summary_bytes(sizes, 16384, 1) == 4 * ((kv + kv // 16) + (2 * kv + 2 * kv // 16)) == 1_711_276_032
    runner = runner_module('cla_lm_train')
    config = Manifest().config(CONFIG)
    work = runner.needed_work(config, {'lm': {'seq_len': 16384, 'sequences': 1}, 'counters': {}})       # no counter is asked for
    assert work == {'needed_macs': macs, 'needed_step_flops': 6 * total} and runner.needed_work(config, {}) == {}


@pytest.fixture(scope='module')
def toy(tmp_path_factory):
    """A copy of the benchmark's data with a toy configuration and cell of the new runner added as files."""
    tmp = tmp_path_factory.mktemp('toycla')
    bench = tmp / 'benchmarks'
    for d in ('configs', 'workloads', 'layer_metrics'):
        shutil.copytree(os.path.join(BENCH_DIR, d), bench / d)
    man = load_json(os.path.join(ROOT, 'BENCHMARK.json'))
    (bench / 'configs' / 'toy_cla.json').write_text(json.dumps({
        'name': 'toy_cla', 'source': 'test', 'model': 'evabyte_toy', 'reference': 'evabyte', 'sizes': TOY_SIZES,
        'recipe': {'clip_grad': 1.0, 'weight_decay': 0.1, 'betas': [0.9, 0.95]},
        'train_args': ['-b', '8', '--opt', 'adamw', '--opt-betas', '0.9', '0.95', '--weight-decay', '0.1', '--clip-grad', '1.0',
                       '--grad-checkpointing', '--dataset', 'tokens', '--seq-len', '128'],
        'reduced': [], 'reference_block_q': 16, 'limits': {'cla_lm_train': TOY_LIMITS}}))
    (bench / 'workloads' / 'toy_cla_train.json').write_text(json.dumps({
        'config': 'toy_cla', 'runner': 'cla_lm_train', 'chips': 1,
        'traffic': {'warmup_steps': 3, 'token_stream': {'name': 'toy', 'data_seed': 1, 'tokens': 128 * 8 * 200,
                                                        'validation_tokens': 128 * 8}}}))
    man['configs'].append({'name': 'toy_cla', 'source': 'test', 'file': 'benchmarks/configs/toy_cla.json', 'reduced': [], 'why': 'test'})
    man['workloads'].append({'name': 'toy_cla_train', 'config': 'toy_cla', 'traffic': 'toy_tokens', 'chips': 1, 'why': 'test'})
    for metric in man['end_to_end'] + man['per_layer']:
        if CELL in metric.get('workloads', ()):
            metric['workloads'].append('toy_cla_train')
    (tmp / 'BENCHMARK.json').write_text(json.dumps(man))
    return Manifest(bench_dir=str(bench), manifest_path=str(tmp / 'BENCHMARK.json')), str(tmp / 'scratch')


def _run(toy, seconds, **kw):
    m, scratch = toy
    cell = m.cell('toy_cla_train')
    lines = []
    record = runner_module(cell['runner']).run(cell, m.config(cell['config']), seed=2 ** 31 + 11, seconds=seconds, trace=False,
                                               process_start=time.perf_counter(), scratch=scratch, log=lines.append, **kw)
    return record, lines


@pytest.fixture(scope='module')
def sound(toy):
    return _run(toy, 0.4, control_precision='float8')


def test_the_new_runner_runs_a_cell_added_by_files_and_prints_the_contracts_line(toy, sound):
    from benchmarks import run as bench_run
    from benchmarks.harness import lm_train_runner
    record, lines = sound
    assert record['correct'] and record['failed'] == 0 and record['attempted'] > 0 and record['compiles_in_window'] == 0
    assert record['runner'] == 'train' and record['batch_size'] == 8 and record['lm']['seq_len'] == 128
    compared = {l.split()[1].rstrip(':') for l in lines if l.startswith('check ')}
    assert {'loss_gap_step3', 'first_grad_norm_gap', 'param_change_norm_gap', 'feed_repeated_rows', 'feed_targets_off',
            'feed_negative_ids', 'feed_head_targets_off', 'step_counters_missing', 'first_loss', 'compiles_in_window'} <= compared
    # no router: no routes are read, no agreement is judged, no `moe.*` counter is demanded or returned
    assert not {'ema_change_norm_gap', 'route_agreement', 'moe_dropped_slots'} & compared and 'route_agreement' not in record['numbers']
    assert not [k for k in record['counters'] if k.startswith('moe.')] and 'routes' not in record['followed']['program']
    assert lm_train_runner.program_routes.__name__ == 'program_routes'                    # the question is the module's own again
    steps = record['steps']
    assert all(len(record['counters'][k]) == steps for k in ('lm.tokens', 'attn.eva_blocks', 'attn.eva_pairs'))
    assert set(record['counters']['lm.tokens']) == {8 * 128} and set(record['counters']['attn.eva_blocks']) == {8 * 2 * 20}
    assert set(record['counters']['attn.eva_pairs']) == {8 * 2 * 4 * cla_lm_flops.core_pairs(128, 32, 4)}
    assert len(record['lm']['head_nll_last']) == 8 and abs(record['lm']['head_nll_first'] - math.log(320)) < 0.5
    device = {'platform': 'cpu', 'kind': 'cpu', 'count': 1}
    plain = bench_run.result_line(toy[0], 'toy_cla_train', record, device, trace=False)
    assert set(plain['metrics']) == {'train_img_per_s', 'setup_s'} and plain['correct']
    # the traced line: the readers every training cell has read this run, the new ones read its counters and scopes
    scopes = {'scope_s': {'evabyte.ffn': 0.25, 'evabyte.attn.proj': 0.08, 'evabyte.attn.core': 0.04, 'evabyte.attn.summary': 0.01,
                          'glm.head_loss': 0.03, 'glm.embed': 0.001},
              'busy_s': 0.5, 'unscoped': [['fusion', 0.05]]}
    traced = dict(record, device_kind='TPU v5 lite', trace={
        'busy_s': 0.5, 'window_s': 1.0, 'idle_share': 0.5, 'work': 5, 'idle_total_s': 0.5, 'scopes': scopes,
        'breakdown': {'device_ops': [], 'idle_gaps': []}})
    line = json.loads(json.dumps(bench_run.result_line(toy[0], 'toy_cla_train', traced, device, trace=True)))
    got = {k: v['value'] for k, v in line['metrics'].items()}
    assert set(OWN) | {'step_mfu.train'} <= set(got) and not NOT_ITS & set(got)
    assert {'step_device_ms.train', 'step_wall_ms.train', 'dispatch_host_ms.train', 'input_host_ms.train', 'step_call_ms.train',
            'device_idle_share.train', 'input_batch_wait_ms.train', 'loop_bookkeeping_ms.train', 'setup_compile_s'} <= set(got)
    macs = cla_lm_flops.forward_macs(TOY_SIZES, 128, 8)
    assert record['needed_macs'] == macs and record['needed_step_flops'] == cla_lm_flops.train_flops(macs)
    assert got['eva_device_ms.train'] == pytest.approx(26.0) and got['ffn_device_ms.train'] == pytest.approx(50.0)
    assert got['step_mfu.train'] == pytest.approx(100 * cla_lm_flops.train_flops(macs) / 0.1 / 197e12)
    assert got['eva_core_mfu.train'] == pytest.approx(100 * 6 * macs['attn_core'] / 0.008 / 197e12)
    assert got['ffn_mfu.train'] == pytest.approx(100 * 6 * macs['ffn'] / 0.05 / 197e12)
    assert got['eva_summary_hbm_share.train'] == pytest.approx(100 * cla_lm_flops.summary_bytes(TOY_SIZES, 128, 8) / 0.002 / 819e9)
    # tiles of 16 x 16, from the core's own count against the table's: 20 a layer and sequence
    assert cla_lm_readers.block_side(traced) == 16 and cla_lm_flops.visited_tiles(128, 32, 4, 16) == 20
    assert got['eva_block_fill.train'] == pytest.approx(100 * cla_lm_flops.core_pairs(128, 32, 4) / (20 * 256))
    assert all(got[n] < 100 for n in OWN if n.endswith(('_mfu.train', '_share.train', '_fill.train')))
    table = device_scopes.scope_table(traced, cla_lm_readers.SCOPE_PARTS)
    assert any(l.startswith('device scopes cover 82.2 %') for l in table)
    assert any(l.startswith('device scope evabyte.ffn: 50.00 ms a step, 50.0 % of busy, ') for l in table)
    # the same numbers as data, beside their limits: what the result line ends with
    assert set(record['checks']) == compared and all(c['ok'] for c in record['checks'].values())
    # and both definitions of the memory peak, until one is chosen
    assert any(l.startswith('memory_peak_bytes: ') and 'peaks.memory_peak_bytes' in l for l in lines)
    assert record['memory_peak_bytes'] <= record['memory_peak_bytes_summed']


@pytest.mark.parametrize('name', OWN)
def test_a_new_reader_returns_none_where_there_is_nothing_to_read(name):
    """A parent without the scopes and counters, an image cell's run, another family's record, an empty one: no
    value, no raise."""
    read = cla_lm_readers.READERS[name].read
    assert read({}) is None and read(SWA_RECORD) is None
    assert read({'runner': 'train', 'steps': 3, 'sizes': {'embed_dim': 768}, 'device_kind': 'TPU v5 lite',
                 'trace': {'busy_s': 0.5, 'window_s': 1.0, 'idle_share': 0.5, 'work': 5}}) is None
    # this family's sizes and nothing measured: still nothing
    assert read({'runner': 'train', 'sizes': TOY_SIZES, 'device_kind': 'TPU v5 lite', 'lm': {'seq_len': 128, 'sequences': 8}}) is None
    # a count of tiles no side explains is no fill
    odd = {'runner': 'train', 'sizes': TOY_SIZES, 'lm': {'seq_len': 128, 'sequences': 8},
           'counters': {'attn.eva_blocks': [7.0], 'attn.eva_pairs': [1.0]}}
    assert cla_lm_readers.block_fill(odd) is None


def test_the_float8_control_is_not_correct(toy, sound):
    record, _ = sound
    limits = toy[0].config('toy_cla')['limits']['cla_lm_train']
    numbers = lambda d: {k: (v, '') for k, v in d.items()}  # noqa: E731
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
    assert record['checks']['first_loss']['ok'] is False and record['checks']['first_loss']['value'] == 'nan'      # no head's loss came back
    assert record['numbers']['param_change_norm_gap'] == pytest.approx(1.0) and 'attn.eva_blocks' not in record['counters']


def test_targets_that_are_not_the_input_ahead_are_not_correct():
    runner = runner_module('cla_lm_train')
    ids = np.arange(2 * 16).reshape(2, 16)
    target = np.concatenate([ids[:, 1:], np.full((2, 1), -1)], axis=1)
    given = np.stack([np.pad(target[:, p:], ((0, 0), (0, p)), constant_values=-1) for p in range(8)], axis=-1)
    followed = [{'input': ids, 'target': target}]
    assert runner.head_target_numbers(followed, [given], 8)['feed_head_targets_off'][0] == 0
    shifted = given.copy()
    shifted[..., 3] = given[..., 2]                                            # head 3 handed head 2's targets
    assert runner.head_target_numbers(followed, [shifted], 8)['feed_head_targets_off'][0] > 0
    assert runner.head_target_numbers(followed, [given[..., :4]], 8)['feed_head_targets_off'][0] > 0    # four heads of eight
    kept = given.copy()
    kept[:, -3, 2] = 5                                                         # a target past the window
    assert runner.head_target_numbers(followed, [kept], 8)['feed_head_targets_off'][0] == 2


def test_a_program_without_the_family_fails_before_any_work(toy, monkeypatch, tmp_path):
    """What the parent commit does when the driver asks it for the new cell: the import fails at once, before the
    stream is written or `train.main` is entered."""
    m, _ = toy
    cell = m.cell('toy_cla_train')
    monkeypatch.setitem(sys.modules, 'timm_tpu.models.evabyte', None)
    with pytest.raises(ImportError):
        runner_module('cla_lm_train').run(cell, m.config('toy_cla'), seed=1, seconds=0.1, trace=False,
                                          process_start=time.perf_counter(), scratch=str(tmp_path / 's'), log=lambda s: None)
    assert not (tmp_path / 's').exists()


def test_device_time_is_reduced_by_the_familys_scopes_too():
    names = cla_lm_readers.declared_scopes()
    assert names >= device_scopes.declared_scopes() | {'evabyte.attn.proj', 'evabyte.attn.summary', 'evabyte.attn.core', 'evabyte.ffn'}
    assert set(cla_lm_readers.SCOPE_PARTS) <= names
    of = lambda op: device_scopes.scope_of(op, names)  # noqa: E731
    assert of('jit(train_step)/transpose(jvp(checkpoint))/evabyte.attn.core/vmap(jit(_splash_attention))/pallas_call') == 'evabyte.attn.core'
    assert of('jit(train_step)/jvp(evabyte.ffn)/dot_general') == 'evabyte.ffn' and of('jit(train_step)/adamw/mul') is None
    # a real step program's compiled text names the scopes (the CPU's here; the chip's in a traced run)
    import jax
    import timm_tpu
    from flax import nnx
    model = timm_tpu.create_model('evabyte_toy', seed=0)
    graphdef, state = nnx.split(model)
    ids = jax.numpy.zeros((1, 128), 'int32')
    text = jax.jit(lambda st: nnx.merge(graphdef, st)(ids)).lower(state).compile().as_text()
    assert {'glm.embed', 'evabyte.attn.proj', 'evabyte.attn.summary', 'evabyte.attn.core', 'evabyte.ffn'} <= set(
        device_scopes.instruction_scopes(text, names).values())
