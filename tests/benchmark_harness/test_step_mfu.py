"""`step_mfu.train` as the ONE share of the whole step's peak in every training cell, and the ten readings of the two
language-model cells as per-layer metrics of `BENCHMARK.json` (ISSUE 35). Everything here is arithmetic on records
written by hand at the cells' real sizes: nothing runs a model, and no number below is a measurement of this file (the
step times are the ledger's and PERF.md's, named where they stand). What a step NEEDS comes from the cell's own runner
(`needed_work(config, record)`, which every runner has), as in a run. Nothing here holds the manifest's cells or
metrics EQUAL to today's: a later PR adds a cell, a family and its metrics as files and entries, and may not edit this
file. The same readers on a real toy run of each family: `test_lm_harness.py`, `test_swa_lm_harness.py`.
"""
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import flops, lm_flops, swa_lm_flops, train_runner  # noqa: E402
from benchmarks.harness.manifest import Manifest, runner_module  # noqa: E402

VIT, CNX, GLM, SWA = 'vit_b16_train', 'convnext_b_train', 'glm47_flash_ep8_train_8k', 'smallthinker_21b_ep8_train_16k'
FOUR = [VIT, CNX, GLM, SWA]
BOTH = ['moe_route_device_ms.train', 'moe_device_ms.train', 'moe_experts_mfu.train']
GLM_ONLY = ['mla_device_ms.train', 'mla_core_mfu.train']
SWA_ONLY = ['attn_device_ms.train', 'attn_proj_mfu.train', 'attn_full_core_mfu.train', 'attn_window_core_mfu.train',
            'attn_window_block_fill.train']
TEN = BOTH + GLM_ONLY + SWA_ONLY
THEIRS = {VIT: [], CNX: [], GLM: BOTH + GLM_ONLY, SWA: BOTH + SWA_ONLY}
# routing that gives every expert its share: tokens x expert layers x chosen x held / all
EVEN_SLOTS = {GLM: 16384 * 5 * 4 * 8 // 64, SWA: 16384 * 8 * 6 * 8 // 64}
# seconds under each device scope in ten traced steps (PERF.md section 5: ms a step), and the steps' busy seconds
SCOPE_S = {GLM: {'glm.mla.core': 2.5767, 'glm.mla.proj': 2.0819, 'glm.moe.route': 1.1970, 'glm.head_loss': 0.6299,
                 'glm.moe.experts': 0.4919, 'glm.dense_ffn': 0.4708, 'glm.moe.shared': 0.3412, 'glm.mtp': 0.1001, 'glm.embed': 0.0218},
           SWA: {'glm.moe.route': 3.0529, 'swa.attn.core_window': 1.9056, 'swa.attn.proj': 1.6288, 'swa.attn.core_full': 1.1722,
                 'glm.moe.experts': 0.6754, 'glm.head_loss': 0.4096, 'glm.embed': 0.0906}}
BUSY_S = {VIT: 1.4911, CNX: 1.5635, GLM: 8.4127, SWA: 9.6657}       # ledger, PR 31: `step_device_ms.train` x 10 (SWA: PERF.md)
COUNTERS = {GLM: {'moe.local_slots': [37715.0], 'moe.load_max': [2275.0], 'moe.dropped_slots': [0]},
            SWA: {'moe.local_slots': [72900.0], 'moe.load_max': [13300.0], 'moe.dropped_slots': [0],
                  'attn.full_blocks': [272], 'attn.window_blocks': [420]}}


@pytest.fixture(scope='module')
def manifest():
    return Manifest()


def record(manifest, cell_name: str, slots: float = None) -> dict:
    """What a traced run of the cell hands the readers, as far as they read it, from the cell's own configuration and
    its own runner's `needed_work`. A cell this file does not know (a later PR's) gets a second of busy time in ten
    steps and, where its arguments give a sequence length, a thousand routed slots a step."""
    cell = manifest.cell(cell_name)
    config = manifest.config(cell['config'])
    args = config['train_args']
    run = {'runner': 'train', 'cell': cell_name, 'reference': config['reference'], 'sizes': config['sizes'],
           'batch_size': int(args[args.index('-b') + 1]), 'device_kind': 'TPU v5 lite', 'correct': True, 'attempted': 10,
           'failed': 0, 'memory_peak_bytes': 1,
           'trace': {'busy_s': BUSY_S.get(cell_name, 1.0), 'window_s': 10.0, 'idle_share': 0.0, 'work': 10,
                     'breakdown': {'device_ops': [], 'idle_gaps': []}}}
    if '--seq-len' in args:
        run['lm'] = {'seq_len': int(args[args.index('--seq-len') + 1]), 'sequences': run['batch_size']}
        run['counters'] = dict(COUNTERS.get(cell_name, {'moe.local_slots': [1000.0]}))
        if slots is not None:
            run['counters']['moe.local_slots'] = [slots]
        scope_s = SCOPE_S.get(cell_name, {})
        run['trace']['scopes'] = {'scope_s': scope_s, 'busy_s': run['trace']['busy_s'], 'unscoped': []}
    run.update(runner_module(cell['runner']).needed_work(config, run))
    return run


def test_every_cell_that_trains_is_listed_by_exactly_one_share_of_the_whole_steps_peak(manifest):
    """A later cell cannot arrive without its share: a claimed gain in a cell is bounded by it. And it can arrive: the
    share reads what the cell's own runner says a step needs, so a new cell is an entry in the list and files."""
    trains = [w['name'] for w in manifest.data['workloads'] if 'train_img_per_s' in manifest.metrics_of(w['name'], 'end_to_end')]
    assert set(FOUR) <= set(trains)
    for cell in trains:
        shares = [m['name'] for m in manifest.data['per_layer'] if m['layer'] == 'step' and 'mfu' in m['name']
                  and m['moves'] == 'train_img_per_s' and cell in m.get('workloads', [cell])]
        assert len(shares) == 1, (cell, shares)
        value = manifest.reader(shares[0])(record(manifest, cell))
        assert isinstance(value, float) and 0 < value < 100, (cell, value)
    entry = manifest.per_layer['step_mfu.train']
    assert entry == manifest.data['per_layer'][5] and set(FOUR) <= set(entry['workloads']) and entry['unit'] == '%'
    assert 'lm_step_mfu.train' not in manifest.per_layer          # one share, one name


@pytest.mark.parametrize('cell,tflop,rel,step_ms,share', [
    (VIT, 128 * 105.4e-3, 1e-3, 149.11, 45.92),          # PERF.md section 4: 17.56 GMACs = 105.4 GFLOP a trained image
    (CNX, 128 * 92.1e-3, 1e-3, 156.35, 38.29),           # 15.35 GMACs = 92.1 GFLOP; both shares: ledger, PR 23 to PR 31
    (GLM, 59.0, 0.01, 841.27, None),                     # ISSUE 26: 604M MACs a token, 59 TFLOP a step, at even routing
    (SWA, 51.6, 2e-3, 966.57, None)])                    # PERF.md section 4: 8.60e12 MACs = 51.6 TFLOP at 98304 slots
def test_the_needed_operations_of_a_step_are_the_tables_of_perf_md_section_4(manifest, cell, tflop, rel, step_ms, share):
    run = record(manifest, cell, slots=EVEN_SLOTS.get(cell))
    needed = run['needed_step_flops']
    assert needed == pytest.approx(tflop * 1e12, rel=rel)
    sizes, lm = run['sizes'], run.get('lm')
    if cell == GLM:
        macs = lm_flops.forward_macs(sizes, 8192, 2, 40960)
        assert EVEN_SLOTS[GLM] == 40960 and needed == 6 * sum(macs.values()) and run['needed_macs'] == macs
        assert needed / 6 / 16384 == pytest.approx(604e6, rel=5e-3)
    elif cell == SWA:
        macs = swa_lm_flops.forward_macs(sizes, 16384, 1, 98304)
        assert EVEN_SLOTS[SWA] == 98304 and needed == 6 * sum(macs.values()) and run['needed_macs'] == macs
        assert (lm['seq_len'], lm['sequences']) == (16384, 1)
    else:
        assert needed == flops.train_flops_per_image(run['reference'], sizes) * 128 and 'needed_macs' not in run
    # the reader: those operations over the busy time a step over the bfloat16 peak; at the ledger's step time, its share
    read = manifest.reader('step_mfu.train')
    got = read(run)
    assert got == pytest.approx(100 * needed / (step_ms / 1e3) / 197e12, rel=1e-12) and 0 < got < 100
    if share is not None:
        assert got == pytest.approx(share, abs=0.01)
    if lm:                                               # the step's own slots, not the worst-case rows, are the work
        assert read(record(manifest, cell, slots=EVEN_SLOTS[cell] / 2)) < got
        runner = runner_module(manifest.cell(cell)['runner'])
        assert runner.needed_work(manifest.config(manifest.cell(cell)['config']), dict(run, counters={})) == {}
    # a record that does not say what its step needs has no share: the reader knows no family's shapes itself
    bare = {k: v for k, v in run.items() if not k.startswith('needed_')}
    assert read(bare) is None and read({}) is None and read(dict(run, trace=None)) is None


def test_an_image_reference_that_brings_its_own_shape_function_is_asked_for_it(manifest, monkeypatch):
    """`flops.FORWARD_MACS` knows two families; a third brings `forward_macs(sizes)` in its reference's file."""
    config = dict(manifest.config('vit_b16'), reference='later_family')
    later = types.SimpleNamespace(forward_macs=lambda sizes: sizes['embed_dim'] * 1000)
    monkeypatch.setattr('benchmarks.harness.manifest.reference_module', lambda name: {'later_family': later}[name])
    assert train_runner.needed_work(config, {'batch_size': 4}) == {'needed_step_flops': 768 * 1000 * 6 * 4}


def test_the_lm_cells_shares_at_their_measured_slots_are_the_builders(manifest):
    """35.8-35.9 % and 26.6-26.9 % (PERF.md sections 5-6, PR 26 and PR 31, read then as `lm_step_mfu.train`)."""
    read = manifest.reader('step_mfu.train')
    assert read(record(manifest, GLM)) == pytest.approx(35.85, abs=0.15)
    assert read(record(manifest, SWA)) == pytest.approx(26.65, abs=0.15)


@pytest.mark.parametrize('name', TEN)
def test_a_reading_is_a_number_on_its_familys_record_and_none_elsewhere(manifest, name):
    read = manifest.reader(name)                         # the reader's own file; LAYER, UNIT, MOVES agree with the entry
    entry = manifest.per_layer[name]
    theirs = [c for c in FOUR if name in THEIRS[c]]
    assert set(theirs) <= set(entry['workloads']) and not (set(FOUR) - set(theirs)) & set(entry['workloads'])
    assert entry['moves'] == 'train_img_per_s' and manifest.data['per_layer'].index(entry) >= 26
    assert entry['unit'] == ('ms' if name.endswith('_ms.train') else '%')          # device time in ms a step: no share of busy
    for cell in FOUR:
        value = read(record(manifest, cell))
        if cell in theirs:
            assert isinstance(value, float) and value > 0 and (entry['unit'] != '%' or value < 100), (cell, value)
        else:
            assert value is None, (cell, value)
    assert read({}) is None
    stripped = record(manifest, theirs[0])               # a parent older than the scopes and the counters
    del stripped['trace']['scopes'], stripped['counters']
    assert read(stripped) is None


def test_a_layers_device_time_does_not_move_when_another_layers_does(manifest):
    """Why the three are ms a step and no share of busy time: halve the route's time (the `perf_opt` ISSUE 35 prepares)
    and attention's metrics stand."""
    for cell, names in ((GLM, ['mla_device_ms.train', 'mla_core_mfu.train']), (SWA, SWA_ONLY)):
        before = record(manifest, cell)
        after = json.loads(json.dumps(before))
        after['trace']['scopes']['scope_s']['glm.moe.route'] /= 2
        after['trace']['scopes']['busy_s'] -= after['trace']['scopes']['scope_s']['glm.moe.route']
        for name in names:
            assert manifest.reader(name)(after) == manifest.reader(name)(before)
        for name in ('moe_route_device_ms.train', 'moe_device_ms.train'):
            assert manifest.reader(name)(after) < manifest.reader(name)(before)


@pytest.mark.parametrize('cell', FOUR)
def test_the_traced_line_carries_the_readings_in_the_cells_that_list_them(manifest, cell):
    device = {'platform': 'tpu', 'kind': 'TPU v5 lite', 'count': 1}
    line = json.loads(json.dumps(bench_run.result_line(manifest, cell, record(manifest, cell), device, trace=True)))
    got = {k: v['value'] for k, v in line['metrics'].items()}
    assert set(got) & set(TEN) == set(THEIRS[cell]) and 'step_mfu.train' in got and 'step_device_ms.train' in got
    assert all(v < 100 for k, v in got.items() if 'mfu' in k) and not [k for k in got if k.startswith('lm_')]
    assert all(line['metrics'][n]['unit'] == manifest.per_layer[n]['unit'] for n in got)
    if cell == GLM:                                      # PERF.md section 5's table, from its scope seconds
        assert got['moe_route_device_ms.train'] == pytest.approx(119.70) and got['mla_core_mfu.train'] == pytest.approx(48.7, abs=0.1)
        assert got['moe_experts_mfu.train'] == pytest.approx(22.0, abs=0.3) and got['mla_device_ms.train'] == pytest.approx(465.86)
        assert got['moe_device_ms.train'] == pytest.approx(203.01)
    if cell == SWA:
        assert got['moe_route_device_ms.train'] == pytest.approx(305.29) and got['attn_window_block_fill.train'] == pytest.approx(80.0, abs=0.05)
        assert got['attn_full_core_mfu.train'] == pytest.approx(50.0, abs=0.1) and got['attn_window_core_mfu.train'] == pytest.approx(40.4, abs=0.1)
        assert got['attn_proj_mfu.train'] == pytest.approx(51.4, abs=0.1) and got['attn_device_ms.train'] == pytest.approx(470.66)
        assert got['moe_device_ms.train'] == pytest.approx(372.83) and got['moe_experts_mfu.train'] == pytest.approx(19.4, abs=0.3)
