"""Utility tests (reference: tests/test_utils.py — freeze/EMA/AGC/unwrap; plus
the extraction/relabel helpers)."""
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import nnx

import timm_tpu


def test_ema_update_math():
    from timm_tpu.utils import ema_update
    ema = {'w': jnp.ones((4,))}
    new = {'w': jnp.zeros((4,))}
    out = ema_update(ema, new, decay=0.9)
    np.testing.assert_allclose(np.asarray(out['w']), 0.9, rtol=1e-6)


def test_ema_decay_warmup():
    from timm_tpu.utils import ModelEmaV3
    ema = ModelEmaV3(decay=0.999, use_warmup=True)
    assert ema.get_decay(0) == 0.0
    assert 0.0 < ema.get_decay(10) < ema.get_decay(1000) <= 0.999


def test_attention_extract_vit():
    from timm_tpu.utils import AttentionExtract
    m = timm_tpu.create_model('test_vit', num_classes=5)
    m.eval()
    x = jnp.asarray(np.random.RandomState(0).rand(1, 160, 160, 3), jnp.float32)
    maps = AttentionExtract(m, names=['blocks.0.attn', 1])(x)
    assert set(maps) == {'blocks.0.attn', 'blocks.1.attn'}
    for v in maps.values():
        assert v.shape == (1, 2, 101, 101)
        assert bool(jnp.allclose(v.sum(-1), 1.0, atol=1e-4))


def test_attention_extract_rope_model():
    from timm_tpu.utils import AttentionExtract
    m = timm_tpu.create_model('test_eva', num_classes=5)
    m.eval()
    x = jnp.asarray(np.random.RandomState(0).rand(1, 160, 160, 3), jnp.float32)
    maps = AttentionExtract(m, names=[0])(x)
    v = maps['blocks.0.attn']
    assert bool(jnp.allclose(v.sum(-1), 1.0, atol=1e-4))


def test_real_labels(tmp_path):
    from timm_tpu.data import RealLabelsImagenet
    rj = tmp_path / 'real.json'
    json.dump([[1], [2], []], open(rj, 'w'))
    rl = RealLabelsImagenet(
        [f'ILSVRC2012_val_{i + 1:08d}.JPEG' for i in range(3)], real_json=str(rj))
    logits = np.zeros((3, 5))
    logits[0, 1] = 9  # correct
    logits[1, 0] = 9  # wrong (top1), label 2 not in top1
    logits[1, 2] = 8  # ...but in top5
    logits[2, 4] = 9  # excluded (no labels)
    rl.add_result(logits)
    acc = rl.get_accuracy()
    assert acc[1] == pytest.approx(50.0)
    assert acc[5] == pytest.approx(100.0)
    # top-k path equivalence
    rl2 = RealLabelsImagenet(
        [f'ILSVRC2012_val_{i + 1:08d}.JPEG' for i in range(3)], real_json=str(rj))
    topk = np.argsort(logits, axis=-1)[:, ::-1][:, :5]
    rl2.add_result(topk, is_topk=True)
    assert rl2.get_accuracy() == acc


def test_freeze_unfreeze():
    from timm_tpu.utils import freeze, unfreeze
    m = timm_tpu.create_model('test_vit', num_classes=5)
    n_before = len(jax.tree.leaves(nnx.state(m, nnx.Param)))
    freeze(m, 'patch_embed')
    n_frozen = len(jax.tree.leaves(nnx.state(m, nnx.Param)))
    assert n_frozen < n_before
    unfreeze(m, 'patch_embed')
    assert len(jax.tree.leaves(nnx.state(m, nnx.Param))) == n_before


def test_flatten_unflatten_roundtrip():
    from timm_tpu.utils import flatten_pytree, unflatten_into
    tree = {'a': jnp.ones((2, 2)), 'b': [jnp.zeros((3,)), jnp.full((1,), 7.0)]}
    flat = flatten_pytree(tree, 'x')
    assert all(k.startswith('x.') for k in flat)
    rebuilt = unflatten_into(tree, flat, 'x')
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(rebuilt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_program_imports_none_of_its_cpu_tools():
    """What a benchmark cell imports is what the program is: in a fresh
    interpreter, `import train` and a `ClassificationTask` built on `test_vit`
    pull in none of the CPU tools (`analysis/source_rules.py`'s `layering` rule
    reads the same from the source; this reads it from `sys.modules`)."""
    code = '''
import sys
import train
import timm_tpu
from timm_tpu.optim import create_optimizer_v2
from timm_tpu.task import ClassificationTask
model = timm_tpu.create_model('test_vit', num_classes=10, img_size=32)
ClassificationTask(model, optimizer=create_optimizer_v2(model, opt='adamw', lr=1e-3))
tools = ('bench', 'timm_tpu.perfbudget', 'timm_tpu.analysis', 'timm_tpu.autotune')
print('LOADED', sorted(m for m in sys.modules if m in tools or m.startswith(tuple(t + '.' for t in tools))))
'''
    r = subprocess.run([sys.executable, '-c', code], cwd=REPO_ROOT, capture_output=True, text=True,
                       env=dict(os.environ, JAX_PLATFORMS='cpu'), timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert 'LOADED []' in r.stdout, r.stdout[-2000:]


def test_readme_names_only_what_is_in_the_tree():
    """Every top-level script, every path in backticks under `timm_tpu/`,
    `benchmarks/` or `tests/`, and every `python -m timm_tpu.<module>` that
    README.md names exists."""
    with open(os.path.join(REPO_ROOT, 'README.md'), encoding='utf-8') as f:
        readme = f.read()
    scripts = set(re.findall(r'(?<![\w/.-])([A-Za-z_]\w*\.py)\b', readme))
    paths = {p.split('::')[0].rstrip('.,;:)')
             for p in re.findall(r'`((?:timm_tpu|benchmarks|tests)/[^`\s]*)', readme) if not set(p) & set('<*{')}
    modules = {m.replace('.', '/') for m in re.findall(r'python3? -m (timm_tpu[\w.]*)', readme)}
    assert len(scripts) >= 5 and len(paths) >= 10 and len(modules) >= 3, (scripts, paths, modules)
    missing = sorted(p for p in scripts | paths if not os.path.exists(os.path.join(REPO_ROOT, p)))
    missing += sorted(m for m in modules if not any(
        os.path.exists(os.path.join(REPO_ROOT, m + tail)) for tail in ('.py', '/__main__.py')))
    assert not missing, f'README.md names what the tree does not have: {missing}'
