"""Model zoo tests (reference: tests/test_models.py): feature extraction and
the single cases. The forward, backward and cfg sweeps are in
`test_models_forward.py`, `test_models_backward.py` and `test_models_cfg.py`."""
import jax.numpy as jnp
import numpy as np
import pytest
from flax import nnx

import timm_tpu
from timm_tpu.models import list_models

from models_common import FWD_SIZE, create_small


@pytest.mark.features
@pytest.mark.parametrize('model_name', list_models('test_*'))
def test_model_forward_intermediates(model_name):
    model, size = create_small(model_name)
    model.eval()
    x = jnp.asarray(np.random.rand(1, size, size, 3), jnp.float32)
    final, intermediates = model.forward_intermediates(x, indices=(0, 1))
    assert len(intermediates) == 2
    for feat in intermediates:
        assert feat.ndim == 4  # NHWC grid
        assert feat.shape[0] == 1
    # parity with features_only wrapper
    try:
        wrapped = timm_tpu.create_model(
            model_name, img_size=size, num_classes=10, features_only=True, out_indices=(0, 1))
    except TypeError:
        wrapped = timm_tpu.create_model(model_name, num_classes=10, features_only=True, out_indices=(0, 1))
    wrapped.eval()
    feats = wrapped(x)
    assert len(feats) == 2
    assert feats[-1].shape == intermediates[-1].shape


@pytest.mark.features
def test_features_info():
    model = timm_tpu.create_model('test_vit', features_only=True, out_indices=(0, 1))
    assert len(model.feature_info.channels()) == 2
    assert all(c == 64 for c in model.feature_info.channels())


@pytest.mark.base
def test_model_no_weight_decay():
    model = timm_tpu.create_model('test_vit')
    nwd = model.no_weight_decay()
    assert 'pos_embed' in nwd and 'cls_token' in nwd


@pytest.mark.base
def test_model_group_matcher():
    from timm_tpu.models import group_parameters
    model = timm_tpu.create_model('test_vit')
    groups = group_parameters(model, model.group_matcher())
    # stem group + per-block groups + final-norm merged into last
    assert len(groups) >= 3


@pytest.mark.base
def test_grad_checkpointing_forward_match():
    model = timm_tpu.create_model('test_vit', num_classes=10, img_size=FWD_SIZE)
    model.eval()
    x = jnp.asarray(np.random.rand(1, FWD_SIZE, FWD_SIZE, 3), jnp.float32)
    out_ref = model(x)
    model.set_grad_checkpointing(True)
    out_ckpt = model(x)
    assert bool(jnp.allclose(out_ref, out_ckpt, atol=1e-5))


@pytest.mark.base
def test_state_dict_roundtrip(tmp_path):
    from timm_tpu.models import load_checkpoint, model_state_dict, save_state_dict
    m1 = timm_tpu.create_model('test_vit', num_classes=10, img_size=FWD_SIZE, seed=0)
    m2 = timm_tpu.create_model('test_vit', num_classes=10, img_size=FWD_SIZE, seed=99)
    m1.eval(), m2.eval()
    x = jnp.asarray(np.random.rand(1, FWD_SIZE, FWD_SIZE, 3), jnp.float32)
    path = str(tmp_path / 'w.safetensors')
    save_state_dict(model_state_dict(m1), path)
    load_checkpoint(m2, path)
    assert bool(jnp.allclose(m1(x), m2(x), atol=1e-6))


@pytest.mark.base
def test_torch_checkpoint_conversion():
    torch = pytest.importorskip('torch')
    from timm_tpu.models._torch_convert import convert_torch_state_dict
    sd = {
        'head.weight': torch.zeros(10, 64).numpy(),
        'head.bias': torch.zeros(10).numpy(),
        'patch_embed.proj.weight': torch.zeros(64, 3, 16, 16).numpy(),
        'norm.weight': torch.ones(64).numpy(),
        'bn.running_mean': torch.zeros(64).numpy(),
    }
    out = convert_torch_state_dict(sd)
    assert out['head.kernel'].shape == (64, 10)
    assert out['patch_embed.proj.kernel'].shape == (16, 16, 3, 64)
    assert 'norm.scale' in out
    assert 'bn.mean' in out


@pytest.mark.base
def test_byobnet_reparameterize_matches():
    """RepVGG/MobileOne branch fusion must be numerically transparent."""
    from timm_tpu.utils import reparameterize_model
    x = jnp.asarray(np.random.RandomState(0).rand(1, 64, 64, 3), jnp.float32)
    for name in ('repvgg_a0', 'mobileone_s0'):
        m = timm_tpu.create_model(name, num_classes=10)
        m.train()
        _ = m(x + 0.3)  # populate BN running stats with non-trivial values
        m.eval()
        before = np.asarray(m(x))
        reparameterize_model(m)
        after = np.asarray(m(x))
        rel = np.abs(before - after).max() / max(1.0, np.abs(before).max())
        assert rel < 1e-5, (name, rel)


@pytest.mark.base
def test_byobnet_head_types():
    """attn_abs / attn_rot / mlp heads produce correctly-shaped outputs."""
    from timm_tpu.models.byobnet import ByoBlockCfg, ByoModelCfg, ByobNet
    cfg = ByoModelCfg(
        blocks=(ByoBlockCfg(type='basic', d=1, c=32, s=2),),
        stem_chs=16, stem_pool='',
    )
    x = jnp.asarray(np.random.rand(2, 64, 64, 3), jnp.float32)
    from dataclasses import replace as dc_replace
    for head_type, kw in (('classifier', {}), ('mlp', dict(head_hidden_size=24)),
                          ('attn_abs', dict(head_hidden_size=64)), ('attn_rot', dict(head_hidden_size=64))):
        m = ByobNet(dc_replace(cfg, head_type=head_type, **kw), num_classes=10, img_size=64, rngs=nnx.Rngs(0))
        m.eval()
        assert m(x).shape == (2, 10), head_type
        pre = m.forward_head(m.forward_features(x), pre_logits=True)
        assert pre.ndim == 2 and pre.shape[0] == 2, head_type
