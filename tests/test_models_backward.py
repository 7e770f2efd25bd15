"""Model zoo gradient sweeps (reference: tests/test_models.py): the `test_*`
fixture models of the attention, mixer and hybrid families (the convolutional
ones: `test_models_backward_conv.py`), and one representative per family under
-m slow."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import nnx

import timm_tpu
from timm_tpu.models import get_pretrained_cfg, list_models

from models_common import backward_case, split_conv


# one small representative per family for gradient coverage (reference
# tests/test_models.py:213 runs backward over every model; we cover every
# FAMILY with its smallest member to keep CPU wall time bounded)
FAMILY_BACKWARD_MODELS = [
    'vit_tiny_patch16_224', 'vit_tiny_r_s16_p8_224', 'deit_tiny_distilled_patch16_224', 'eva02_tiny_patch14_336',
    'beit_base_patch16_224', 'cait_xxs24_224', 'xcit_nano_12_p16_224',
    'levit_128s', 'volo_d1_224', 'mvitv2_tiny', 'swin_tiny_patch4_window7_224', 'edgenext_xx_small',
    'repvit_m0_9', 'tiny_vit_5m_224', 'efficientformer_l1', 'efficientformerv2_s0',
    'mobilevit_xxs', 'mobilevitv2_050', 'twins_svt_small', 'mambaout_femto',
    'swinv2_tiny_window8_256', 'coatnet_pico_rw_224', 'maxvit_pico_rw_256',
    'mixer_s32_224', 'convnext_atto', 'resnet18', 'resnetv2_50', 'nf_resnet50',
    'regnetx_002', 'vgg11', 'densenet121', 'efficientnet_lite0',
    'mobilenetv3_small_100', 'mnasnet_050', 'lcnet_035', 'gernet_s',
    'halonet26t', 'lambda_resnet26t', 'botnet26t_256',
]
_family_backward = FAMILY_BACKWARD_MODELS


# halo blocked attention needs block_size (8) to divide every stage grid
_BACKWARD_SIZE_OVERRIDES = {
    'halonet26t': 256,
    'efficientformer_l1': 224,  # fixed 7x7 attention-bias table in the final stage
}


@pytest.mark.backward
@pytest.mark.slow
@pytest.mark.parametrize('model_name', _family_backward)
def test_model_backward_family(model_name):
    """Gradient sweep, one representative per family (markers: backward+slow).

    Also marked slow: each case re-traces and lowers a full-size model's
    fwd+bwd (~30s CPU; the persistent XLA cache only skips the compile, not
    the trace), so the 39-family sweep is a ~20-minute job that belongs in
    the explicit `-m backward` / `-m slow` tiers, not the fast suite. Until
    the flax-compat fixes these cases crashed at import time, which is the
    only reason they ever looked cheap enough for the fast tier."""
    cfg = get_pretrained_cfg(model_name)
    want = _BACKWARD_SIZE_OVERRIDES.get(model_name, 96)
    try:
        model = timm_tpu.create_model(model_name, img_size=want, num_classes=5)
        size = want
    except TypeError:
        model = timm_tpu.create_model(model_name, num_classes=5)
        size = cfg.input_size[-1] if cfg else 224
    model.train()
    x = jnp.asarray(np.random.rand(2, size, size, 3), jnp.float32)
    t = jnp.asarray([0, 1])

    def loss_fn(model):
        out = model(x)
        out = out[0] if isinstance(out, tuple) else out
        return jnp.mean((out - jax.nn.one_hot(t, out.shape[-1])) ** 2)

    grads = nnx.grad(loss_fn)(model)
    num_params = len(jax.tree.leaves(nnx.state(model, nnx.Param)))
    num_grads = len([g for g in jax.tree.leaves(grads) if g is not None])
    assert num_params == num_grads, 'Some params missing gradients'
    finite = all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))
    assert finite, 'NaN/Inf gradient'


@pytest.mark.base
@pytest.mark.parametrize('model_name', split_conv(list_models('test_*'))[1])
def test_model_backward(model_name):
    backward_case(model_name)
