"""Model zoo cfg consistency and classifier reset (reference:
tests/test_models.py)."""
import jax.numpy as jnp
import numpy as np
import pytest

from timm_tpu.models import get_pretrained_cfg, list_models

from models_common import ALL_MODELS, create_small


@pytest.mark.cfg
@pytest.mark.parametrize('model_name', ALL_MODELS)
def test_model_default_cfg(model_name):
    cfg = get_pretrained_cfg(model_name)
    if cfg is None:
        pytest.skip('no pretrained cfg')
    # headless feature models (e.g. CLIP trunks) legitimately ship num_classes=0
    assert cfg.num_classes >= 0
    assert len(cfg.input_size) == 3
    assert cfg.classifier is not None
    assert cfg.first_conv is not None


@pytest.mark.cfg
@pytest.mark.parametrize('model_name', list_models('test_*'))
def test_model_classifier_reset(model_name):
    model, size = create_small(model_name)
    model.eval()
    x = jnp.asarray(np.random.rand(1, size, size, 3), jnp.float32)
    # pre-logits / identity head
    model.reset_classifier(0)
    out = model(x)
    # heads with a pre-logits MLP keep it on reset (reference ClNormMlpClassifierHead
    # semantics: reset() without reset_other preserves hidden layers)
    want = {model.num_features, getattr(model, 'head_hidden_size', model.num_features)}
    assert out.ndim == 2 and out.shape[-1] in want
    # new head size
    model.reset_classifier(7)
    assert model(x).shape == (1, 7)
