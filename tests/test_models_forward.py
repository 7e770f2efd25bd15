"""Model zoo forward sweep (reference: tests/test_models.py): the fast
per-family representatives of the attention, mixer and hybrid families (the
convolutional ones: `test_models_forward_conv.py`), and the whole registry
under -m slow."""
import pytest

from models_common import SLOW_MODELS, TEST_MODELS, forward_case, split_conv


@pytest.mark.base
@pytest.mark.parametrize('model_name', split_conv(TEST_MODELS)[1])
def test_model_forward(model_name):
    forward_case(model_name)


@pytest.mark.slow
@pytest.mark.parametrize('model_name', SLOW_MODELS)
def test_model_forward_slow(model_name):
    forward_case(model_name, rows=1)
