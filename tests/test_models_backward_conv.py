"""Model zoo gradient sweep, the convolutional `test_*` fixture models (the
others: `test_models_backward.py`)."""
import pytest

from timm_tpu.models import list_models

from models_common import backward_case, split_conv


@pytest.mark.base
@pytest.mark.parametrize('model_name', split_conv(list_models('test_*'))[0])
def test_model_backward(model_name):
    backward_case(model_name)
