"""Model zoo forward sweep, the convolutional families' fast representatives
(the others: `test_models_forward.py`)."""
import pytest

from models_common import TEST_MODELS, forward_case, split_conv


@pytest.mark.base
@pytest.mark.parametrize('model_name', split_conv(TEST_MODELS)[0])
def test_model_forward(model_name):
    forward_case(model_name)
