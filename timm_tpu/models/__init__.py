from ._builder import build_model_with_cfg, load_pretrained, resolve_pretrained_cfg
from ._factory import create_model, parse_model_name, safe_model_name
from ._features import FeatureGetterNet, FeatureInfo, feature_take_indices
from ._helpers import (
    clean_state_dict, load_checkpoint, load_state_dict, load_state_dict_into_model,
    model_state_dict, remap_state_dict, save_state_dict,
)
from ._manipulate import checkpoint_seq, group_parameters, group_with_matcher, named_parameters
from ._pretrained import DefaultCfg, PretrainedCfg
from ._registry import (
    generate_default_cfgs, get_arch_name, get_pretrained_cfg, get_pretrained_cfg_value,
    is_model, is_model_in_modules, is_model_pretrained, list_models, list_modules,
    list_pretrained, model_entrypoint, register_model, split_model_name_tag,
)

from .beit import Beit
from .byoanet import *  # noqa: F401,F403 — registers byoanet entrypoints
from .byobnet import ByoBlockCfg, ByoModelCfg, ByobNet
from .cait import Cait
from .convnext import ConvNeXt
from .deit import VisionTransformerDistilled
from .densenet import DenseNet
from .dpn import DPN
from .edgenext import EdgeNeXt
from .efficientformer import EfficientFormer
from .efficientformer_v2 import EfficientFormerV2
from .efficientnet import EfficientNet
from .eva import Eva
from .ghostnet import GhostNet
from .inception_v3 import InceptionV3
from .levit import Levit, LevitDistilled
from .mambaout import MambaOut
from .maxxvit import MaxxVit, MaxxVitCfg
from .metaformer import MetaFormer
from .mlp_mixer import MlpMixer
from .mobilenetv3 import MobileNetV3
from .mobilevit import *  # noqa: F401,F403 — registers mobilevit entrypoints
from .mvitv2 import MultiScaleVit, MultiScaleVitCfg
from .naflexvit import NaFlexVit
from .nfnet import NfCfg, NormFreeNet
from .regnet import RegNet
from .repvit import RepVit
from .res2net import Bottle2neck
from .resnest import ResNestBottleneck
from .resnet import ResNet
from .rexnet import RexNet
from .sknet import SelectiveKernelBasic, SelectiveKernelBottleneck
from .resnetv2 import ResNetV2
from .swin_transformer import SwinTransformer
from .tiny_vit import TinyVit
from .swin_transformer_v2 import SwinTransformerV2
from .twins import Twins
from .vgg import VGG
from .volo import VOLO
from .xcit import Xcit
from .vision_transformer import VisionTransformer
from .vision_transformer_hybrid import *  # noqa: F401,F403 — registers hybrid vit entrypoints
from .convmixer import ConvMixer
from .hardcorenas import *  # noqa: F401,F403 — registers hardcorenas entrypoints
from .starnet import StarNet
from .xception import Xception
from .pvt_v2 import PyramidVisionTransformerV2
from .repghost import RepGhostNet
from .vovnet import VovNet
from .pit import PoolingVisionTransformer
from .inception_v4 import InceptionV4
from .evabyte import EvaByte
from .glm4_moe_lite import Glm4MoeLite
from .lfm2_moe import Lfm2Moe
from .sdar_moe import SdarMoe
from .smallthinker import SmallThinker
from .solar_open2 import SolarOpen2
