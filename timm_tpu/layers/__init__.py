from .attention import Attention, AttentionRope, maybe_add_mask, scaled_dot_product_attention
from .attention2d import Attention2d, MultiQueryAttention2d, MultiQueryAttentionV2
from .attention_pool import AttentionPool2d, AttentionPoolLatent, RotAttentionPool2d
from .classifier import ClNormMlpClassifierHead, ClassifierHead, NormMlpClassifierHead, create_classifier
from .config import (
    is_exportable, is_scriptable, set_exportable, set_scriptable,
    set_fused_attn, use_fused_attn,
    norm_internal_dtype, resolve_dtype_arg, set_norm_internal_dtype,
    set_softmax_dtype, softmax_dtype, softmax_with_policy,
)
from .blur_pool import AvgPool2dAA, BlurPool2d, get_aa_layer
from .cbam import CbamModule, LightCbamModule
from .create_act import create_act_layer, get_act_fn, get_act_layer
from .create_attn import create_attn, get_attn
from .diff_attention import DiffAttention
from .eca import CecaModule, EcaModule
from .evo_norm import EvoNorm2dB0, EvoNorm2dS0, EvoNorm2dS0a
from .std_conv import ScaledStdConv2d, StdConv2d
from .create_conv2d import ConvNormAct, SeparableConvNormAct, create_conv2d, get_padding
from .cond_conv2d import CondConv2d, get_condconv_initializer
from .create_norm import create_norm_layer, get_norm_layer
from .drop import DropBlock2d, DropPath, Dropout, calculate_drop_path_rates, drop_block_2d, drop_path
from .filter_response_norm import FilterResponseNormAct2d, FilterResponseNormTlu2d
from .gather_excite import GatherExcite
from .global_context import GlobalContext
from .helpers import extend_tuple, make_divisible, to_1tuple, to_2tuple, to_3tuple, to_4tuple, to_ntuple
from .layer_scale import LayerScale, LayerScale2d
from .mixed_conv2d import MixedConv2d
from .mlp import ConvMlp, GatedMlp, GlobalResponseNorm, GlobalResponseNormMlp, GluMlp, Mlp, SwiGLU, SwiGLUPacked
from .non_local_attn import BatNonLocalAttn, BilinearAttnTransform, NonLocalAttn
from .norm import (
    BatchNorm2d, GroupNorm, GroupNorm1, LayerNorm, LayerNorm2d, LayerNormFp32,
    RmsNorm, RmsNorm2d, SimpleNorm, SimpleNorm2d,
)
from .norm_act import (
    BatchNormAct2d, FrozenBatchNormAct2d, GroupNorm1Act, GroupNormAct,
    LayerNormAct, LayerNormAct2d, get_norm_act_layer,
)
from .patch_dropout import PatchDropout
from .patch_embed import PatchEmbed, resample_patch_embed
from .pool import Pool2d, SelectAdaptivePool2d, adaptive_pool_feat_mult, create_pool2d, global_pool_nlc
from .pos_embed import resample_abs_pos_embed, resample_abs_pos_embed_nhwc
from .pos_embed_rel import (
    RelPosBias, RelPosBiasTf, RelPosMlp, gen_relative_log_coords, gen_relative_position_index,
    resize_rel_pos_bias_table_simple,
)
from .selective_kernel import SelectiveKernel, SelectiveKernelAttn
from .split_attn import SplitAttn
from .split_batchnorm import SplitBatchNorm2d, SplitBatchNormAct2d, convert_splitbn_model
from .test_time_pool import TestTimePoolHead, apply_test_time_pool
from .pos_embed_sincos import (
    RotaryEmbeddingCat, RotaryEmbeddingDinoV3, RotaryEmbeddingMixed,
    build_fourier_pos_embed, build_rotary_pos_embed, build_rotary_pos_embed_1d,
    build_sincos2d_pos_embed, create_rope_embed, freq_bands, pixel_freq_bands,
)
from .squeeze_excite import EffectiveSEModule, SEModule, SqueezeExcite
from .weight_init import lecun_normal_, ones_, trunc_normal_, trunc_normal_tf_, variance_scaling_, zeros_
from .hybrid_embed import HybridEmbed
from .latent_attention import LatentAttention, causal_attention
from .grouped_attention import GroupedQueryAttention, grouped_block_diffusion_attention, grouped_causal_attention
from .chunked_linear_attention import ChunkedLinearAttention, chunk_summaries, chunk_window_attention, chunk_window_pairs
from .short_conv import ShortConv, gated_short_conv
from .delta_attention import KimiDeltaAttention, chunked_delta_rule
from .moe import SparseMoe
