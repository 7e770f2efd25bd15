"""The toy sizes the LFM2 test files share: hidden 64, 4 query heads on 2 key/value heads of width 16, 3 taps, a
dense width of 160, 8 experts of width 32 top-2 with 2 held, vocabulary 256, and the share's five layers: a dense
conv layer, then one period (attention, conv, conv, conv) on experts. One bias vector for every expert layer, as the
benchmark's runner places it: large enough (+-0.05 beside scores that spread by ~0.01) to change many choices."""
import numpy as np

TOL = 1e-4
S = 32
BIAS = [float(x) for x in np.random.default_rng(5).uniform(-0.05, 0.05, 8).astype(np.float32)]
SIZES = dict(vocab_held=256, hidden_size=64, num_hidden_layers=5,
             layer_types=['conv', 'full_attention', 'conv', 'conv', 'conv'], num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, conv_L_cache=3, intermediate_size=160, num_dense_layers=1,
             moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2, experts_held=2, expert_offset=0,
             routed_scaling_factor=1.0, rope_theta=1e6, norm_eps=1e-5, expert_bias=BIAS)


def place_bias(model, bias=BIAS):
    """The vector into every expert layer's `score_bias`, by the benchmark runner's own function."""
    from benchmarks.harness.sconv_lm_train_runner import place_expert_bias
    place_expert_bias(model, bias)
    return model
