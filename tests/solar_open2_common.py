"""The toy sizes the Solar-Open2 test files share: hidden 64, 8 query heads on 4 key/value heads of width 16 of which
this share holds 4 on 2, 8 delta-rule heads of 16 of which it holds 4 (chunks of 16, 4 taps, low-rank gates of 8), 8
experts of width 32 top-2 with 2 held and a shared one, vocabulary 256, and the share's four layers: attention, KDA,
KDA, KDA, all on experts. One bias vector for every expert layer, as the benchmark's runner places it: large enough
(+-0.05 beside scores that spread by ~0.01) to change many choices."""
import numpy as np

TOL = 1e-4
S = 32
BIAS = [float(x) for x in np.random.default_rng(5).uniform(-0.05, 0.05, 8).astype(np.float32)]
SIZES = dict(vocab_held=256, hidden_size=64, num_hidden_layers=4, gqa_layers=[0], num_attention_heads=8,
             num_key_value_heads=4, head_dim=16, heads_held=4, head_offset=0, short_conv_kernel_size=4, gate_rank=8,
             moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1, experts_held=2,
             expert_offset=0, routed_scaling_factor=1.0, rms_norm_eps=1e-5, expert_bias=BIAS)


def place_bias(model, bias=BIAS):
    """The vector into every expert layer's `score_bias`, by the benchmark runner's own function."""
    from benchmarks.harness.sconv_lm_train_runner import place_expert_bias
    place_expert_bias(model, bias)
    return model


def seeded(ref, seed: int, sizes=SIZES):
    """The benchmark's seeded weights for `sizes`: `weights.make` and the reference's two seeded vectors."""
    from benchmarks.harness import weights
    return ref.finish_weights(seed, sizes, weights.make(seed, ref.init_spec(sizes)))
