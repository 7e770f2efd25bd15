"""The toy sizes the two SmallThinker test files share: hidden 64, 4 query heads on 2 key/value heads of width 16,
window 8 at S 32 in query blocks of 8, 8 experts top-2 with 2 held, vocabulary 256, 4 layers = one period (full,
window, window, window)."""
TOL = 1e-4
SIZES = dict(vocab_held=256, hidden_size=64, num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             moe_ffn_hidden_size=32, moe_num_primary_experts=8, moe_num_active_primary_experts=2, experts_held=2,
             expert_offset=0, rope_layout=[0, 1, 1, 1], sliding_window_layout=[0, 1, 1, 1], sliding_window_size=8,
             rope_theta=1.5e6, rms_norm_eps=1e-6)
S = 32
