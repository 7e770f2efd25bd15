"""EVA / EVA02 (reference: timm/models/eva.py:1-3096), TPU-native.

ViT with rotary position embeddings (shared per-model ROPE table, applied to
non-prefix tokens), optional SwiGLU MLP with inner norm, and pre/post-norm
block options. Covers the eva02 family (the reference zoo's top-1 leader).

Not `models/evabyte.py` (EvaByte, a byte-level language model whose mixer is the
linear attention EVA, arXiv:2302.04542): the two share a name and nothing else.
"""
from __future__ import annotations

from functools import partial

from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from flax import nnx

from ..layers import (
    AttentionPoolLatent, AttentionRope, Dropout, DropPath, GluMlp, LayerNorm,
    LayerScale, Mlp, PatchEmbed, RotaryEmbeddingCat, SwiGLU,
    calculate_drop_path_rates, create_rope_embed, get_norm_layer,
    global_pool_nlc, resample_abs_pos_embed, to_2tuple, trunc_normal_, zeros_,
)
from ..layers.drop import apply_drop_path
from ._builder import build_model_with_cfg
from ._features import feature_take_indices
from ._manipulate import (
    BlockStackError, checkpoint_seq, drop_path_scan_inputs, resolve_block_scan,
    scan_block_stack, warn_scan_fallback,
)
from ._registry import generate_default_cfgs, register_model

__all__ = ['Eva', 'EvaBlock', 'EvaAttention']


class EvaAttention(nnx.Module):
    """ROPE attention with optional unfused q/k/v projections — eva02
    base/large checkpoints store separate q/k/v with no k bias
    (reference eva.py EvaAttention)."""

    def __init__(
            self,
            dim: int,
            num_heads: int = 8,
            qkv_bias: bool = True,
            qkv_fused: bool = True,
            qk_norm: bool = False,
            attn_drop: float = 0.0,
            proj_drop: float = 0.0,
            norm_layer: Optional[Callable] = None,
            scale_norm: bool = False,
            rotate_half: bool = False,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        from functools import partial as _partial
        from ..layers.attention import scaled_dot_product_attention, apply_rot_embed_cat
        from ..layers.drop import Dropout as _Dropout, dropout_rng_key as _drk
        assert dim % num_heads == 0
        self.num_heads = num_heads
        self.head_dim = dim // num_heads
        self.scale = self.head_dim ** -0.5
        self.attn_drop_rate = attn_drop
        self.qkv_fused = qkv_fused
        self.rotate_half = rotate_half
        self._sdpa = scaled_dot_product_attention
        self._rot = apply_rot_embed_cat
        self._drk = _drk

        linear = _partial(
            nnx.Linear, dtype=dtype, param_dtype=param_dtype,
            kernel_init=trunc_normal_(std=0.02), bias_init=zeros_, rngs=rngs)
        if qkv_fused:
            # reference layout: unbiased fused projection + separate q/v bias
            # params (k bias fixed at zero) — BEiT-style (reference eva.py:161)
            self.qkv = linear(dim, dim * 3, use_bias=False)
            self.q_proj = self.k_proj = self.v_proj = None
            if qkv_bias:
                self.q_bias = nnx.Param(jnp.zeros((dim,), param_dtype))
                self.v_bias = nnx.Param(jnp.zeros((dim,), param_dtype))
            else:
                self.q_bias = self.v_bias = None
        else:
            self.qkv = None
            self.q_bias = self.v_bias = None
            self.q_proj = linear(dim, dim, use_bias=qkv_bias)
            self.k_proj = linear(dim, dim, use_bias=False)
            self.v_proj = linear(dim, dim, use_bias=qkv_bias)
        self.q_norm = norm_layer(self.head_dim, rngs=rngs) if qk_norm else None
        self.k_norm = norm_layer(self.head_dim, rngs=rngs) if qk_norm else None
        self.attn_drop = _Dropout(attn_drop, rngs=rngs)
        self.norm = norm_layer(dim, rngs=rngs) if scale_norm else None
        self.proj = linear(dim, dim)
        self.proj_drop = _Dropout(proj_drop, rngs=rngs)

    def __call__(self, x, rope=None, attn_mask=None):
        B, N, C = x.shape
        if self.qkv_fused:
            qkv = self.qkv(x)
            if self.q_bias is not None:
                bias = jnp.concatenate([
                    self.q_bias[...], jnp.zeros_like(self.q_bias[...]), self.v_bias[...]])
                qkv = qkv + bias.astype(qkv.dtype)
            qkv = qkv.reshape(B, N, 3, self.num_heads, self.head_dim).transpose(2, 0, 3, 1, 4)
            q, k, v = qkv[0], qkv[1], qkv[2]
        else:
            q = self.q_proj(x).reshape(B, N, self.num_heads, self.head_dim).transpose(0, 2, 1, 3)
            k = self.k_proj(x).reshape(B, N, self.num_heads, self.head_dim).transpose(0, 2, 1, 3)
            v = self.v_proj(x).reshape(B, N, self.num_heads, self.head_dim).transpose(0, 2, 1, 3)
        from ..parallel import shard_activation
        q, k, v = (shard_activation(t, 'heads') for t in (q, k, v))
        if self.q_norm is not None:
            q = self.q_norm(q)
        if self.k_norm is not None:
            k = self.k_norm(k)
        if rope is not None:
            num_prefix = N - rope.shape[-2]
            half = self.rotate_half
            if num_prefix > 0:
                q = jnp.concatenate(
                    [q[..., :num_prefix, :], self._rot(q[..., num_prefix:, :], rope, half=half)], axis=-2)
                k = jnp.concatenate(
                    [k[..., :num_prefix, :], self._rot(k[..., num_prefix:, :], rope, half=half)], axis=-2)
            else:
                q, k = self._rot(q, rope, half=half), self._rot(k, rope, half=half)
            q = q.astype(v.dtype)
            k = k.astype(v.dtype)
        dropout_p = 0.0 if self.attn_drop.deterministic else self.attn_drop_rate
        dropout_key = self._drk(self.attn_drop) if dropout_p > 0.0 else None
        x = self._sdpa(q, k, v, attn_mask=attn_mask, dropout_p=dropout_p,
                       dropout_key=dropout_key, scale=self.scale)
        x = shard_activation(x.transpose(0, 2, 1, 3).reshape(B, N, C), 'hidden')
        if self.norm is not None:
            x = self.norm(x)
        x = self.proj(x)
        return self.proj_drop(x)


class EvaBlock(nnx.Module):
    def __init__(
            self,
            dim: int,
            num_heads: int,
            qkv_bias: bool = True,
            qkv_fused: bool = True,
            qk_norm: bool = False,
            mlp_ratio: float = 4.0,
            swiglu_mlp: bool = False,
            scale_mlp: bool = False,
            scale_attn_inner: bool = False,
            attn_type: str = 'eva',
            rotate_half: bool = False,
            num_prefix_tokens: int = 1,
            swiglu_align_to: int = 0,
            proj_drop: float = 0.0,
            attn_drop: float = 0.0,
            drop_path: float = 0.0,
            init_values: Optional[float] = None,
            act_layer: Union[str, Callable] = 'gelu',
            norm_layer: Callable = LayerNorm,
            use_post_norm: bool = False,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        # post-norm (beit3-style, reference eva.py EvaBlockPostNorm:430-525):
        # residual adds norm(branch(x)) and layer scale is ignored
        self.use_post_norm = use_post_norm
        if use_post_norm:
            init_values = None
        self.norm1 = norm_layer(dim, rngs=rngs)
        if attn_type == 'rope':
            # plain fused/unfused rope attention (PE / naver rope-vit,
            # reference eva.py:327,460 attn_cls selection)
            self.attn = AttentionRope(
                dim,
                num_heads=num_heads,
                qkv_bias=qkv_bias,
                qkv_fused=qkv_fused,
                qk_norm=qk_norm,
                scale_norm=scale_attn_inner,
                num_prefix_tokens=num_prefix_tokens,
                rotate_half=rotate_half,
                attn_drop=attn_drop,
                proj_drop=proj_drop,
                norm_layer=norm_layer,
                dtype=dtype,
                param_dtype=param_dtype,
                rngs=rngs,
            )
        else:
            self.attn = EvaAttention(
                dim,
                num_heads=num_heads,
                qkv_bias=qkv_bias,
                qkv_fused=qkv_fused,
                qk_norm=qk_norm,
                attn_drop=attn_drop,
                proj_drop=proj_drop,
                norm_layer=norm_layer,
                scale_norm=scale_attn_inner,
                rotate_half=rotate_half,
                dtype=dtype,
                param_dtype=param_dtype,
                rngs=rngs,
            )
        self.ls1 = LayerScale(dim, init_values, param_dtype=param_dtype, rngs=rngs) if init_values else None
        self.drop_path1 = DropPath(drop_path, rngs=rngs)
        self.norm2 = norm_layer(dim, rngs=rngs)
        hidden = int(dim * mlp_ratio)
        if swiglu_mlp:
            if scale_mlp or swiglu_align_to:
                # norm/alignment requires the un-packed variant (reference eva.py block init)
                self.mlp = SwiGLU(
                    dim, hidden, norm_layer=norm_layer if scale_mlp else None,
                    align_to=swiglu_align_to,
                    drop=proj_drop, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
            else:
                # packed weights (one fc1) to match eva02 tiny/small checkpoints
                self.mlp = GluMlp(
                    dim, hidden * 2, act_layer='silu', gate_last=False,
                    drop=proj_drop, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        else:
            self.mlp = Mlp(
                dim, hidden, act_layer=act_layer,
                norm_layer=norm_layer if scale_mlp else None,
                drop=proj_drop, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.ls2 = LayerScale(dim, init_values, param_dtype=param_dtype, rngs=rngs) if init_values else None
        self.drop_path2 = DropPath(drop_path, rngs=rngs)

    def __call__(self, x, rope=None, attn_mask=None, drop_path_override=None):
        if self.use_post_norm:
            x = x + apply_drop_path(
                self.norm1(self.attn(x, rope=rope, attn_mask=attn_mask)),
                self.drop_path1, drop_path_override, 0)
            x = x + apply_drop_path(
                self.norm2(self.mlp(x)), self.drop_path2, drop_path_override, 1)
            return x
        y = self.attn(self.norm1(x), rope=rope, attn_mask=attn_mask)
        if self.ls1 is not None:
            y = self.ls1(y)
        x = x + apply_drop_path(y, self.drop_path1, drop_path_override, 0)
        y = self.mlp(self.norm2(x))
        if self.ls2 is not None:
            y = self.ls2(y)
        x = x + apply_drop_path(y, self.drop_path2, drop_path_override, 1)
        return x


class Eva(nnx.Module):
    def __init__(
            self,
            img_size: Union[int, Tuple[int, int]] = 224,
            patch_size: int = 16,
            in_chans: int = 3,
            num_classes: int = 1000,
            global_pool: str = 'avg',
            embed_dim: int = 768,
            depth: int = 12,
            num_heads: int = 12,
            qkv_bias: bool = True,
            qkv_fused: bool = True,
            qk_norm: bool = False,
            mlp_ratio: float = 4.0,
            swiglu_mlp: bool = False,
            scale_mlp: bool = False,
            scale_attn_inner: bool = False,
            swiglu_align_to: int = 0,
            attn_type: str = 'eva',
            drop_rate: float = 0.0,
            pos_drop_rate: float = 0.0,
            proj_drop_rate: float = 0.0,
            attn_drop_rate: float = 0.0,
            drop_path_rate: float = 0.0,
            init_values: Optional[float] = None,
            class_token: bool = True,
            num_reg_tokens: int = 0,
            no_embed_class: bool = False,
            use_abs_pos_emb: bool = True,
            use_rot_pos_emb: bool = False,
            rope_type: Optional[str] = 'cat',
            ref_feat_shape: Optional[Tuple[int, int]] = None,
            rope_grid_offset: float = 0.0,
            rope_grid_indexing: str = 'ij',
            rope_temperature: float = 10000.0,
            rope_rotate_half: bool = False,
            use_post_norm: bool = False,
            use_pre_transformer_norm: bool = False,
            use_post_transformer_norm: Optional[bool] = None,
            use_fc_norm: Optional[bool] = None,
            attn_pool_num_heads: Optional[int] = None,
            attn_pool_mlp_ratio: Optional[float] = None,
            dynamic_img_size: bool = False,
            norm_layer: Optional[Union[str, Callable]] = None,
            act_layer: Union[str, Callable] = 'gelu',
            block_scan: Optional[bool] = None,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        assert global_pool in ('', 'avg', 'avgmax', 'max', 'token', 'map')
        norm_layer = get_norm_layer(norm_layer) or LayerNorm
        self.num_classes = num_classes
        self.global_pool = global_pool
        self.num_features = self.head_hidden_size = self.embed_dim = embed_dim
        self.num_prefix_tokens = (1 if class_token else 0) + num_reg_tokens
        self.num_reg_tokens = num_reg_tokens
        self.no_embed_class = no_embed_class
        self.dynamic_img_size = dynamic_img_size
        self.grad_checkpointing = False
        self.block_scan = resolve_block_scan(block_scan)

        # norm / pool placement (reference eva.py:643-651)
        activate_pre_norm = use_pre_transformer_norm
        activate_fc_norm = use_fc_norm if use_fc_norm is not None else global_pool == 'avg'
        activate_post_norm = use_post_transformer_norm if use_post_transformer_norm is not None \
            else not activate_fc_norm

        embed_args = {}
        if dynamic_img_size:
            embed_args.update(dict(strict_img_size=False))
        self.patch_embed = PatchEmbed(
            img_size=img_size, patch_size=patch_size, in_chans=in_chans, embed_dim=embed_dim,
            bias=not use_pre_transformer_norm,
            dtype=dtype, param_dtype=param_dtype, rngs=rngs, **embed_args)
        num_patches = self.patch_embed.num_patches

        self.cls_token = nnx.Param(jnp.zeros((1, 1, embed_dim), param_dtype)) if class_token else None
        self.reg_token = nnx.Param(
            trunc_normal_(std=0.02)(rngs.params(), (1, num_reg_tokens, embed_dim), param_dtype)) \
            if num_reg_tokens else None

        num_pos_tokens = num_patches if no_embed_class else num_patches + self.num_prefix_tokens
        if use_abs_pos_emb:
            self.pos_embed = nnx.Param(trunc_normal_(std=0.02)(
                rngs.params(), (1, num_pos_tokens, embed_dim), param_dtype))
        else:
            self.pos_embed = None
        self.pos_drop = Dropout(pos_drop_rate, rngs=rngs)

        self.rope_mixed = False
        if use_rot_pos_emb:
            ref_feat_shape = to_2tuple(ref_feat_shape) if ref_feat_shape is not None else None
            rope_kwargs = dict(
                dim=embed_dim,
                num_heads=num_heads,
                feat_shape=None if dynamic_img_size else self.patch_embed.grid_size,
                temperature=rope_temperature,
                grid_indexing=rope_grid_indexing,
            )
            if rope_type == 'mixed':
                rope_kwargs.update(dict(depth=depth))
                self.rope_mixed = True
            elif rope_type == 'cat':
                rope_kwargs.update(dict(
                    in_pixels=False,
                    grid_offset=rope_grid_offset,
                    ref_feat_shape=ref_feat_shape,
                ))
            elif rope_type == 'dinov3':
                rope_kwargs.update(dict(rotate_half=rope_rotate_half))
            self.rope = create_rope_embed(rope_type=rope_type, rngs=rngs, **rope_kwargs)
        else:
            self.rope = None

        self.norm_pre = norm_layer(embed_dim, rngs=rngs) if activate_pre_norm else None

        dpr = calculate_drop_path_rates(drop_path_rate, depth)
        self.blocks = nnx.List([
            EvaBlock(
                dim=embed_dim,
                num_heads=num_heads,
                qkv_bias=qkv_bias,
                qkv_fused=qkv_fused,
                qk_norm=qk_norm,
                mlp_ratio=mlp_ratio,
                swiglu_mlp=swiglu_mlp,
                scale_mlp=scale_mlp,
                scale_attn_inner=scale_attn_inner,
                swiglu_align_to=swiglu_align_to,
                attn_type=attn_type,
                rotate_half=rope_rotate_half,
                num_prefix_tokens=self.num_prefix_tokens,
                proj_drop=proj_drop_rate,
                attn_drop=attn_drop_rate,
                drop_path=dpr[i],
                init_values=init_values,
                act_layer=act_layer,
                norm_layer=norm_layer,
                use_post_norm=use_post_norm,
                dtype=dtype,
                param_dtype=param_dtype,
                rngs=rngs,
            )
            for i in range(depth)
        ])
        reduction = self.patch_embed.patch_size[0]
        self.feature_info = [
            dict(module=f'blocks.{i}', num_chs=embed_dim, reduction=reduction) for i in range(depth)]

        self.norm = norm_layer(embed_dim, rngs=rngs) if activate_post_norm else None
        if global_pool == 'map':
            self.attn_pool = AttentionPoolLatent(
                embed_dim,
                num_heads=attn_pool_num_heads or num_heads,
                mlp_ratio=attn_pool_mlp_ratio or mlp_ratio,
                norm_layer=norm_layer,
                act_layer='gelu',
                dtype=dtype,
                param_dtype=param_dtype,
                rngs=rngs,
            )
        else:
            self.attn_pool = None
        self.fc_norm = norm_layer(embed_dim, rngs=rngs) if activate_fc_norm else None
        self.head_drop = Dropout(drop_rate, rngs=rngs)
        self.head = nnx.Linear(
            embed_dim, num_classes, kernel_init=trunc_normal_(std=0.02), bias_init=zeros_,
            dtype=dtype, param_dtype=param_dtype, rngs=rngs) if num_classes > 0 else None
        self._dtype = dtype
        self._param_dtype = param_dtype

    # -- contract ------------------------------------------------------------
    def no_weight_decay(self) -> set:
        return {'pos_embed', 'cls_token', 'reg_token'}

    def group_matcher(self, coarse: bool = False):
        return dict(
            stem=r'^cls_token|pos_embed|patch_embed|reg_token',
            blocks=[(r'^blocks\.(\d+)', None), (r'^norm|^fc_norm', (99999,))],
        )

    def set_grad_checkpointing(self, enable: bool = True):
        self.grad_checkpointing = enable

    def set_block_scan(self, enable: bool = True):
        """Toggle scan-over-layers block execution (see VisionTransformer).
        Mixed-rope models thread their per-depth rope table through the scan."""
        self.block_scan = enable

    def get_classifier(self):
        return self.head

    def reset_classifier(self, num_classes: int, global_pool: Optional[str] = None, *, rngs=None):
        self.num_classes = num_classes
        if global_pool is not None:
            self.global_pool = global_pool
        rngs = rngs if rngs is not None else nnx.Rngs(0)
        self.head = nnx.Linear(
            self.embed_dim, num_classes, kernel_init=trunc_normal_(std=0.02),
            dtype=self._dtype, param_dtype=self._param_dtype, rngs=rngs) if num_classes > 0 else None

    # -- forward -------------------------------------------------------------
    def _pos_embed(self, x, grid_size: Optional[Tuple[int, int]] = None):
        """Add abs pos embed + prefix tokens; return (tokens, rope table)
        (reference eva.py:865-918)."""
        B = x.shape[0]
        if self.dynamic_img_size and grid_size is not None:
            if self.pos_embed is not None:
                pos_embed = resample_abs_pos_embed(
                    self.pos_embed[...].astype(x.dtype),
                    new_size=grid_size,
                    old_size=self.patch_embed.grid_size,
                    num_prefix_tokens=0 if self.no_embed_class else self.num_prefix_tokens,
                )
            else:
                pos_embed = None
            rope = self.rope.get_embed(shape=grid_size) if self.rope is not None else None
        else:
            pos_embed = self.pos_embed[...].astype(x.dtype) if self.pos_embed is not None else None
            rope = self.rope.get_embed() if self.rope is not None else None

        to_cat = []
        if self.cls_token is not None:
            to_cat.append(jnp.broadcast_to(self.cls_token[...].astype(x.dtype), (B, 1, x.shape[-1])))
        if self.reg_token is not None:
            to_cat.append(jnp.broadcast_to(self.reg_token[...].astype(x.dtype), (B, self.num_reg_tokens, x.shape[-1])))
        if self.no_embed_class:
            if pos_embed is not None:
                x = x + pos_embed
            if to_cat:
                x = jnp.concatenate(to_cat + [x], axis=1)
        else:
            if to_cat:
                x = jnp.concatenate(to_cat + [x], axis=1)
            if pos_embed is not None:
                x = x + pos_embed
        return self.pos_drop(x), rope

    def _forward_blocks(self, x, rope, attn_mask=None):
        if self.block_scan:
            try:
                dp = drop_path_scan_inputs(self.blocks)
                # mixed rope is a per-depth table: thread it through the scan
                # as data; a shared rope table is a closure constant
                mixed = self.rope_mixed and rope is not None
                per_layer = {'dp': dp, 'rope': rope if mixed else None}

                def call(blk, xx, extra):
                    blk_rope = extra['rope'] if mixed else rope
                    return blk(xx, rope=blk_rope, attn_mask=attn_mask,
                               drop_path_override=extra['dp'])

                return scan_block_stack(
                    self.blocks, x, call, per_layer=per_layer,
                    remat=self.grad_checkpointing)
            except BlockStackError as e:
                warn_scan_fallback(type(self).__name__, e)
        from ..parallel import shard_activation
        x = shard_activation(x, 'residual')
        remat_block = None
        if self.grad_checkpointing:
            def run_block(blk, x_, rope_, mask_):
                return blk(x_, rope=rope_, attn_mask=mask_)
            remat_block = nnx.remat(run_block)
        for i, blk in enumerate(self.blocks):
            # mixed rope: depth-dependent table (depth, num_heads, N, head_dim)
            blk_rope = rope[i] if (self.rope_mixed and rope is not None) else rope
            if remat_block is not None:
                x = remat_block(blk, x, blk_rope, attn_mask)
            else:
                x = blk(x, rope=blk_rope, attn_mask=attn_mask)
            x = shard_activation(x, 'residual')
        return x

    def forward_features(self, x, attn_mask=None):
        grid_size = self.patch_embed.dynamic_feat_size(x.shape[1:3]) if self.dynamic_img_size else None
        x = self.patch_embed(x)
        x, rope = self._pos_embed(x, grid_size=grid_size)
        if self.norm_pre is not None:
            x = self.norm_pre(x)
        x = self._forward_blocks(x, rope, attn_mask=attn_mask)
        if self.norm is not None:
            x = self.norm(x)
        return x

    def forward_head(self, x, pre_logits: bool = False):
        if self.attn_pool is not None:
            x = self.attn_pool(x)
        else:
            x = global_pool_nlc(x, pool_type=self.global_pool, num_prefix_tokens=self.num_prefix_tokens)
        if self.fc_norm is not None:
            x = self.fc_norm(x)
        x = self.head_drop(x)
        if pre_logits or self.head is None:
            return x
        return self.head(x)

    def __call__(self, x):
        return self.forward_head(self.forward_features(x))

    def forward_intermediates(
            self, x, indices=None, return_prefix_tokens: bool = False, norm: bool = False,
            stop_early: bool = False, output_fmt: str = 'NHWC', intermediates_only: bool = False,
    ):
        assert output_fmt in ('NHWC', 'NLC')
        take_indices, max_index = feature_take_indices(len(self.blocks), indices)
        B, H, W, _ = x.shape
        grid = self.patch_embed.dynamic_feat_size((H, W)) if self.dynamic_img_size \
            else self.patch_embed.grid_size
        x = self.patch_embed(x)
        x, rope = self._pos_embed(x, grid_size=grid if self.dynamic_img_size else None)
        if self.norm_pre is not None:
            x = self.norm_pre(x)
        intermediates = []
        blocks = self.blocks if not stop_early else list(self.blocks)[:max_index + 1]
        for i, blk in enumerate(blocks):
            x = blk(x, rope=rope[i] if (self.rope_mixed and rope is not None) else rope)
            if i in take_indices:
                y = self.norm(x) if (norm and self.norm is not None) else x
                prefix = y[:, :self.num_prefix_tokens] if self.num_prefix_tokens else None
                y = y[:, self.num_prefix_tokens:]
                if output_fmt == 'NHWC':
                    y = y.reshape(B, grid[0], grid[1], -1)
                intermediates.append((y, prefix) if return_prefix_tokens and prefix is not None else y)
        if intermediates_only:
            return intermediates
        if self.norm is not None:
            x = self.norm(x)
        return x, intermediates

    def prune_intermediate_layers(self, indices=1, prune_norm: bool = False, prune_head: bool = True):
        take_indices, max_index = feature_take_indices(len(self.blocks), indices)
        self.blocks = nnx.List(list(self.blocks)[:max_index + 1])
        if prune_norm:
            self.norm = None
        if prune_head:
            self.fc_norm = None
            self.reset_classifier(0)
        return take_indices


def _cfg(url: str = '', **kwargs) -> Dict[str, Any]:
    return {
        'url': url, 'num_classes': 1000, 'input_size': (3, 224, 224), 'pool_size': None,
        'crop_pct': 0.9, 'interpolation': 'bicubic', 'fixed_input_size': True,
        'mean': (0.48145466, 0.4578275, 0.40821073), 'std': (0.26862954, 0.26130258, 0.27577711),
        'first_conv': 'patch_embed.proj', 'classifier': 'head',
        **kwargs,
    }


default_cfgs = generate_default_cfgs({
    'eva02_tiny_patch14_336.mim_in22k_ft_in1k': _cfg(
        hf_hub_id='timm/', input_size=(3, 336, 336), crop_pct=1.0),
    'eva02_small_patch14_336.mim_in22k_ft_in1k': _cfg(
        hf_hub_id='timm/', input_size=(3, 336, 336), crop_pct=1.0),
    'eva02_base_patch14_448.mim_in22k_ft_in22k_in1k': _cfg(
        hf_hub_id='timm/', input_size=(3, 448, 448), crop_pct=1.0),
    'eva02_large_patch14_448.mim_m38m_ft_in22k_in1k': _cfg(
        hf_hub_id='timm/', input_size=(3, 448, 448), crop_pct=1.0),
    'eva02_enormous_patch14_clip_224.untrained': _cfg(
        input_size=(3, 224, 224), num_classes=1024),
    'eva_giant_patch14_224.clip_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), crop_pct=0.9, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'eva_giant_patch14_336.clip_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 336, 336), crop_pct=1.0, crop_mode='squash', mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'eva_giant_patch14_336.m30m_ft_in22k_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 336, 336), crop_pct=1.0, crop_mode='squash', mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'eva_giant_patch14_560.m30m_ft_in22k_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 560, 560), crop_pct=1.0, crop_mode='squash', mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'eva02_tiny_patch14_224.mim_in22k': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 224, 224), crop_pct=0.9, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'eva02_small_patch14_224.mim_in22k': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 224, 224), crop_pct=0.9, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'eva02_base_patch14_224.mim_in22k': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 224, 224), crop_pct=0.9, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'eva02_large_patch14_224.mim_in22k': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 224, 224), crop_pct=0.9, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'eva02_large_patch14_224.mim_m38m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 224, 224), crop_pct=0.9, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'eva_giant_patch14_clip_224.laion400m': _cfg(hf_hub_id='timm/', num_classes=1024, input_size=(3, 224, 224), crop_pct=0.9, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'eva_giant_patch14_clip_224.merged2b': _cfg(hf_hub_id='timm/', num_classes=1024, input_size=(3, 224, 224), crop_pct=0.9, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'eva02_base_patch16_clip_224.merged2b': _cfg(hf_hub_id='timm/', num_classes=512, input_size=(3, 224, 224), crop_pct=0.9, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'eva02_large_patch14_clip_224.merged2b': _cfg(hf_hub_id='timm/', num_classes=768, input_size=(3, 224, 224), crop_pct=0.9, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'eva02_large_patch14_clip_336.merged2b': _cfg(hf_hub_id='timm/', num_classes=768, input_size=(3, 336, 336), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_medium_patch16_rope_reg1_gap_256.sbb_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 256, 256), crop_pct=0.95, mean=(0.5, 0.5, 0.5), std=(0.5, 0.5, 0.5), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_mediumd_patch16_rope_reg1_gap_256.sbb_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 256, 256), crop_pct=0.95, mean=(0.5, 0.5, 0.5), std=(0.5, 0.5, 0.5), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_betwixt_patch16_rope_reg4_gap_256.sbb_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 256, 256), crop_pct=0.95, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_base_patch16_rope_reg1_gap_256.sbb_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 256, 256), crop_pct=0.95, mean=(0.5, 0.5, 0.5), std=(0.5, 0.5, 0.5), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_pe_core_tiny_patch16_384.fb': _cfg(hf_hub_id='timm/', num_classes=512, input_size=(3, 384, 384), crop_pct=1.0, mean=(0.5, 0.5, 0.5), std=(0.5, 0.5, 0.5), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_pe_core_small_patch16_384.fb': _cfg(hf_hub_id='timm/', num_classes=512, input_size=(3, 384, 384), crop_pct=1.0, mean=(0.5, 0.5, 0.5), std=(0.5, 0.5, 0.5), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_pe_core_base_patch16_224.fb': _cfg(hf_hub_id='timm/', num_classes=1024, input_size=(3, 224, 224), crop_pct=1.0, mean=(0.5, 0.5, 0.5), std=(0.5, 0.5, 0.5), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_pe_core_large_patch14_336.fb': _cfg(hf_hub_id='timm/', num_classes=1024, input_size=(3, 336, 336), crop_pct=1.0, mean=(0.5, 0.5, 0.5), std=(0.5, 0.5, 0.5), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_pe_core_gigantic_patch14_448.fb': _cfg(hf_hub_id='timm/', num_classes=1280, input_size=(3, 448, 448), crop_pct=1.0, mean=(0.5, 0.5, 0.5), std=(0.5, 0.5, 0.5), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_pe_lang_large_patch14_448.fb': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 448, 448), crop_pct=1.0, mean=(0.5, 0.5, 0.5), std=(0.5, 0.5, 0.5), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_pe_lang_large_patch14_448.fb_tiling': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 448, 448), crop_pct=1.0, mean=(0.5, 0.5, 0.5), std=(0.5, 0.5, 0.5), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_pe_lang_gigantic_patch14_448.fb': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 448, 448), crop_pct=1.0, mean=(0.5, 0.5, 0.5), std=(0.5, 0.5, 0.5), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_pe_lang_gigantic_patch14_448.fb_tiling': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 448, 448), crop_pct=1.0, mean=(0.5, 0.5, 0.5), std=(0.5, 0.5, 0.5), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_pe_spatial_tiny_patch16_512.fb': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 512, 512), crop_pct=1.0, mean=(0.5, 0.5, 0.5), std=(0.5, 0.5, 0.5), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_pe_spatial_small_patch16_512.fb': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 512, 512), crop_pct=1.0, mean=(0.5, 0.5, 0.5), std=(0.5, 0.5, 0.5), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_pe_spatial_base_patch16_512.fb': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 512, 512), crop_pct=1.0, mean=(0.5, 0.5, 0.5), std=(0.5, 0.5, 0.5), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_pe_spatial_large_patch14_448.fb': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 448, 448), crop_pct=1.0, mean=(0.5, 0.5, 0.5), std=(0.5, 0.5, 0.5), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_pe_spatial_gigantic_patch14_448.fb': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 448, 448), crop_pct=1.0, mean=(0.5, 0.5, 0.5), std=(0.5, 0.5, 0.5), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_small_patch16_rope_224.naver_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), crop_pct=0.9, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_base_patch16_rope_224.naver_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), crop_pct=0.9, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_large_patch16_rope_224.naver_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), crop_pct=0.9, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_small_patch16_rope_mixed_224.naver_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), crop_pct=0.9, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_base_patch16_rope_mixed_224.naver_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), crop_pct=0.9, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_large_patch16_rope_mixed_224.naver_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), crop_pct=0.9, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_small_patch16_rope_ape_224.naver_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), crop_pct=0.9, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_base_patch16_rope_ape_224.naver_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), crop_pct=0.9, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_large_patch16_rope_ape_224.naver_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), crop_pct=0.9, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_small_patch16_rope_mixed_ape_224.naver_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), crop_pct=0.9, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_base_patch16_rope_mixed_ape_224.naver_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), crop_pct=0.9, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_large_patch16_rope_mixed_ape_224.naver_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), crop_pct=0.9, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_tiny_patch16_dinov3_qkvb.eupe_lvd1689m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_small_patch16_dinov3.lvd1689m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_small_patch16_dinov3_qkvb.lvd1689m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_small_patch16_dinov3_qkvb.eupe_lvd1689m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_small_plus_patch16_dinov3.lvd1689m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_small_plus_patch16_dinov3_qkvb.lvd1689m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_base_patch16_dinov3.lvd1689m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_base_patch16_dinov3_qkvb.lvd1689m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_base_patch16_dinov3_qkvb.eupe_lvd1689m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_large_patch16_dinov3.lvd1689m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_large_patch16_dinov3.sat493m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256), crop_pct=1.0, mean=(0.43, 0.411, 0.296), std=(0.213, 0.156, 0.143), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_large_patch16_dinov3_qkvb.lvd1689m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_large_patch16_dinov3_qkvb.sat493m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256), crop_pct=1.0, mean=(0.43, 0.411, 0.296), std=(0.213, 0.156, 0.143), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_huge_plus_patch16_dinov3.lvd1689m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_huge_plus_patch16_dinov3_qkvb.lvd1689m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_7b_patch16_dinov3.lvd1689m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'vit_7b_patch16_dinov3.sat493m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256), crop_pct=1.0, mean=(0.43, 0.411, 0.296), std=(0.213, 0.156, 0.143), fixed_input_size=True, first_conv='patch_embed.proj', classifier='head'),
    'test_eva.untrained': _cfg(input_size=(3, 160, 160)),
})


def checkpoint_filter_fn(state_dict: Dict, model) -> Dict:
    """Map reference-timm EVA layouts: raw gamma_1/gamma_2 layer-scale params
    → ls1/ls2 modules (reference eva.py:344,380 naming)."""
    from ._torch_convert import convert_torch_state_dict
    out = {}
    for k, v in state_dict.items():
        k = k.replace('gamma_1', 'ls1.gamma').replace('gamma_2', 'ls2.gamma')
        out[k] = v
    return convert_torch_state_dict(out, model)


def _create_eva(variant: str, pretrained: bool = False, **kwargs) -> Eva:
    out_indices = kwargs.pop('out_indices', 3)
    return build_model_with_cfg(
        Eva, variant, pretrained,
        pretrained_filter_fn=checkpoint_filter_fn,
        feature_cfg=dict(out_indices=out_indices),
        **kwargs,
    )


@register_model
def eva02_tiny_patch14_336(pretrained=False, **kwargs) -> Eva:
    model_args = dict(
        img_size=336, patch_size=14, embed_dim=192, depth=12, num_heads=3,
        mlp_ratio=4 * 2 / 3, swiglu_mlp=True, use_rot_pos_emb=True, ref_feat_shape=(16, 16))
    return _create_eva('eva02_tiny_patch14_336', pretrained, **dict(model_args, **kwargs))


@register_model
def eva02_small_patch14_336(pretrained=False, **kwargs) -> Eva:
    model_args = dict(
        img_size=336, patch_size=14, embed_dim=384, depth=12, num_heads=6,
        mlp_ratio=4 * 2 / 3, swiglu_mlp=True, use_rot_pos_emb=True, ref_feat_shape=(16, 16))
    return _create_eva('eva02_small_patch14_336', pretrained, **dict(model_args, **kwargs))


@register_model
def eva02_base_patch14_448(pretrained=False, **kwargs) -> Eva:
    model_args = dict(
        img_size=448, patch_size=14, embed_dim=768, depth=12, num_heads=12,
        mlp_ratio=4 * 2 / 3, swiglu_mlp=True, scale_mlp=True, use_rot_pos_emb=True,
        qkv_fused=False, ref_feat_shape=(16, 16))
    return _create_eva('eva02_base_patch14_448', pretrained, **dict(model_args, **kwargs))


@register_model
def eva02_large_patch14_448(pretrained=False, **kwargs) -> Eva:
    model_args = dict(
        img_size=448, patch_size=14, embed_dim=1024, depth=24, num_heads=16,
        mlp_ratio=4 * 2 / 3, swiglu_mlp=True, scale_mlp=True, use_rot_pos_emb=True,
        qkv_fused=False, ref_feat_shape=(16, 16))
    return _create_eva('eva02_large_patch14_448', pretrained, **dict(model_args, **kwargs))


@register_model
def eva02_enormous_patch14_clip_224(pretrained=False, **kwargs) -> Eva:
    """EVA-CLIP variant with residual post-norm blocks (reference eva.py:2068;
    post-norm numerics parity-verified at small scale: 1.2e-10)."""
    model_args = dict(
        img_size=224, patch_size=14, embed_dim=1792, depth=64, num_heads=16,
        mlp_ratio=15360 / 1792, use_post_norm=True)
    return _create_eva('eva02_enormous_patch14_clip_224', pretrained, **dict(model_args, **kwargs))


@register_model
def test_eva(pretrained=False, **kwargs) -> Eva:
    model_args = dict(
        img_size=160, patch_size=16, embed_dim=64, depth=2, num_heads=2,
        mlp_ratio=8 / 3, swiglu_mlp=True, scale_mlp=True, use_rot_pos_emb=True, init_values=1e-5)
    return _create_eva('test_eva', pretrained, **dict(model_args, **kwargs))


@register_model
def eva_giant_patch14_224(pretrained: bool = False, **kwargs) -> Eva:
    """EVA-g model https://arxiv.org/abs/2211.07636"""
    model_args = dict(patch_size=14, embed_dim=1408, depth=40, num_heads=16, mlp_ratio=6144 / 1408)
    return _create_eva('eva_giant_patch14_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def eva_giant_patch14_336(pretrained: bool = False, **kwargs) -> Eva:
    """EVA-g model https://arxiv.org/abs/2211.07636"""
    model_args = dict(patch_size=14, embed_dim=1408, depth=40, num_heads=16, mlp_ratio=6144 / 1408)
    return _create_eva('eva_giant_patch14_336', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def eva_giant_patch14_560(pretrained: bool = False, **kwargs) -> Eva:
    """EVA-g model https://arxiv.org/abs/2211.07636"""
    model_args = dict(patch_size=14, embed_dim=1408, depth=40, num_heads=16, mlp_ratio=6144 / 1408)
    return _create_eva('eva_giant_patch14_560', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def eva02_tiny_patch14_224(pretrained: bool = False, **kwargs) -> Eva:
    """EVA02 Tiny https://arxiv.org/abs/2303.11331"""
    model_args = dict(
        img_size=224,
        patch_size=14,
        embed_dim=192,
        depth=12,
        num_heads=3,
        mlp_ratio=4 * 2 / 3,
        swiglu_mlp=True,
        use_rot_pos_emb=True,
        ref_feat_shape=(16, 16),  # 224/14
    )
    return _create_eva('eva02_tiny_patch14_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def eva02_small_patch14_224(pretrained: bool = False, **kwargs) -> Eva:
    """EVA02 Small https://arxiv.org/abs/2303.11331"""
    model_args = dict(
        img_size=224,
        patch_size=14,
        embed_dim=384,
        depth=12,
        num_heads=6,
        mlp_ratio=4 * 2 / 3,
        swiglu_mlp=True,
        use_rot_pos_emb=True,
        ref_feat_shape=(16, 16),  # 224/14
    )
    return _create_eva('eva02_small_patch14_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def eva02_base_patch14_224(pretrained: bool = False, **kwargs) -> Eva:
    """EVA02 Base https://arxiv.org/abs/2303.11331"""
    model_args = dict(
        img_size=224,
        patch_size=14,
        embed_dim=768,
        depth=12,
        num_heads=12,
        qkv_fused=False,
        mlp_ratio=4 * 2 / 3,
        swiglu_mlp=True,
        scale_mlp=True,
        use_rot_pos_emb=True,
        ref_feat_shape=(16, 16),  # 224/14
    )
    return _create_eva('eva02_base_patch14_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def eva02_large_patch14_224(pretrained: bool = False, **kwargs) -> Eva:
    """EVA02 Large https://arxiv.org/abs/2303.11331"""
    model_args = dict(
        img_size=224,
        patch_size=14,
        embed_dim=1024,
        depth=24,
        num_heads=16,
        mlp_ratio=4 * 2 / 3,
        qkv_fused=False,
        swiglu_mlp=True,
        scale_mlp=True,
        use_rot_pos_emb=True,
        ref_feat_shape=(16, 16),  # 224/14
    )
    return _create_eva('eva02_large_patch14_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def eva_giant_patch14_clip_224(pretrained: bool = False, **kwargs) -> Eva:
    """EVA-g CLIP model (only difference from non-CLIP is the pooling)"""
    model_args = dict(
        patch_size=14, embed_dim=1408, depth=40, num_heads=16, mlp_ratio=6144 / 1408,
        global_pool=kwargs.pop('global_pool', 'token'))
    return _create_eva('eva_giant_patch14_clip_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def eva02_base_patch16_clip_224(pretrained: bool = False, **kwargs) -> Eva:
    """An EVA-CLIP specific variant that adds additional attn scale layer-norm to eva02_base"""
    model_args = dict(
        img_size=224,
        patch_size=16,
        embed_dim=768,
        depth=12,
        num_heads=12,
        qkv_fused=False,
        mlp_ratio=4 * 2 / 3,
        swiglu_mlp=True,
        scale_mlp=True,
        scale_attn_inner=True,
        use_rot_pos_emb=True,
        ref_feat_shape=(16, 16),  # 224/14
        global_pool=kwargs.pop('global_pool', 'token'),
    )
    return _create_eva('eva02_base_patch16_clip_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def eva02_large_patch14_clip_224(pretrained: bool = False, **kwargs) -> Eva:
    """An EVA-CLIP specific variant that adds additional attn scale layer-norm to eva02_large"""
    model_args = dict(
        img_size=224,
        patch_size=14,
        embed_dim=1024,
        depth=24,
        num_heads=16,
        mlp_ratio=4 * 2 / 3,
        qkv_fused=False,
        swiglu_mlp=True,
        scale_mlp=True,
        scale_attn_inner=True,
        use_rot_pos_emb=True,
        ref_feat_shape=(16, 16),  # 224/14
        global_pool=kwargs.pop('global_pool', 'token'),
    )
    return _create_eva('eva02_large_patch14_clip_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def eva02_large_patch14_clip_336(pretrained: bool = False, **kwargs) -> Eva:
    """An EVA-CLIP specific variant that adds additional attn scale layer-norm to eva02_large"""
    model_args = dict(
        img_size=336,
        patch_size=14,
        embed_dim=1024,
        depth=24,
        num_heads=16,
        mlp_ratio=4 * 2 / 3,
        qkv_fused=False,
        swiglu_mlp=True,
        scale_mlp=True,
        scale_attn_inner=True,
        use_rot_pos_emb=True,
        ref_feat_shape=(16, 16),  # 224/14
        global_pool=kwargs.pop('global_pool', 'token'),
    )
    return _create_eva('eva02_large_patch14_clip_336', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_medium_patch16_rope_reg1_gap_256(pretrained: bool = False, **kwargs) -> Eva:
    """timm SBB ViT with ROPE"""
    model_args = dict(
        img_size=256,
        patch_size=16,
        embed_dim=512,
        depth=12,
        num_heads=8,
        qkv_fused=True,
        qkv_bias=True,
        init_values=1e-5,
        class_token=False,
        num_reg_tokens=1,
        use_rot_pos_emb=True,
        use_abs_pos_emb=False,
        ref_feat_shape=(16, 16),  # 224/14
    )
    return _create_eva('vit_medium_patch16_rope_reg1_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_mediumd_patch16_rope_reg1_gap_256(pretrained: bool = False, **kwargs) -> Eva:
    """timm SBB ViT with ROPE"""
    model_args = dict(
        img_size=256,
        patch_size=16,
        embed_dim=512,
        depth=20,
        num_heads=8,
        qkv_fused=True,
        qkv_bias=False,
        init_values=1e-5,
        class_token=False,
        num_reg_tokens=1,
        use_rot_pos_emb=True,
        use_abs_pos_emb=False,
        ref_feat_shape=(16, 16),  # 224/14
    )
    return _create_eva('vit_mediumd_patch16_rope_reg1_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_betwixt_patch16_rope_reg4_gap_256(pretrained: bool = False, **kwargs) -> Eva:
    """timm SBB ViT with ROPE"""
    model_args = dict(
        img_size=256,
        patch_size=16,
        embed_dim=640,
        depth=12,
        num_heads=10,
        qkv_fused=True,
        qkv_bias=True,
        init_values=1e-5,
        class_token=False,
        num_reg_tokens=4,
        use_rot_pos_emb=True,
        use_abs_pos_emb=False,
        ref_feat_shape=(16, 16),  # 224/14
    )
    return _create_eva('vit_betwixt_patch16_rope_reg4_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_rope_reg1_gap_256(pretrained: bool = False, **kwargs) -> Eva:
    """timm SBB ViT with ROPE"""
    model_args = dict(
        img_size=256,
        patch_size=16,
        embed_dim=768,
        depth=12,
        num_heads=12,
        qkv_fused=True,
        qkv_bias=True,
        init_values=1e-5,
        class_token=False,
        num_reg_tokens=1,
        use_rot_pos_emb=True,
        use_abs_pos_emb=False,
        ref_feat_shape=(16, 16),  # 224/14
    )
    return _create_eva('vit_base_patch16_rope_reg1_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_pe_core_tiny_patch16_384(pretrained: bool = False, **kwargs) -> Eva:
    """Perception Encoder (PE) ViT from Meta (https://arxiv.org/abs/2504.13181)"""
    model_args = dict(
        patch_size=16,
        embed_dim=192,
        depth=12,
        num_heads=3,
        mlp_ratio=4.0,
        global_pool='map',
        attn_type='rope',
        use_pre_transformer_norm=True,
        use_rot_pos_emb=True,
        ref_feat_shape=(24, 24),
        rope_grid_offset=1.,
        rope_grid_indexing='xy',
        attn_pool_num_heads=8,
        attn_pool_mlp_ratio=4.,
        norm_layer=partial(LayerNorm, eps=1e-5),
        #dynamic_img_size=True
    )
    return _create_eva('vit_pe_core_tiny_patch16_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_pe_core_small_patch16_384(pretrained: bool = False, **kwargs) -> Eva:
    """Perception Encoder (PE) ViT from Meta (https://arxiv.org/abs/2504.13181)"""
    model_args = dict(
        patch_size=16,
        embed_dim=384,
        depth=12,
        num_heads=6,
        mlp_ratio=4.0,
        global_pool='map',
        attn_type='rope',
        use_pre_transformer_norm=True,
        use_rot_pos_emb=True,
        ref_feat_shape=(24, 24),
        rope_grid_offset=1.,
        rope_grid_indexing='xy',
        attn_pool_num_heads=8,
        attn_pool_mlp_ratio=4.,
        norm_layer=partial(LayerNorm, eps=1e-5),
        #dynamic_img_size=True
    )
    return _create_eva('vit_pe_core_small_patch16_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_pe_core_base_patch16_224(pretrained: bool = False, **kwargs) -> Eva:
    """Perception Encoder (PE) ViT from Meta (https://arxiv.org/abs/2504.13181)"""
    model_args = dict(
        patch_size=16,
        embed_dim=768,
        depth=12,
        num_heads=12,
        mlp_ratio=4.0,
        global_pool='map',
        attn_type='rope',
        use_pre_transformer_norm=True,
        use_rot_pos_emb=True,
        ref_feat_shape=(14, 14),
        rope_grid_offset=1.,
        rope_grid_indexing='xy',
        attn_pool_num_heads=8,
        attn_pool_mlp_ratio=4.,
        norm_layer=partial(LayerNorm, eps=1e-5),
        #dynamic_img_size=True
    )
    return _create_eva('vit_pe_core_base_patch16_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_pe_core_large_patch14_336(pretrained: bool = False, **kwargs) -> Eva:
    """Perception Encoder (PE) ViT from Meta (https://arxiv.org/abs/2504.13181)"""
    model_args = dict(
        patch_size=14,
        embed_dim=1024,
        depth=24,
        num_heads=16,
        mlp_ratio=4.0,
        global_pool='map',
        attn_type='rope',
        use_pre_transformer_norm=True,
        use_rot_pos_emb=True,
        ref_feat_shape=(24, 24),
        rope_grid_offset=1.,
        rope_grid_indexing='xy',
        attn_pool_num_heads=8,
        attn_pool_mlp_ratio=4.,
        norm_layer=partial(LayerNorm, eps=1e-5),
        #dynamic_img_size=True,
    )
    return _create_eva('vit_pe_core_large_patch14_336', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_pe_core_gigantic_patch14_448(pretrained: bool = False, **kwargs) -> Eva:
    """Perception Encoder (PE) ViT from Meta (https://arxiv.org/abs/2504.13181)"""
    model_args = dict(
        patch_size=14,
        embed_dim=1536,
        depth=50,
        num_heads=16,
        mlp_ratio=8960 / 1536,
        global_pool='map',
        attn_type='rope',
        class_token=False,
        use_pre_transformer_norm=True,
        use_rot_pos_emb=True,
        ref_feat_shape=(32, 32),
        rope_grid_indexing='xy',
        attn_pool_num_heads=8,
        attn_pool_mlp_ratio=4.,
        norm_layer=partial(LayerNorm, eps=1e-5),
        #dynamic_img_size=True,
    )
    return _create_eva('vit_pe_core_gigantic_patch14_448', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_pe_lang_large_patch14_448(pretrained: bool = False, **kwargs) -> Eva:
    """Perception Encoder (PE) ViT from Meta (https://arxiv.org/abs/2504.13181)"""
    model_args = dict(
        patch_size=14,
        embed_dim=1024,
        depth=23,
        num_heads=16,
        mlp_ratio=4.0,
        attn_type='rope',
        class_token=True,
        use_rot_pos_emb=True,
        ref_feat_shape=(32, 32),
        rope_grid_offset=1.,
        rope_grid_indexing='xy',
        use_pre_transformer_norm=True,
        use_post_transformer_norm=False,
        use_fc_norm=False,  # explicitly disable
        init_values=0.1,
        norm_layer=partial(LayerNorm, eps=1e-5),
        #dynamic_img_size=True,
    )
    return _create_eva('vit_pe_lang_large_patch14_448', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_pe_lang_gigantic_patch14_448(pretrained: bool = False, **kwargs) -> Eva:
    """Perception Encoder (PE) ViT from Meta (https://arxiv.org/abs/2504.13181)"""
    model_args = dict(
        patch_size=14,
        embed_dim=1536,
        depth=47,
        num_heads=16,
        mlp_ratio=8960 / 1536,
        attn_type='rope',
        class_token=False,
        use_rot_pos_emb=True,
        ref_feat_shape=(32, 32),
        rope_grid_indexing='xy',
        use_pre_transformer_norm=True,
        use_post_transformer_norm=False,
        use_fc_norm=False,  # explicitly disable
        init_values=0.1,
        norm_layer=partial(LayerNorm, eps=1e-5),
        #dynamic_img_size=True,
    )
    return _create_eva('vit_pe_lang_gigantic_patch14_448', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_pe_spatial_tiny_patch16_512(pretrained: bool = False, **kwargs) -> Eva:
    """Perception Encoder (PE) ViT from Meta (https://arxiv.org/abs/2504.13181)"""
    model_args = dict(
        patch_size=16,
        embed_dim=192,
        depth=12,
        num_heads=3,
        mlp_ratio=4.0,
        attn_type='rope',
        use_pre_transformer_norm=True,
        use_post_transformer_norm=False,
        use_fc_norm=False,  # explicitly disable
        use_rot_pos_emb=True,
        ref_feat_shape=(32, 32),
        rope_grid_offset=1.,
        rope_grid_indexing='xy',
        norm_layer=partial(LayerNorm, eps=1e-5),
        #dynamic_img_size=True
    )
    return _create_eva('vit_pe_spatial_tiny_patch16_512', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_pe_spatial_small_patch16_512(pretrained: bool = False, **kwargs) -> Eva:
    """Perception Encoder (PE) ViT from Meta (https://arxiv.org/abs/2504.13181)"""
    model_args = dict(
        patch_size=16,
        embed_dim=384,
        depth=12,
        num_heads=6,
        mlp_ratio=4.0,
        attn_type='rope',
        use_pre_transformer_norm=True,
        use_post_transformer_norm=False,
        use_fc_norm=False,  # explicitly disable
        use_rot_pos_emb=True,
        ref_feat_shape=(32, 32),
        rope_grid_offset=1.,
        rope_grid_indexing='xy',
        norm_layer=partial(LayerNorm, eps=1e-5),
        #dynamic_img_size=True
    )
    return _create_eva('vit_pe_spatial_small_patch16_512', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_pe_spatial_base_patch16_512(pretrained: bool = False, **kwargs) -> Eva:
    """Perception Encoder (PE) ViT from Meta (https://arxiv.org/abs/2504.13181)"""
    model_args = dict(
        patch_size=16,
        embed_dim=768,
        depth=12,
        num_heads=12,
        mlp_ratio=4.0,
        attn_type='rope',
        use_pre_transformer_norm=True,
        use_post_transformer_norm=False,
        use_fc_norm=False,  # explicitly disable
        use_rot_pos_emb=True,
        ref_feat_shape=(32, 32),
        rope_grid_offset=1.,
        rope_grid_indexing='xy',
        norm_layer=partial(LayerNorm, eps=1e-5),
        #dynamic_img_size=True
    )
    return _create_eva('vit_pe_spatial_base_patch16_512', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_pe_spatial_large_patch14_448(pretrained: bool = False, **kwargs) -> Eva:
    """Perception Encoder (PE) ViT from Meta (https://arxiv.org/abs/2504.13181)"""
    model_args = dict(
        patch_size=14,
        embed_dim=1024,
        depth=24,
        num_heads=16,
        mlp_ratio=4.0,
        attn_type='rope',
        use_pre_transformer_norm=True,
        use_post_transformer_norm=False,
        use_fc_norm=False,  # explicitly disable
        use_rot_pos_emb=True,
        ref_feat_shape=(32, 32),
        rope_grid_offset=1.,
        rope_grid_indexing='xy',
        norm_layer=partial(LayerNorm, eps=1e-5),
        #dynamic_img_size=True,
    )
    return _create_eva('vit_pe_spatial_large_patch14_448', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_pe_spatial_gigantic_patch14_448(pretrained: bool = False, **kwargs) -> Eva:
    """Perception Encoder (PE) ViT from Meta (https://arxiv.org/abs/2504.13181)"""
    model_args = dict(
        patch_size=14,
        embed_dim=1536,
        depth=50,
        num_heads=16,
        mlp_ratio=8960 / 1536,
        attn_type='rope',
        class_token=False,
        use_rot_pos_emb=True,
        ref_feat_shape=(32, 32),
        rope_grid_indexing='xy',
        use_pre_transformer_norm=True,
        use_post_transformer_norm=False,
        use_fc_norm=False,  # explicitly disable
        init_values=0.1,
        norm_layer=partial(LayerNorm, eps=1e-5),
        #dynamic_img_size=True,
    )
    return _create_eva('vit_pe_spatial_gigantic_patch14_448', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_small_patch16_rope_224(pretrained: bool = False, **kwargs) -> Eva:
    """RoPE-Axial ViT-S/16 from https://github.com/naver-ai/rope-vit"""
    model_args = dict(
        patch_size=16,
        embed_dim=384,
        depth=12,
        num_heads=6,
        mlp_ratio=4,
        attn_type='rope',
        qkv_bias=True,
        init_values=1e-5,
        class_token=True,
        global_pool='token',
        use_abs_pos_emb=False,
        use_rot_pos_emb=True,
        rope_grid_indexing='xy',
        rope_temperature=100.0,
    )
    return _create_eva('vit_small_patch16_rope_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_rope_224(pretrained: bool = False, **kwargs) -> Eva:
    """RoPE-Axial ViT-B/16 from https://github.com/naver-ai/rope-vit"""
    model_args = dict(
        patch_size=16,
        embed_dim=768,
        depth=12,
        num_heads=12,
        mlp_ratio=4,
        attn_type='rope',
        use_fc_norm=False,
        qkv_bias=True,
        init_values=1e-5,
        class_token=True,
        global_pool='token',
        use_abs_pos_emb=False,
        use_rot_pos_emb=True,
        rope_grid_indexing='xy',
        rope_temperature=100.0,
    )
    return _create_eva('vit_base_patch16_rope_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch16_rope_224(pretrained: bool = False, **kwargs) -> Eva:
    """RoPE-Axial ViT-L/16 from https://github.com/naver-ai/rope-vit"""
    model_args = dict(
        patch_size=16,
        embed_dim=1024,
        depth=24,
        num_heads=16,
        mlp_ratio=4,
        attn_type='rope',
        qkv_bias=True,
        init_values=1e-5,
        class_token=True,
        global_pool='token',
        use_abs_pos_emb=False,
        use_rot_pos_emb=True,
        rope_grid_indexing='xy',
        rope_temperature=100.0,
    )
    return _create_eva('vit_large_patch16_rope_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_small_patch16_rope_mixed_224(pretrained: bool = False, **kwargs) -> Eva:
    """RoPE-Mixed ViT-S/16 from https://github.com/naver-ai/rope-vit"""
    model_args = dict(
        patch_size=16,
        embed_dim=384,
        depth=12,
        num_heads=6,
        mlp_ratio=4,
        attn_type='rope',
        qkv_bias=True,
        init_values=1e-5,
        class_token=True,
        global_pool='token',
        use_abs_pos_emb=False,
        use_rot_pos_emb=True,
        rope_grid_indexing='xy',
        rope_temperature=10.0,
        rope_type='mixed'
    )
    return _create_eva('vit_small_patch16_rope_mixed_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_rope_mixed_224(pretrained: bool = False, **kwargs) -> Eva:
    """RoPE-Mixed ViT-B/16 from https://github.com/naver-ai/rope-vit"""
    model_args = dict(
        patch_size=16,
        embed_dim=768,
        depth=12,
        num_heads=12,
        mlp_ratio=4,
        qkv_bias=True,
        attn_type='rope',
        init_values=1e-5,
        class_token=True,
        global_pool='token',
        use_abs_pos_emb=False,
        use_rot_pos_emb=True,
        rope_grid_indexing='xy',
        rope_temperature=10.0,
        rope_type='mixed'
    )
    return _create_eva('vit_base_patch16_rope_mixed_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch16_rope_mixed_224(pretrained: bool = False, **kwargs) -> Eva:
    """RoPE-Mixed ViT-L/16 from https://github.com/naver-ai/rope-vit"""
    model_args = dict(
        patch_size=16,
        embed_dim=1024,
        depth=24,
        num_heads=16,
        mlp_ratio=4,
        attn_type='rope',
        qkv_bias=True,
        init_values=1e-5,
        class_token=True,
        global_pool='token',
        use_abs_pos_emb=False,
        use_rot_pos_emb=True,
        rope_grid_indexing='xy',
        rope_temperature=10.0,
        rope_type='mixed'
    )
    return _create_eva('vit_large_patch16_rope_mixed_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_small_patch16_rope_ape_224(pretrained: bool = False, **kwargs) -> Eva:
    """RoPE-Axial + APE ViT-S/16 from https://github.com/naver-ai/rope-vit"""
    model_args = dict(
        patch_size=16,
        embed_dim=384,
        depth=12,
        num_heads=6,
        mlp_ratio=4,
        attn_type='rope',
        qkv_bias=True,
        init_values=1e-5,
        class_token=True,
        global_pool='token',
        no_embed_class=True,
        use_abs_pos_emb=True,
        use_rot_pos_emb=True,
        rope_grid_indexing='xy',
        rope_temperature=100.0,
    )
    return _create_eva('vit_small_patch16_rope_ape_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_rope_ape_224(pretrained: bool = False, **kwargs) -> Eva:
    """RoPE-Axial + APE ViT-B/16 from https://github.com/naver-ai/rope-vit"""
    model_args = dict(
        patch_size=16,
        embed_dim=768,
        depth=12,
        num_heads=12,
        mlp_ratio=4,
        attn_type='rope',
        qkv_bias=True,
        init_values=1e-5,
        class_token=True,
        global_pool='token',
        no_embed_class=True,
        use_abs_pos_emb=True,
        use_rot_pos_emb=True,
        rope_grid_indexing='xy',
        rope_temperature=100.0,
    )
    return _create_eva('vit_base_patch16_rope_ape_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch16_rope_ape_224(pretrained: bool = False, **kwargs) -> Eva:
    """RoPE-Axial + APE ViT-L/16 from https://github.com/naver-ai/rope-vit"""
    model_args = dict(
        patch_size=16,
        embed_dim=1024,
        depth=24,
        num_heads=16,
        mlp_ratio=4,
        attn_type='rope',
        qkv_bias=True,
        init_values=1e-5,
        class_token=True,
        global_pool='token',
        no_embed_class=True,
        use_abs_pos_emb=True,
        use_rot_pos_emb=True,
        rope_grid_indexing='xy',
        rope_temperature=100.0,
    )
    return _create_eva('vit_large_patch16_rope_ape_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_small_patch16_rope_mixed_ape_224(pretrained: bool = False, **kwargs) -> Eva:
    """RoPE-Mixed + APE ViT-S/16 from https://github.com/naver-ai/rope-vit"""
    model_args = dict(
        patch_size=16,
        embed_dim=384,
        depth=12,
        num_heads=6,
        mlp_ratio=4,
        attn_type='rope',
        qkv_bias=True,
        init_values=1e-5,
        class_token=True,
        global_pool='token',
        no_embed_class=True,
        use_abs_pos_emb=True,
        use_rot_pos_emb=True,
        rope_grid_indexing='xy',
        rope_temperature=10.0,
        rope_type='mixed'
    )
    return _create_eva('vit_small_patch16_rope_mixed_ape_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_rope_mixed_ape_224(pretrained: bool = False, **kwargs) -> Eva:
    """RoPE-Mixed + APE ViT-B/16 from https://github.com/naver-ai/rope-vit"""
    model_args = dict(
        patch_size=16,
        embed_dim=768,
        depth=12,
        num_heads=12,
        mlp_ratio=4,
        attn_type='rope',
        qkv_bias=True,
        init_values=1e-5,
        class_token=True,
        global_pool='token',
        no_embed_class=True,
        use_abs_pos_emb=True,
        use_rot_pos_emb=True,
        rope_grid_indexing='xy',
        rope_temperature=10.0,
        rope_type='mixed'
    )
    return _create_eva('vit_base_patch16_rope_mixed_ape_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch16_rope_mixed_ape_224(pretrained: bool = False, **kwargs) -> Eva:
    """RoPE-Mixed + APE ViT-L/16 from https://github.com/naver-ai/rope-vit"""
    model_args = dict(
        patch_size=16,
        embed_dim=1024,
        depth=24,
        num_heads=16,
        mlp_ratio=4,
        attn_type='rope',
        qkv_bias=True,
        init_values=1e-5,
        class_token=True,
        global_pool='token',
        no_embed_class=True,
        use_abs_pos_emb=True,
        use_rot_pos_emb=True,
        rope_grid_indexing='xy',
        rope_temperature=10.0,
        rope_type='mixed'
    )
    return _create_eva('vit_large_patch16_rope_mixed_ape_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_tiny_patch16_dinov3_qkvb(pretrained: bool = False, **kwargs) -> Eva:
    """DINOv3-style T/16 w/ QKV bias enabled."""
    model_args = dict(
        patch_size=16,
        dynamic_img_size=True,
        embed_dim=192,
        depth=12,
        num_heads=3,
        qkv_bias=True,
        # global_pool='token',  # upstream uses CLS token; default here is 'avg', pass via kwargs or --gp
        init_values=1.0e-05, # layer-scale
        rope_type='dinov3',
        rope_temperature=100,
        #rope_rescale_coords=2,  # haven't added to interface
        rope_rotate_half=True,
        use_rot_pos_emb=True,
        use_abs_pos_emb=False,
        num_reg_tokens=4,
        use_fc_norm=False,
        norm_layer=partial(LayerNorm, eps=1e-5),
    )
    return _create_eva('vit_tiny_patch16_dinov3_qkvb', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_small_patch16_dinov3(pretrained: bool = False, **kwargs) -> Eva:
    """DINOv3 S/16 https://arxiv.org/abs/2508.10104"""
    model_args = dict(
        patch_size=16,
        dynamic_img_size=True,
        embed_dim=384,
        depth=12,
        num_heads=6,
        qkv_bias=False,
        # global_pool='token',  # upstream uses CLS token; default here is 'avg', pass via kwargs or --gp
        init_values=1.0e-05, # layer-scale
        rope_type='dinov3',
        rope_temperature=100,
        #rope_rescale_coords=2,  # haven't added to interface
        rope_rotate_half=True,
        use_rot_pos_emb=True,
        use_abs_pos_emb=False,
        num_reg_tokens=4,
        use_fc_norm=False,
        norm_layer=partial(LayerNorm, eps=1e-5),
    )
    return _create_eva('vit_small_patch16_dinov3', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_small_patch16_dinov3_qkvb(pretrained: bool = False, **kwargs) -> Eva:
    """DINOv3 S/16 w/ QKV bias enabled (but zero) https://arxiv.org/abs/2508.10104"""
    model_args = dict(
        patch_size=16,
        dynamic_img_size=True,
        embed_dim=384,
        depth=12,
        num_heads=6,
        qkv_bias=True,
        # global_pool='token',  # upstream uses CLS token; default here is 'avg', pass via kwargs or --gp
        init_values=1.0e-05, # layer-scale
        rope_type='dinov3',
        rope_temperature=100,
        #rope_rescale_coords=2,  # haven't added to interface
        rope_rotate_half=True,
        use_rot_pos_emb=True,
        use_abs_pos_emb=False,
        num_reg_tokens=4,
        use_fc_norm=False,
        norm_layer=partial(LayerNorm, eps=1e-5),
    )
    return _create_eva('vit_small_patch16_dinov3_qkvb', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_small_plus_patch16_dinov3(pretrained: bool = False, **kwargs) -> Eva:
    """DINOv3 S/16 Plus https://arxiv.org/abs/2508.10104"""
    model_args = dict(
        patch_size=16,
        dynamic_img_size=True,
        embed_dim=384,
        depth=12,
        num_heads=6,
        qkv_bias=False,
        # global_pool='token',  # upstream uses CLS token; default here is 'avg', pass via kwargs or --gp
        init_values=1.0e-05, # layer-scale
        rope_type='dinov3',
        rope_temperature=100,
        #rope_rescale_coords=2,  # haven't added to interface
        rope_rotate_half=True,
        use_rot_pos_emb=True,
        use_abs_pos_emb=False,
        swiglu_mlp=True,
        swiglu_align_to=8,
        num_reg_tokens=4,
        use_fc_norm=False,
        norm_layer=partial(LayerNorm, eps=1e-5),
    )
    return _create_eva('vit_small_plus_patch16_dinov3', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_small_plus_patch16_dinov3_qkvb(pretrained: bool = False, **kwargs) -> Eva:
    """DINOv3 S/16 Plus w/ QKV bias enabled (but 0) https://arxiv.org/abs/2508.10104"""
    model_args = dict(
        patch_size=16,
        dynamic_img_size=True,
        embed_dim=384,
        depth=12,
        num_heads=6,
        qkv_bias=True,
        # global_pool='token',  # upstream uses CLS token; default here is 'avg', pass via kwargs or --gp
        init_values=1.0e-05, # layer-scale
        rope_type='dinov3',
        rope_temperature=100,
        #rope_rescale_coords=2,  # haven't added to interface
        rope_rotate_half=True,
        use_rot_pos_emb=True,
        use_abs_pos_emb=False,
        swiglu_mlp=True,
        swiglu_align_to=8,
        num_reg_tokens=4,
        use_fc_norm=False,
        norm_layer=partial(LayerNorm, eps=1e-5),
    )
    return _create_eva('vit_small_plus_patch16_dinov3_qkvb', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_dinov3(pretrained: bool = False, **kwargs) -> Eva:
    """DINOv3 B/16 https://arxiv.org/abs/2508.10104"""
    model_args = dict(
        patch_size=16,
        dynamic_img_size=True,
        embed_dim=768,
        depth=12,
        num_heads=12,
        qkv_bias=False,
        # global_pool='token',  # upstream uses CLS token; default here is 'avg', pass via kwargs or --gp
        init_values=1.0e-05, # layer-scale
        rope_type='dinov3',
        rope_temperature=100,
        #rope_rescale_coords=2,  # haven't added to interface
        rope_rotate_half=True,
        use_rot_pos_emb=True,
        use_abs_pos_emb=False,
        num_reg_tokens=4,
        use_fc_norm=False,
        norm_layer=partial(LayerNorm, eps=1e-5),
    )
    return _create_eva('vit_base_patch16_dinov3', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_dinov3_qkvb(pretrained: bool = False, **kwargs) -> Eva:
    """DINOv3 B/16 w/ QKV bias enabled (but zero) https://arxiv.org/abs/2508.10104"""
    model_args = dict(
        patch_size=16,
        dynamic_img_size=True,
        embed_dim=768,
        depth=12,
        num_heads=12,
        qkv_bias=True,
        # global_pool='token',  # upstream uses CLS token; default here is 'avg', pass via kwargs or --gp
        init_values=1.0e-05, # layer-scale
        rope_type='dinov3',
        rope_temperature=100,
        #rope_rescale_coords=2,  # haven't added to interface
        rope_rotate_half=True,
        use_rot_pos_emb=True,
        use_abs_pos_emb=False,
        num_reg_tokens=4,
        use_fc_norm=False,
        norm_layer=partial(LayerNorm, eps=1e-5),
    )
    return _create_eva('vit_base_patch16_dinov3_qkvb', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch16_dinov3(pretrained: bool = False, **kwargs) -> Eva:
    """DINOv3 L/16 https://arxiv.org/abs/2508.10104"""
    model_args = dict(
        patch_size=16,
        dynamic_img_size=True,
        embed_dim=1024,
        depth=24,
        num_heads=16,
        qkv_bias=False,
        # global_pool='token',  # upstream uses CLS token; default here is 'avg', pass via kwargs or --gp
        init_values=1.0e-5, # layer-scale
        rope_type='dinov3',
        rope_temperature=100,
        use_rot_pos_emb=True,
        use_abs_pos_emb=False,
        rope_rotate_half=True,
        #rope_rescale_coords=2,  # haven't added to interface
        num_reg_tokens=4,
        use_fc_norm=False,
        norm_layer=partial(LayerNorm, eps=1e-5),
    )
    return _create_eva('vit_large_patch16_dinov3', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch16_dinov3_qkvb(pretrained: bool = False, **kwargs) -> Eva:
    """DINOv3 w/ QKV bias enabled (but zero) https://arxiv.org/abs/2508.10104"""
    model_args = dict(
        patch_size=16,
        dynamic_img_size=True,
        embed_dim=1024,
        depth=24,
        num_heads=16,
        qkv_bias=True,
        # global_pool='token',  # upstream uses CLS token; default here is 'avg', pass via kwargs or --gp
        init_values=1.0e-5, # layer-scale
        rope_type='dinov3',
        rope_temperature=100,
        use_rot_pos_emb=True,
        use_abs_pos_emb=False,
        rope_rotate_half=True,
        #rope_rescale_coords=2,  # haven't added to interface
        num_reg_tokens=4,
        use_fc_norm=False,
        norm_layer=partial(LayerNorm, eps=1e-5),
    )
    return _create_eva('vit_large_patch16_dinov3_qkvb', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_huge_plus_patch16_dinov3(pretrained: bool = False, **kwargs) -> Eva:
    """DINOv3 H/16 Plus https://arxiv.org/abs/2508.10104"""
    model_args = dict(
        patch_size=16,
        dynamic_img_size=True,
        embed_dim=1280,
        depth=32,
        num_heads=20,
        qkv_bias=False,
        # global_pool='token',  # upstream uses CLS token; default here is 'avg', pass via kwargs or --gp
        init_values=1.0e-5, # layer-scale
        rope_type='dinov3',
        rope_temperature=100,
        use_rot_pos_emb=True,
        use_abs_pos_emb=False,
        rope_rotate_half=True,
        swiglu_mlp=True,
        swiglu_align_to=8,
        #rope_rescale_coords=2,  # haven't added to interface
        num_reg_tokens=4,
        use_fc_norm=False,
        norm_layer=partial(LayerNorm, eps=1e-5),
    )
    return _create_eva('vit_huge_plus_patch16_dinov3', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_huge_plus_patch16_dinov3_qkvb(pretrained: bool = False, **kwargs) -> Eva:
    """DINOv3 H/16 Plus w/ QKV bias enabled (but zero) https://arxiv.org/abs/2508.10104"""
    model_args = dict(
        patch_size=16,
        dynamic_img_size=True,
        embed_dim=1280,
        depth=32,
        num_heads=20,
        qkv_bias=True,
        # global_pool='token',  # upstream uses CLS token; default here is 'avg', pass via kwargs or --gp
        init_values=1.0e-5, # layer-scale
        rope_type='dinov3',
        rope_temperature=100,
        use_rot_pos_emb=True,
        use_abs_pos_emb=False,
        rope_rotate_half=True,
        swiglu_mlp=True,
        swiglu_align_to=8,
        #rope_rescale_coords=2,  # haven't added to interface
        num_reg_tokens=4,
        use_fc_norm=False,
        norm_layer=partial(LayerNorm, eps=1e-5),
    )
    return _create_eva('vit_huge_plus_patch16_dinov3_qkvb', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_7b_patch16_dinov3(pretrained: bool = False, **kwargs) -> Eva:
    """DINOv3 7B/16 https://arxiv.org/abs/2508.10104"""
    model_args = dict(
        patch_size=16,
        dynamic_img_size=True,
        embed_dim=4096,
        depth=40,
        num_heads=32,
        qkv_bias=False,
        # global_pool='token',  # upstream uses CLS token; default here is 'avg', pass via kwargs or --gp
        mlp_ratio=2,
        init_values=1.0e-5, # layer-scale
        rope_type='dinov3',
        rope_temperature=100,
        use_rot_pos_emb=True,
        use_abs_pos_emb=False,
        rope_rotate_half=True,
        swiglu_mlp=True,
        swiglu_align_to=64,
        #rope_rescale_coords=2,  # haven't added to interface
        num_reg_tokens=4,
        use_fc_norm=False,
        norm_layer=partial(LayerNorm, eps=1e-5),
    )
    return _create_eva('vit_7b_patch16_dinov3', pretrained=pretrained, **dict(model_args, **kwargs))
