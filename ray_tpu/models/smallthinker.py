"""SmallThinker family (flax linen): grouped-query attention whose reach
and position encoding are a function of the layer's layout (full layers
WITHOUT rotary, NoPE, beside sliding-window layers with it), and routed
ReGLU experts whose router reads the ATTENTION's input.

Source: PowerInfer/SmallThinker-21BA3B-Instruct ``config.json``
(``model_name`` ``smallthinker_21b_instruct``). Pre-norm RMSNorm, a
residual round the attention and round the experts:

  n = RMSNorm(x)
  r = n W_r                      the router's logits, float32: it reads
                                 what the attention reads, so the choice
                                 of experts does not wait for the attention
  h = x + Attn_l(n)              ``num_attention_heads`` query heads of
                                 ``head_dim`` over ``num_key_value_heads``
                                 key/value heads. ``sliding_window_layout``
                                 0: every earlier position, no rotary;
                                 1: ``p - sliding_window_size + 1 .. p``,
                                 q and k rotated over the whole head at the
                                 absolute position (``rope_theta``). No
                                 gate, no bias, no query/key norm
  y = h + Experts(RMSNorm(h))    the ``moe_num_active_primary_experts``
                                 largest of ``r``, weighed by a softmax
                                 over the chosen (``softmax`` over all,
                                 renormalised over the chosen, is the same
                                 numbers: ``RoutedExperts(score="softmax",
                                 renormalize=True)``), each ``W_d (relu(W_g
                                 m) * W_u m)``; no shared expert

The attention, the model's two forms (``model(ids)``, and the incremental
step over ``k_full`` / ``v_full`` / ``k_window`` / ``v_window``) and
``cache_spec`` are ``models/laguna.py``'s, read with this family's
numbers: the config below carries the names they ask for (``layer_types``,
``rope_of``, ``num_attention_heads_per_layer``, ...). Its own: the block
above.

Device-trace scopes: ``attn_full/{qkv,write,attend,out}``,
``attn_window/{qkv,rope,write,attend,out}``, ``moe/router``,
``moe/experts``, ``lm_head``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp

from ray_tpu.models.laguna import (FULL, SLIDING, LagunaAttention,
                                   LagunaModel, PartialRope,
                                   cache_spec)  # noqa: F401 (the adapter's)
from ray_tpu.models.mla import RMSNorm, YarnRope
from ray_tpu.parallel.moe import RoutedExperts


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    # attention
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window_size: int = 4096
    # 1: a window layer / a layer with rotary. None: 0 1 1 1, repeated
    sliding_window_layout: Optional[Tuple[int, ...]] = None
    rope_layout: Optional[Tuple[int, ...]] = None
    rope_theta: float = 1.5e6
    # experts
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_ffn_hidden_size: int = 768
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 4096         # what a served sequence may reach
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        n = self.num_hidden_layers
        for name in ("sliding_window_layout", "rope_layout"):
            value = getattr(self, name)
            value = tuple(int(v) for v in value) if value is not None \
                else tuple(int(i % 4 != 0) for i in range(n))
            # a configuration file gives the published lists whole: the
            # layers kept are their first ``num_hidden_layers``
            if len(value) < n:
                raise ValueError(f"{name} names {len(value)} layers of {n}")
            object.__setattr__(self, name, value[:n])
        if self.sliding_window_layout != self.rope_layout:
            raise ValueError(
                "a layer's rotary follows its window here, as published "
                "(rope_layout == sliding_window_layout): "
                f"{self.rope_layout} != {self.sliding_window_layout}")

    # ---- what models/laguna.py reads of a config ----

    n_layers = property(lambda self: self.num_hidden_layers)
    sliding_window = property(lambda self: self.sliding_window_size)
    gating = False
    num_experts = property(lambda self: self.moe_num_primary_experts)
    num_experts_per_tok = property(
        lambda self: self.moe_num_active_primary_experts)

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(SLIDING if w else FULL
                     for w in self.sliding_window_layout)

    @property
    def mlp_layer_types(self) -> Tuple[str, ...]:
        return ("sparse",) * self.num_hidden_layers

    @property
    def num_attention_heads_per_layer(self) -> Tuple[int, ...]:
        return (self.num_attention_heads,) * self.num_hidden_layers

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

    def rope_of(self, kind: str) -> Optional[PartialRope]:
        """A window layer's rotary (the whole head, plain frequencies);
        None for a full layer: it has no position encoding."""
        if kind == FULL:
            return None
        return PartialRope(YarnRope(self.head_dim, float(self.rope_theta)))

    @classmethod
    def tiny(cls, vocab_size: int = 512, **kw):       # tests
        """Two full and three window layers, groups of 7 query heads, a
        window shorter than the tests' long prompts."""
        base = dict(
            vocab_size=vocab_size, hidden_size=64, num_hidden_layers=5,
            num_attention_heads=14, num_key_value_heads=2, head_dim=16,
            sliding_window_size=32,
            sliding_window_layout=(0, 1, 1, 1, 0),
            rope_layout=(0, 1, 1, 1, 0), rope_theta=100.0,
            moe_num_primary_experts=16, moe_num_active_primary_experts=4,
            moe_ffn_hidden_size=32, max_seq_len=256, dtype=jnp.float32)
        base.update(kw)
        return cls(**base)


class SmallThinkerBlock(nn.Module):
    config: SmallThinkerConfig
    index: int

    @nn.compact
    def __call__(self, x, attn_kwargs, valid=None):
        cfg, i = self.config, self.index
        window = cfg.sliding_window_size \
            if cfg.sliding_window_layout[i] else None
        n = RMSNorm(cfg.rms_norm_eps, name="attn_norm")(x)
        y, k_pages, v_pages = LagunaAttention(
            cfg, cfg.num_attention_heads, window, name="attn")(
                n, valid=valid, **attn_kwargs)
        h = x + y.astype(x.dtype)
        m = RMSNorm(cfg.rms_norm_eps, name="ffn_norm")(h)
        # the router scores ``n``, the attention's input; the experts
        # multiply ``m``
        y, counts = RoutedExperts(
            cfg.moe_num_primary_experts, cfg.moe_ffn_hidden_size,
            cfg.moe_num_active_primary_experts, renormalize=True,
            dtype=cfg.dtype, score="softmax", act="relu", name="moe")(
                m, valid=valid, router_x=n)
        return h + y.astype(x.dtype), k_pages, v_pages, counts


class SmallThinkerModel(LagunaModel):
    """``LagunaModel`` over ``SmallThinkerBlock`` layers: the token table,
    the loop over the layers with each kind's pools and tables, the final
    norm and the untied head are the same code."""
    config: SmallThinkerConfig
    block = SmallThinkerBlock
