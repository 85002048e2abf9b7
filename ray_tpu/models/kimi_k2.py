"""Kimi-K2 family (flax linen): rotary latent attention (MLA with a
low-rank query and YaRN) in every layer, routed SwiGLU experts.

Source: moonshotai/Kimi-K2.7-Code ``config.json`` (``model_type``
``kimi_k2``; DeepSeek-V3's block). Pre-norm RMSNorm with a residual round
the mixer and round the feed-forward:

  h = x + MLA(RMSNorm(x))        models/mla.py: c_q = RMSNorm(x W_qa),
                                 q = c_q W_qb, (c, k_r) = x W_kva, the
                                 rope parts rotated at the token's
                                 absolute position (YaRN frequencies),
                                 softmax scale 192^-1/2 m^2
  y = h + FFN(RMSNorm(h))        a dense SwiGLU in the
                                 ``first_k_dense_replace`` leading layers,
                                 then ``n_routed_experts`` sigmoid-scored
                                 experts, ``num_experts_per_tok`` a token,
                                 beside ``n_shared_experts`` shared ones
                                 (parallel/moe.py:RoutedExperts)

One module serves both forms, as ``models/kimi_linear.py`` does. The
training form, ``model(ids)``, is a full forward over whole sequences.
The served form, ``model(ids, cache=..., seq_lengths=..., valid=...)``,
is one incremental step over what ``cache_spec`` states: ONE pool
``kv_pages`` [layers, P, bs, row] of latent rows ``(c, RoPE(k_r))``
(kv_lora_rank + rope values in whole lanes: 576 in 640) for all the
layers, and no per-sequence state: every cached token is a page row, so
what cuts, shares or ships pages (prefix cache, speculative windows,
prefill/decode hand-off) works on it as on K and V pages.

Weights are stored and multiplied in ``dtype`` (bfloat16 as served);
norms, the router and the softmax are float32. ``experts_held`` and
``vocab_size`` are the chip's share, cut in the configuration and never
here.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.mla import MLAMixer, RMSNorm, YarnRope, dense, lanes, \
    yarn_rope
from ray_tpu.parallel.moe import RoutedExperts, SwiGLU


@dataclasses.dataclass(frozen=True)
class KimiK2Config:
    vocab_size: int = 163840
    hidden_size: int = 7168
    num_hidden_layers: int = 61
    # MLA
    num_attention_heads: int = 64
    q_lora_rank: Optional[int] = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 50000.0
    rope_scaling: Optional[Any] = dataclasses.field(
        default_factory=lambda: {
            "type": "yarn", "factor": 64.0, "beta_fast": 32.0,
            "beta_slow": 1.0, "mscale": 1.0, "mscale_all_dim": 1.0,
            "original_max_position_embeddings": 4096})
    # feed-forward
    intermediate_size: int = 18432
    first_k_dense_replace: int = 1
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 384             # the router's width
    experts_held: Optional[Tuple[int, int]] = None   # (first, count) here
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.827
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 4096         # what a served sequence may reach
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        # a configuration file gives lists and dicts; the object is hashed
        if isinstance(self.experts_held, list):
            object.__setattr__(self, "experts_held",
                               tuple(self.experts_held))
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))

    @property
    def rope(self) -> YarnRope:
        return yarn_rope(self.qk_rope_head_dim, self.rope_theta,
                         dict(self.rope_scaling or ()))

    @property
    def n_layers(self) -> int:
        return self.num_hidden_layers

    @classmethod
    def tiny(cls, vocab_size: int = 512, **kw):       # tests
        base = dict(
            vocab_size=vocab_size, hidden_size=64, num_hidden_layers=3,
            num_attention_heads=2, q_lora_rank=24, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            # the blend inside 4 pairs: 64 positions, 8 and 1 turns
            rope_scaling={"type": "yarn", "factor": 8.0, "beta_fast": 8.0,
                          "beta_slow": 1.0, "mscale": 1.0,
                          "mscale_all_dim": 1.0,
                          "original_max_position_embeddings": 64},
            rope_theta=100.0, intermediate_size=128,
            moe_intermediate_size=32, n_routed_experts=16,
            experts_held=(0, 4), num_experts_per_tok=4, max_seq_len=256,
            dtype=jnp.float32)
        base.update(kw)
        return cls(**base)


def cache_spec(cfg: KimiK2Config) -> Dict[str, Any]:
    """What a served sequence keeps between steps, for the adapter: one
    latent pool over all the layers, no state."""
    held = cfg.experts_held[1] if cfg.experts_held else cfg.n_routed_experts
    return {
        "expert_counts": (max(cfg.num_hidden_layers
                              - cfg.first_k_dense_replace, 0), held),
        # ``moe.expert_product``'s arguments beside a step's tokens
        "routed_experts": (cfg.num_experts_per_tok, cfg.n_routed_experts,
                           held, cfg.hidden_size, jnp.dtype(cfg.dtype).itemsize),
        "pages": {"kv_pages": {
            "layers": cfg.num_hidden_layers,
            "row": lanes(cfg.kv_lora_rank + cfg.qk_rope_head_dim),
            "latent_rank": cfg.kv_lora_rank, "dtype": cfg.dtype}},
        "state": {},
    }


class KimiK2Block(nn.Module):
    config: KimiK2Config
    routed: bool

    @nn.compact
    def __call__(self, x, mixer_kwargs):
        cfg = self.config
        h = RMSNorm(cfg.rms_norm_eps, name="attn_norm")(x)
        with jax.named_scope("mla"):
            y, pages = MLAMixer(cfg, name="mla")(h, **mixer_kwargs)
        x = x + y.astype(x.dtype)
        h = RMSNorm(cfg.rms_norm_eps, name="ffn_norm")(x)
        if self.routed:
            y, counts = RoutedExperts(
                cfg.n_routed_experts, cfg.moe_intermediate_size,
                cfg.num_experts_per_tok, held=cfg.experts_held,
                scaling=cfg.routed_scaling_factor,
                renormalize=cfg.norm_topk_prob,
                shared_d_ff=cfg.n_shared_experts
                * cfg.moe_intermediate_size, dtype=cfg.dtype, name="moe")(
                    h, valid=mixer_kwargs.get("valid"))
        else:
            with jax.named_scope("mlp"):
                y = SwiGLU(cfg.intermediate_size, cfg.dtype, name="mlp")(h)
            counts = None
        return x + y.astype(x.dtype), pages, counts


class KimiK2Model(nn.Module):
    config: KimiK2Config

    @nn.compact
    def __call__(self, input_ids, cache=None, seq_lengths=None, valid=None,
                 logits_at=None):
        """Logits [B, S, V] of a full forward; or, with ``cache``
        (``{"kv_pages", "block_tables"}``: ``cache_spec``), one
        incremental step: ``seq_lengths`` [B] the tokens cached before
        this call (the new tokens' absolute positions start there),
        ``valid`` [B, S] the real tokens of a padded bucket. Returns
        ``(logits, new cache, expert_counts)``, expert_counts [routed
        layers, experts held] int32. ``logits_at`` ([B] int) keeps one
        position a row before the head."""
        cfg = self.config
        dt = cfg.dtype
        embed = self.param("embed", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.hidden_size), dt)
        # the residual stream is float32, every product takes bfloat16
        # operands (as models/kimi_linear.py, and for its reason)
        x = embed[input_ids].astype(jnp.float32)
        served = cache is not None
        pages = cache["kv_pages"] if served else None
        counts = []
        for i in range(cfg.num_hidden_layers):
            kw: Dict[str, Any] = {"valid": valid}
            if served:
                kw.update(pages=pages, block_tables=cache["block_tables"],
                          seq_lengths=seq_lengths, layer=i)
            x, pages, c = KimiK2Block(
                cfg, routed=i >= cfg.first_k_dense_replace,
                name=f"layers_{i}")(x, kw)
            if c is not None:
                counts.append(c)
        x = RMSNorm(cfg.rms_norm_eps, name="final_norm")(x)
        if logits_at is not None:
            x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
        with jax.named_scope("lm_head"):
            logits = jnp.matmul(
                x.astype(dt), dense(self, "lm_head", (cfg.hidden_size,
                                                      cfg.vocab_size), dt),
                preferred_element_type=jnp.float32)
        if not served:
            return logits
        counts = jnp.stack(counts) if counts else jnp.zeros((0, 0), jnp.int32)
        return logits, dict(cache, kv_pages=pages), counts
