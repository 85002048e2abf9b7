"""LongCat-Flash family (flax linen): shortcut-connected double layers.
One logical layer is two latent-attention (MLA) sublayers and two dense
SwiGLU sublayers beside ONE routed product, whose result is computed
after the first attention and added at the layer's end.

Source: meituan-longcat/LongCat-Flash-Omni ``config.json`` (the language
model; ``model_type`` ``longcat_flash``). ``N_k`` = RMSNorm with a gain:

  h1 = x  + MLA_0(N_1(x))          models/mla.py: a low-rank query, plain
  u  = N_2(h1)                     rotary; q times (hidden / q_rank)^1/2
  m  = MoE(u)                      after its up-projection, the normalised
  h2 = h1 + SwiGLU_0(u)            latent times (hidden / kv_rank)^1/2
  h3 = h2 + MLA_1(N_3(h2))
  y  = h3 + SwiGLU_1(N_4(h3)) + m

``MoE`` (parallel/moe.py:RoutedExperts): a softmax over ``n_routed_experts
+ zero_expert_num`` router outputs, the ``moe_topk`` largest, weights
``p_i * routed_scaling_factor`` NOT renormalised; an output below
``n_routed_experts`` is a SwiGLU expert, one above gives the token itself
(a zero-compute expert), so a token runs 0 to ``moe_topk`` real experts.
No shared expert, no bias.

One module serves both forms, as ``models/kimi_k2.py`` does. The training
form, ``model(ids)``, is a full forward over whole sequences. The served
form, ``model(ids, cache=..., seq_lengths=..., valid=...)``, is one
incremental step over what ``cache_spec`` states: ONE pool ``kv_pages``
[2 * layers, P, bs, row] of latent rows, sublayer ``2 i + j`` the rows of
layer ``i``'s attention ``j`` (as the source indexes its cache), and no
per-sequence state: every cached token is a page row, so what cuts, shares
or ships pages works on it as on Kimi-K2's.

Device-trace scopes: ``sub0/mla``, ``sub0/mlp``, ``sub1/mla``,
``sub1/mlp`` (a view that reads ``mla`` or ``mlp`` reads both sublayers),
``moe/router``, ``moe/experts``, ``moe/zero``, ``lm_head``.

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
class LongcatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    num_layers: int = 28            # logical layers: two sublayers each
    # MLA
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    rope_theta: float = 1e7
    # feed-forward
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    n_routed_experts: int = 512             # the router's real outputs
    zero_expert_num: int = 256              # its zero-compute outputs
    experts_held: Optional[Tuple[int, int]] = None   # (first, count) here
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    rms_norm_eps: float = 1e-5
    # the norms inside an MLA (of c_q and c_kv): the source's module gives
    # them no eps, so they run at its class's default
    latent_norm_eps: float = 1e-6
    # a prompt's float32 logits, one block of 512 queries against the
    # whole context, above which an MLA walks the keys in blocks inside
    # one kernel (models/mla.py). At ``ops.attention``'s own budget a
    # 2,048-token prompt over 3,072 positions (384 MiB a block) attended
    # by XLA, 12.2 ms a sublayer, 42% of a prompt; 1.9 ms by the kernel
    # (my chip runs, PR 41)
    prompt_logits_bytes: int = 1 << 28
    max_seq_len: int = 4096         # what a served sequence may reach
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        # a configuration file gives a list; the object is hashed
        if isinstance(self.experts_held, list):
            object.__setattr__(self, "experts_held",
                               tuple(self.experts_held))

    @property
    def rope(self) -> YarnRope:
        return yarn_rope(self.qk_rope_head_dim, self.rope_theta, None)

    @property
    def q_scale(self) -> float:
        return (self.hidden_size / self.q_lora_rank) ** 0.5 \
            if self.mla_scale_q_lora else 1.0

    @property
    def latent_scale(self) -> float:
        return (self.hidden_size / self.kv_lora_rank) ** 0.5 \
            if self.mla_scale_kv_lora else 1.0

    @property
    def n_layers(self) -> int:
        return self.num_layers

    @classmethod
    def tiny(cls, vocab_size: int = 512, **kw):       # tests
        base = dict(
            vocab_size=vocab_size, hidden_size=64, num_layers=2,
            num_attention_heads=2, q_lora_rank=24, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            rope_theta=100.0, ffn_hidden_size=128,
            expert_ffn_hidden_size=32, n_routed_experts=16,
            zero_expert_num=8, experts_held=(0, 4), moe_topk=4,
            max_seq_len=256, dtype=jnp.float32)
        base.update(kw)
        return cls(**base)


def cache_spec(cfg: LongcatFlashConfig) -> Dict[str, Any]:
    """What a served sequence keeps between steps, for the adapter: one
    latent pool over the ``2 * num_layers`` attention sublayers, no
    state."""
    held = cfg.experts_held[1] if cfg.experts_held else cfg.n_routed_experts
    return {
        "expert_counts": (cfg.num_layers, held),
        # ``moe.expert_product``'s arguments beside a step's tokens: the
        # real experts and, apart, the router's zero-compute outputs
        "routed_experts": (cfg.moe_topk, cfg.n_routed_experts, held,
                           cfg.hidden_size, jnp.dtype(cfg.dtype).itemsize,
                           cfg.zero_expert_num),
        "pages": {"kv_pages": {
            "layers": 2 * cfg.num_layers,
            "row": lanes(cfg.kv_lora_rank + cfg.qk_rope_head_dim),
            "latent_rank": cfg.kv_lora_rank, "dtype": cfg.dtype}},
        "state": {},
    }


class LongcatFlashBlock(nn.Module):
    """One logical layer; ``layer`` is its index (its attention
    sublayers' rows are pool layers ``2 * layer`` and ``2 * layer +
    1``)."""
    config: LongcatFlashConfig

    @nn.compact
    def __call__(self, x, mixer_kwargs, layer: int):
        cfg = self.config
        served = mixer_kwargs.get("pages") is not None
        pages = mixer_kwargs.get("pages")
        shortcut = counts = zeros = None
        for j in (0, 1):
            h = RMSNorm(cfg.rms_norm_eps, name=f"attn_norm_{j}")(x)
            kw = dict(mixer_kwargs)
            if served:
                kw.update(pages=pages, layer=2 * layer + j)
            with jax.named_scope(f"sub{j}/mla"):
                y, pages = MLAMixer(cfg, name=f"mla_{j}")(h, **kw)
            x = x + y.astype(x.dtype)
            h = RMSNorm(cfg.rms_norm_eps, name=f"ffn_norm_{j}")(x)
            if j == 0:
                # the shortcut: computed here, added at the layer's end
                shortcut, counts, *zeros = RoutedExperts(
                    cfg.n_routed_experts, cfg.expert_ffn_hidden_size,
                    cfg.moe_topk, held=cfg.experts_held,
                    scaling=cfg.routed_scaling_factor, renormalize=False,
                    dtype=cfg.dtype, score="softmax",
                    zero_experts=cfg.zero_expert_num, name="moe")(
                        h, valid=mixer_kwargs.get("valid"))
            with jax.named_scope(f"sub{j}/mlp"):
                y = SwiGLU(cfg.ffn_hidden_size, cfg.dtype, name=f"mlp_{j}")(h)
            x = x + y.astype(x.dtype)
        x = x + shortcut.astype(x.dtype)
        return x, pages, counts, (zeros[0] if zeros
                                  else jnp.zeros((), jnp.int32))


class LongcatFlashModel(nn.Module):
    config: LongcatFlashConfig

    @nn.compact
    def __call__(self, input_ids, cache=None, seq_lengths=None, valid=None,
                 logits_at=None):
        """Logits [B, S, V] of a full forward; or, with ``cache``
        (``{"kv_pages", "block_tables"}``: ``cache_spec``), one
        incremental step: ``seq_lengths`` [B] the tokens cached before
        this call (the new tokens' absolute positions start there),
        ``valid`` [B, S] the real tokens of a padded bucket. Returns
        ``(logits, new cache, expert_counts, zero_counts)``,
        expert_counts [layers, experts held] int32 the real tokens each
        held expert got, zero_counts [layers] int32 the assignments to a
        zero-compute expert. ``logits_at`` ([B] int) keeps one position a
        row before the head."""
        cfg = self.config
        dt = cfg.dtype
        embed = self.param("embed", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.hidden_size), dt)
        # the residual stream is float32, every product takes bfloat16
        # operands (as models/kimi_linear.py, and for its reason)
        x = embed[input_ids].astype(jnp.float32)
        served = cache is not None
        pages = cache["kv_pages"] if served else None
        counts, zeros = [], []
        for i in range(cfg.num_layers):
            kw: Dict[str, Any] = {"valid": valid}
            if served:
                kw.update(pages=pages, block_tables=cache["block_tables"],
                          seq_lengths=seq_lengths)
            x, pages, c, z = LongcatFlashBlock(cfg, name=f"layers_{i}")(
                x, kw, i)
            counts.append(c)
            zeros.append(z)
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
        return (logits, dict(cache, kv_pages=pages), jnp.stack(counts),
                jnp.stack(zeros))
