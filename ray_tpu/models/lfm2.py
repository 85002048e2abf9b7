"""LFM2-MoE family (flax linen): gated short convolutions with a few
grouped-query attention layers among them, a dense SwiGLU in the leading
layers and sigmoid-routed experts in the rest, the head tied to the
token table.

Source: LiquidAI/LFM2-8B-A1B ``config.json`` (``model_type``
``lfm2_moe``). The operator of layer ``i`` is ``layer_types[i]`` (``conv``
| ``full_attention``; of the published 24: attention at 2, 6, 10, 14, 18,
21), its feed-forward part dense where ``i < num_dense_layers`` and routed
otherwise: the two vary independently. Pre-norm RMSNorm with a residual
round the operator and round the feed-forward part, one RMSNorm after the
last layer (the family's ``embedding_norm``), logits against the token
table. No bias anywhere. With ``n`` the operator's normed input and ``m``
the feed-forward part's, ``d = hidden_size``:

  Convolution  [B | C | u] = n W_in             d -> 3 d, in this order
               g = B * u                        elementwise
               c_t = sum_j w_j g_(t-(K-1)+j)    causal, depthwise, kernel
                                                ``conv_L_cache`` = K, no
                                                bias, NO activation; the
                                                last K - 1 rows of g are
                                                what a sequence carries
               out = (C * c) W_out              d -> d
  Attention    ``models/laguna.py``'s, read with this family's numbers:
               ``num_attention_heads`` query heads of ``head_dim`` over
               ``num_key_value_heads``; q and k under an RMSNorm over a
               head's values (one learned gain of ``head_dim`` each)
               BEFORE the rotary, which turns the whole head in halves
               (``rope_theta``, no scaling); no gate
  Dense        (silu(m W_1) * (m W_3)) W_2      ``parallel/moe.py:SwiGLU``
  Routed       s = sigmoid(m W_r), float32; the ``num_experts_per_tok``
               largest of ``s + expert_bias`` (the bias chooses and never
               weighs), weighed by ``s / (sum of the chosen s + 1e-6)``
               times ``routed_scaling_factor``; no shared expert
               (``parallel/moe.py:RoutedExperts``)

Every layer is a block of its own (``layers_0``, ``layers_1``, ...), as
``models/laguna.py``'s are, and NOT a run looped over stacked parameters
as ``models/jamba.py``'s Mamba layers: the routed product is a Mosaic
call, whose operands the compiler materializes, so a loop over a stack
[run, experts, d, d_ff] copies a layer's experts (705 MB at the published
widths) out of the stack before every call (compiled for the described
chip, PR 53: 0.68 GiB of temporaries and three ``dynamic-slice`` fusions
of ``bf16[32,2048,1792]`` a layer, which a decode step would read and
write beside the weights themselves).

One module serves both forms. ``model(ids)`` is a full forward. The
served form, ``model(ids, cache=..., seq_lengths=..., valid=...)``, is
one incremental step over what ``cache_spec`` states for the adapter
(serve/llm/model_runner.py):

  pages   ``k_pages`` / ``v_pages`` [n_attn, P, bs, Hkv x head_dim], for
          the attention layers alone
  state   a slot a running sequence for the convolution layers:
          ``conv_tail`` [n_conv, slots, K - 1, d], the last K - 1 rows of
          ``g``. A tail alone: no array names a ``recurrence`` (a decode
          step's convolution is three multiply-adds a channel)

Weights are stored and multiplied in ``dtype`` (bfloat16 as served), the
tail holds ``dtype`` rows (multiplied and summed in float32); norms, the
router, rotary angles, the softmax and the residual stream are float32.

Device-trace scopes: ``conv/{in_proj,mix,out_proj}`` (the two gates, the
taps and the tail's read and write all lie in ``conv/mix``),
``attn_full/{qkv,norm,rope,write,attend,out}``, ``mlp``,
``moe/{router,experts}``, ``lm_head``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.laguna import FULL, LagunaAttention, PartialRope
from ray_tpu.models.mla import RMSNorm, YarnRope, dense as _dense
from ray_tpu.ops import linear_attention as LA
from ray_tpu.parallel.moe import RoutedExperts, SwiGLU

CONV, ATTENTION = "conv", FULL
DENSE, ROUTED = "dense", "routed"

_PUBLISHED_LAYER_TYPES = tuple(
    ATTENTION if i in (2, 6, 10, 14, 18, 21) else CONV for i in range(24))


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    hidden_size: int = 2048
    num_hidden_layers: int = 24
    # given whole, as published: the layers kept are the first
    # ``num_hidden_layers``
    layer_types: Tuple[str, ...] = _PUBLISHED_LAYER_TYPES
    num_dense_layers: int = 2
    # the convolution operator
    conv_L_cache: int = 3
    conv_bias: bool = False
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: Optional[int] = None          # None: hidden_size / heads
    rope_theta: float = 1e6
    # feed-forward
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    max_seq_len: int = 4096         # what a served sequence may reach
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        n = self.num_hidden_layers
        types = tuple(self.layer_types)
        if len(types) < n or set(types) - {CONV, ATTENTION}:
            raise ValueError(
                f"layer_types names {len(types)} layers of {n}, each "
                f"{CONV!r} or {ATTENTION!r}: {types}")
        object.__setattr__(self, "layer_types", types[:n])
        if self.head_dim is None:
            object.__setattr__(
                self, "head_dim", self.hidden_size // self.num_attention_heads)
        if self.conv_bias or not self.tie_word_embeddings \
                or not self.norm_topk_prob or not self.use_expert_bias:
            raise ValueError(
                "this file holds the family as published: conv_bias false, "
                "a tied head, norm_topk_prob and use_expert_bias true")

    n_layers = property(lambda self: self.num_hidden_layers)
    rms_norm_eps = property(lambda self: self.norm_eps)

    def ffn_kinds(self) -> Tuple[str, ...]:
        return tuple(DENSE if i < self.num_dense_layers else ROUTED
                     for i in range(self.num_hidden_layers))

    # ---- what models/laguna.py's attention reads of a config ----
    gating = False
    qk_norm = True          # an RMSNorm over a head, before the rotary

    def rope_of(self, kind: str) -> PartialRope:
        return PartialRope(YarnRope(self.head_dim, float(self.rope_theta)))

    @classmethod
    def tiny(cls, vocab_size: int = 512, **kw):       # tests
        """C C A C C C A C: two dense convolution layers, then routed
        layers: three and one convolutions round two attention layers of
        eight query heads over two."""
        base = dict(
            vocab_size=vocab_size, hidden_size=64, num_hidden_layers=8,
            layer_types=(CONV, CONV, ATTENTION, CONV, CONV, CONV, ATTENTION,
                         CONV),
            num_attention_heads=8, num_key_value_heads=2, head_dim=16,
            rope_theta=100.0, intermediate_size=128,
            moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
            max_seq_len=256, dtype=jnp.float32)
        base.update(kw)
        return cls(**base)


def cache_spec(cfg: Lfm2Config) -> Dict[str, Any]:
    """What a served sequence keeps between steps, for the adapter: K and
    V pages for the attention layers alone, and the convolution layers'
    tails: a state without a recurrence."""
    page = {"layers": cfg.layer_types.count(ATTENTION),
            "row": cfg.num_key_value_heads * cfg.head_dim,
            "dtype": cfg.dtype, "head_dim": cfg.head_dim,
            "q_heads": cfg.num_attention_heads}
    return {
        "expert_counts": (cfg.ffn_kinds().count(ROUTED), cfg.num_experts),
        # ``moe.expert_product``'s arguments beside a step's tokens
        "routed_experts": (cfg.num_experts_per_tok, cfg.num_experts,
                           cfg.num_experts, cfg.hidden_size,
                           jnp.dtype(cfg.dtype).itemsize),
        "pages": {"k_pages": dict(page), "v_pages": dict(page)},
        "state": {"conv_tail": {
            "shape": (cfg.layer_types.count(CONV), cfg.conv_L_cache - 1,
                      cfg.hidden_size), "dtype": cfg.dtype}},
    }


class ShortConv(nn.Module):
    """The gated short convolution. x [B, S, D] (normed); ``tail`` [B,
    K - 1, D] the rows of ``g`` the rows' sequences carried here (None:
    the start of a sequence). Returns (y, new tail)."""
    config: Lfm2Config

    @nn.compact
    def __call__(self, x, tail=None, valid=None):
        cfg = self.config
        B, S, D = x.shape
        K, dt_, f32 = cfg.conv_L_cache, cfg.dtype, jnp.float32
        with jax.named_scope("conv/in_proj"):
            bcu = jnp.matmul(x.astype(dt_),
                             _dense(self, "in_proj", (D, 3 * D), dt_),
                             preferred_element_type=f32)
        with jax.named_scope("conv/mix"):
            if tail is None:
                tail = jnp.zeros((B, K - 1, D), dt_)
            gate_b, gate_c, u = bcu[..., :D], bcu[..., D:2 * D], bcu[..., 2 * D:]
            # the convolution's rows are what the tail holds of them: the
            # activations' dtype, multiplied and summed in float32
            g = (gate_b * u).astype(dt_).astype(f32)
            # one token a row: the new tail is the old one moved up a row,
            # or the old one (a select, as models/jamba.py and for its
            # reason); a prompt gathers its last K - 1 real rows
            n_new = None if valid is None or S == 1 else \
                jnp.sum(valid.astype(jnp.int32), axis=1)
            c, new_tail = LA.short_conv(
                g, tail, _dense(self, "conv", (K, D), dt_, std=0.5), n_new)
            if valid is not None and S == 1:
                new_tail = jnp.where(valid[:, :, None], new_tail,
                                     tail.astype(new_tail.dtype))
            y = (gate_c * c).astype(dt_)
        with jax.named_scope("conv/out_proj"):
            return jnp.matmul(y, _dense(self, "out_proj", (D, D), dt_),
                              preferred_element_type=f32), new_tail


def _feed_forward(cfg, kind, x, valid):
    """``x + FeedForward(RMSNorm(x))``; the routed kind's per-expert
    token counts beside it (None: dense)."""
    h = RMSNorm(cfg.norm_eps, name="ffn_norm")(x)
    if kind == DENSE:
        with jax.named_scope("mlp"):
            y = SwiGLU(cfg.intermediate_size, cfg.dtype, name="mlp")(h)
        return x + y.astype(x.dtype), None
    y, counts = RoutedExperts(
        cfg.num_experts, cfg.moe_intermediate_size, cfg.num_experts_per_tok,
        scaling=cfg.routed_scaling_factor, renormalize=True,
        renormalize_eps=1e-6, dtype=cfg.dtype, score="sigmoid",
        name="moe")(h, valid=valid)
    return x + y.astype(x.dtype), counts


class ConvBlock(nn.Module):
    """One convolution layer. ``pool`` (served) is the tail pool [n_conv,
    slots, K - 1, D] and ``layer`` this layer's index among the
    convolution layers; ``slots`` [B] int the rows' state slots (None:
    row r is slot r + 1). Returns (x, pool, the routed kind's token
    counts)."""
    config: Lfm2Config
    ffn: str

    @nn.compact
    def __call__(self, x, pool=None, layer=0, slots=None, valid=None):
        cfg = self.config
        B = x.shape[0]
        at = slice(1, 1 + B) if slots is None else slots
        h = RMSNorm(cfg.norm_eps, name="operator_norm")(x)
        tail = None
        if pool is not None:
            with jax.named_scope("conv/mix"):
                tail = pool[layer, at]
        y, new_tail = ShortConv(cfg, name="conv")(h, tail, valid)
        if pool is not None:
            with jax.named_scope("conv/mix"):
                pool = pool.at[layer, at].set(new_tail.astype(pool.dtype))
        x, counts = _feed_forward(cfg, self.ffn, x + y.astype(x.dtype), valid)
        return x, pool, counts


class AttentionBlock(nn.Module):
    config: Lfm2Config
    ffn: str

    @nn.compact
    def __call__(self, x, attn_kwargs, valid=None):
        cfg = self.config
        h = RMSNorm(cfg.norm_eps, name="operator_norm")(x)
        y, k_pages, v_pages = LagunaAttention(
            cfg, cfg.num_attention_heads, None, name="attn")(
                h, valid=valid, **attn_kwargs)
        x, counts = _feed_forward(cfg, self.ffn, x + y.astype(x.dtype), valid)
        return x, k_pages, v_pages, counts


class Lfm2Model(nn.Module):
    config: Lfm2Config

    @nn.compact
    def __call__(self, input_ids, cache=None, seq_lengths=None, valid=None,
                 logits_at=None):
        """Logits [B, S, V] of a full forward; or, with ``cache``
        (``{"k_pages", "v_pages", "block_tables", "conv_tail", "slots"}``:
        ``cache_spec``; ``slots`` [B] is each row's state slot; without it
        row r IS slot r + 1, a full decode batch, whose tails are read and
        written where they lie), one incremental step: ``seq_lengths`` [B]
        the tokens cached before this call, ``valid`` [B, S] the real
        tokens of a padded bucket (a padded position or row leaves tail
        and pages untouched). Returns ``(logits, new cache,
        expert_counts)``, expert_counts [routed layers, experts] int32.
        ``logits_at`` ([B] int) keeps one position a row before the
        head."""
        cfg = self.config
        dt_ = cfg.dtype
        embed = self.param("embed", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.hidden_size), dt_)
        # the residual stream is float32, every product takes ``dtype``
        # operands (as models/kimi_linear.py, and for its reason)
        x = embed[input_ids].astype(jnp.float32)
        served = cache is not None
        cache = dict(cache) if served else {}
        pool = cache.get("conv_tail")
        i_conv = i_attn = 0
        counts = []
        for i, (op, ffn) in enumerate(zip(cfg.layer_types, cfg.ffn_kinds())):
            if op == CONV:
                x, pool, c = ConvBlock(cfg, ffn, name=f"layers_{i}")(
                    x, pool, i_conv, cache.get("slots"), valid)
                i_conv += 1
            else:
                kw: Dict[str, Any] = {}
                if served:
                    kw = dict(k_pages=cache["k_pages"],
                              v_pages=cache["v_pages"],
                              block_tables=cache["block_tables"],
                              seq_lengths=seq_lengths, layer=i_attn)
                x, k_pages, v_pages, c = AttentionBlock(
                    cfg, ffn, name=f"layers_{i}")(x, kw, valid=valid)
                if served:
                    cache["k_pages"], cache["v_pages"] = k_pages, v_pages
                i_attn += 1
            if c is not None:
                counts.append(c)
        x = RMSNorm(cfg.norm_eps, name="embedding_norm")(x)
        if logits_at is not None:
            x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
        with jax.named_scope("lm_head"):    # the token table, tied
            logits = jnp.einsum("bsd,vd->bsv", x.astype(dt_), embed,
                                preferred_element_type=jnp.float32)
        if not served:
            return logits
        counts = jnp.stack(counts) if counts \
            else jnp.zeros((0, 0), jnp.int32)
        return logits, dict(cache, conv_tail=pool), counts
