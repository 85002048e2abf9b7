"""Kimi-Linear family (flax linen): KDA linear-attention layers and
latent-attention (MLA) layers in one stack, routed SwiGLU experts.

Source: moonshotai/Kimi-Linear-48B-A3B-Instruct ``config.json``. Three
kinds of block in one stack, pre-norm RMSNorm with a residual round the
mixer and round the feed-forward. No position encoding anywhere in this
family (``mla_use_nope``: the KDA layers carry order in their state);
the MLA mixer is ``models/mla.py``'s, shared with ``models/kimi_k2.py``,
which rotates its ``rope`` values and has a low-rank query, used here
without either:

  KDA + dense SwiGLU     the ``first_k_dense`` leading layers
  KDA + routed experts   ``kda_layers`` (1-based, as the source counts)
  MLA + routed experts   ``full_attn_layers``

One module serves both forms. The training form, ``model(ids)``, is a
full forward over whole sequences (chunkwise KDA from a zero state,
causal MLA over the sequence's own latents). The served form,
``model(ids, cache=..., seq_lengths=..., valid=...)``, is one
incremental step over what the model caches, which ``cache_spec`` states
for the adapter (serve/llm/model_runner.py):

  pages   one pool ``kv_pages`` [n_mla, P, bs, row] of latent rows
          (kv_lora_rank + rope values in a row of whole lanes: 576 in
          640), for the MLA layers only (no V pool)
  state   a slot a running sequence for the KDA layers:
          ``kda_state`` [n_kda, slots, H, dk, dv] float32 and
          ``kda_conv`` [n_kda, slots, (K-1) * 3*H*dk], the last K-1
          pre-convolution rows of q, k, v, one after the other

Weights are stored and multiplied in ``dtype`` (bfloat16 as served);
norms, the router and the KDA state are float32. ``experts_held`` says
which of the ``num_experts`` routed experts this chip holds and
``vocab_size`` is the slice of the vocabulary it holds: the cut the
model-configs guide describes, made in the configuration and never in
this file.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.mla import MLAMixer, RMSNorm, dense as _dense, \
    lanes as _lanes
from ray_tpu.ops import linear_attention as LA
from ray_tpu.parallel.moe import RoutedExperts, SwiGLU


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    num_hidden_layers: int = 27
    kda_layers: Tuple[int, ...] = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15,
                                   17, 18, 19, 21, 22, 23, 25, 26)
    full_attn_layers: Tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    # KDA (linear_attn_config)
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_gate_rank: int = 128        # low-rank decay and output gates
    kda_chunk: int = 64
    # MLA
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64      # carried without rotation: mla_use_nope
    q_lora_rank: Optional[int] = None       # the source's null: one q matrix
    rope = None                     # mla_use_nope (models/mla.py)
    v_head_dim: int = 128
    # feed-forward
    intermediate_size: int = 9216
    first_k_dense_replace: int = 1
    moe_intermediate_size: int = 1024
    num_experts: int = 256                  # the router's width
    experts_held: Optional[Tuple[int, int]] = None   # (first, count) here
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    moe_renormalize: bool = True
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 4096         # what a served sequence may reach
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        # a configuration file gives lists
        for key in ("kda_layers", "full_attn_layers", "experts_held"):
            value = getattr(self, key)
            if isinstance(value, list):
                object.__setattr__(self, key, tuple(value))
        kinds = self.layer_kinds()
        if len(kinds) != self.num_hidden_layers:
            raise ValueError("kda_layers and full_attn_layers must name "
                             "each of the layers once")

    def layer_kinds(self) -> Tuple[str, ...]:
        """``kda`` or ``mla`` for layers 1 .. num_hidden_layers, cut to
        the depth that is held."""
        n = self.num_hidden_layers
        kda = {i for i in self.kda_layers if i <= n}
        mla = {i for i in self.full_attn_layers if i <= n}
        if kda & mla or (kda | mla) != set(range(1, n + 1)):
            return ()
        return tuple("kda" if i in kda else "mla" for i in range(1, n + 1))

    @property
    def n_layers(self) -> int:
        return self.num_hidden_layers

    @classmethod
    def tiny(cls, vocab_size: int = 512, **kw):       # tests
        base = dict(
            vocab_size=vocab_size, hidden_size=64, num_hidden_layers=4,
            kda_layers=(1, 2, 3), full_attn_layers=(4,), kda_num_heads=2,
            kda_head_dim=16, kda_gate_rank=16, kda_chunk=64,
            num_attention_heads=2, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
            moe_intermediate_size=32, num_experts=16, experts_held=(0, 4),
            num_experts_per_token=4, max_seq_len=256, dtype=jnp.float32)
        base.update(kw)
        return cls(**base)


def cache_spec(cfg: KimiLinearConfig) -> Dict[str, Any]:
    """What a served sequence keeps between steps, for the adapter."""
    kinds = cfg.layer_kinds()
    H, d = cfg.kda_num_heads, cfg.kda_head_dim
    n_kda = kinds.count("kda")
    held = cfg.experts_held[1] if cfg.experts_held else cfg.num_experts
    return {
        # the step's per-expert token counts: [routed layers, held]
        "expert_counts": (max(len(kinds) - cfg.first_k_dense_replace, 0),
                          held),
        # ``moe.expert_product``'s arguments beside a step's tokens
        "routed_experts": (cfg.num_experts_per_token, cfg.num_experts,
                           held, cfg.hidden_size, jnp.dtype(cfg.dtype).itemsize),
        "pages": {"kv_pages": {
            "layers": kinds.count("mla"),
            "row": _lanes(cfg.kv_lora_rank + cfg.qk_rope_head_dim),
            "latent_rank": cfg.kv_lora_rank, "dtype": cfg.dtype}},
        "state": {
            # (``recurrence``: a decode step runs ``LA.kda_decode_path``'s
            # choice over this array)
            "kda_state": {"shape": (n_kda, H, d, d), "dtype": jnp.float32,
                          "recurrence": "kda"},
            "kda_conv": {"shape": (n_kda, (cfg.short_conv_kernel_size - 1)
                                   * 3 * H * d), "dtype": cfg.dtype}},
    }


class KDAMixer(nn.Module):
    config: KimiLinearConfig

    @nn.compact
    def __call__(self, x, state=None, conv_tail=None, valid=None,
                 pool=None):
        """x [B, S, D]; state [B, H, dk, dv] and conv_tail [B, K-1,
        3*H*dk] are what the rows' sequences carried here (None: the
        start of a sequence). Returns (y, new state, new tail). A served
        decode step (one token a row) hands ``pool = (kda_state, layer,
        slots)`` in ``state``'s place, ``LA.kda_decode_step``'s
        arguments, and gets the pool back in the new state's place."""
        cfg = self.config
        B, S, D = x.shape
        H, d, K = cfg.kda_num_heads, cfg.kda_head_dim, \
            cfg.short_conv_kernel_size
        r, dt = cfg.kda_gate_rank, cfg.dtype
        f32 = jnp.float32
        if state is None and pool is None:
            state = jnp.zeros((B, H, d, d), f32)
            conv_tail = jnp.zeros((B, K - 1, 3 * H * d), dt)
        xb = x.astype(dt)
        qkv = xb @ _dense(self, "qkv_proj", (D, 3 * H * d), dt)
        with jax.named_scope("kda/conv"):
            n_new = None if valid is None else \
                jnp.sum(valid.astype(jnp.int32), axis=1)
            qkv, new_tail = LA.short_conv(
                qkv, conv_tail, _dense(self, "qkv_conv", (K, 3 * H * d), dt,
                                       std=0.5), n_new)
            qkv = nn.silu(qkv.astype(f32))
        q, k, v = (t.reshape(B, S, H, d) for t in jnp.split(qkv, 3, axis=-1))

        def unit(t):
            return t * jax.lax.rsqrt(
                jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
        q, k = unit(q) * d ** -0.5, unit(k)
        a_log = self.param(
            "A_log", lambda key, shape: jnp.log(jax.random.uniform(
                key, shape, f32, 1.0, 16.0)), (H,))
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (H * d,), f32)
        f = (xb @ _dense(self, "f_a", (D, r), dt)) \
            @ _dense(self, "f_b", (r, H * d), dt)
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            f.astype(f32) + dt_bias).reshape(B, S, H, d)
        beta = jax.nn.sigmoid(
            (xb @ _dense(self, "b_proj", (D, H), dt)).astype(f32))
        if valid is not None:       # an empty position leaves the state
            g = jnp.where(valid[..., None, None], g, 0.0)
            beta = jnp.where(valid[..., None], beta, 0.0)
        if S == 1:
            one = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
            with jax.named_scope("kda/recurrence"):
                o, new_state = LA.kda_recurrent_step(*one, state) \
                    if pool is None else LA.kda_decode_step(*one, *pool)
                o = o[:, None]
        else:
            with jax.named_scope("kda/chunk"):
                o, new_state = LA.kda_chunked(q, k, v, g, beta, state,
                                              chunk=cfg.kda_chunk)
        gate = jax.nn.sigmoid(((xb @ _dense(self, "g_a", (D, r), dt))
                               @ _dense(self, "g_b", (r, H * d), dt)
                               ).astype(f32))
        o = RMSNorm(cfg.rms_norm_eps, name="o_norm")(o)
        o = (o.reshape(B, S, H * d) * gate).astype(dt)
        return (jnp.matmul(o, _dense(self, "o_proj", (H * d, D), dt),
                           preferred_element_type=f32), new_state, new_tail)


class KimiBlock(nn.Module):
    config: KimiLinearConfig
    kind: str               # kda | mla
    routed: bool

    @nn.compact
    def __call__(self, x, mixer_kwargs):
        cfg = self.config
        h = RMSNorm(cfg.rms_norm_eps, name="attn_norm")(x)
        if self.kind == "kda":
            with jax.named_scope("kda"):
                y, *carried = KDAMixer(cfg, name="kda")(h, **mixer_kwargs)
        else:
            with jax.named_scope("mla"):
                y, *carried = MLAMixer(cfg, name="mla")(h, **mixer_kwargs)
        x = x + y.astype(x.dtype)
        h = RMSNorm(cfg.rms_norm_eps, name="ffn_norm")(x)
        if self.routed:
            y, counts = RoutedExperts(
                cfg.num_experts, cfg.moe_intermediate_size,
                cfg.num_experts_per_token, held=cfg.experts_held,
                scaling=cfg.routed_scaling_factor,
                renormalize=cfg.moe_renormalize,
                shared_d_ff=cfg.num_shared_experts
                * cfg.moe_intermediate_size, dtype=cfg.dtype, name="moe")(
                    h, valid=mixer_kwargs.get("valid"))
        else:
            with jax.named_scope("mlp"):
                y = SwiGLU(cfg.intermediate_size, cfg.dtype, name="mlp")(h)
            counts = None
        return x + y.astype(x.dtype), carried, counts


class KimiLinearModel(nn.Module):
    config: KimiLinearConfig

    @nn.compact
    def __call__(self, input_ids, cache=None, seq_lengths=None, valid=None,
                 logits_at=None):
        """Logits [B, S, V] of a full forward; or, with ``cache``, one
        incremental step: ``cache`` is ``{"kv_pages", "block_tables",
        "kda_state", "kda_conv", "slots"}`` (``cache_spec``; ``slots``
        [B] is each row's state slot; without it row r IS slot r + 1,
        which a full decode batch uses: the state is then read and
        written where it lies, with no gather and no scatter),
        ``seq_lengths`` [B] the tokens cached before this call, ``valid`` [B, S] the real tokens of a
        padded bucket. Returns ``(logits, new cache, expert_counts)``,
        expert_counts [routed layers, experts held] int32. ``logits_at``
        ([B] int) keeps one position a row before the head."""
        cfg = self.config
        dt = cfg.dtype
        B, S = input_ids.shape
        embed = self.param("embed", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.hidden_size), dt)
        # the residual stream is float32: the blocks' sums are not rounded
        # to bfloat16 between layers (which moved a served token's logit
        # by up to 0.75 against the float32 reference: my chip runs, PR
        # 28); every product still takes bfloat16 operands
        x = embed[input_ids].astype(jnp.float32)
        served = cache is not None
        if served:
            pages, state, conv = (cache["kv_pages"], cache["kda_state"],
                                  cache["kda_conv"])
            # each row's state slot; or rows in slot order (row r is
            # slot r + 1), a contiguous slice of the arrays
            slots = cache.get("slots", slice(1, 1 + B))
        i_kda = i_mla = 0
        counts = []
        for i, kind in enumerate(cfg.layer_kinds()):
            kw: Dict[str, Any] = {"valid": valid}
            # reading and writing the rows' state belongs to the recurrence
            # (its roofline share counts the state in and out once: a
            # write outside the scope read 161% of it, my chip run, PR 28).
            # A decode step hands the mixer the pool: its recurrence
            # (``LA.kda_decode_step``, under the mixer's own
            # ``kda/recurrence``) reads and writes the rows' slots itself.
            if served and kind == "kda":
                if S == 1:
                    kw.update(pool=(state, i_kda, cache.get("slots")))
                else:
                    with jax.named_scope("kda/chunk"):
                        kw.update(state=state[i_kda, slots])
                kw.update(conv_tail=conv[i_kda, slots].reshape(
                    B, cfg.short_conv_kernel_size - 1, -1))
            elif served:
                kw.update(pages=pages, block_tables=cache["block_tables"],
                          seq_lengths=seq_lengths, layer=i_mla)
            x, carried, c = KimiBlock(
                cfg, kind, routed=i >= cfg.first_k_dense_replace,
                name=f"layers_{i}")(x, kw)
            if c is not None:
                counts.append(c)
            if kind == "kda":
                if served:
                    if S == 1:
                        state = carried[0]
                    else:
                        with jax.named_scope("kda/chunk"):
                            state = state.at[i_kda, slots].set(carried[0])
                    conv = conv.at[i_kda, slots].set(
                        carried[1].astype(conv.dtype).reshape(B, -1))
                i_kda += 1
            else:
                if served:
                    pages = carried[0]
                i_mla += 1
        x = RMSNorm(cfg.rms_norm_eps, name="final_norm")(x)
        if logits_at is not None:
            x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
        with jax.named_scope("lm_head"):
            logits = jnp.matmul(
                x.astype(dt), _dense(self, "lm_head", (cfg.hidden_size,
                                                       cfg.vocab_size), dt),
                preferred_element_type=jnp.float32)
        if not served:
            return logits
        counts = jnp.stack(counts) if counts else jnp.zeros((0, 0), jnp.int32)
        return logits, dict(cache, kv_pages=pages, kda_state=state,
                            kda_conv=conv), counts
