"""Llama-family decoder (flax linen), TPU-first.

The modern-decoder counterpart to models/gpt2.py (reference analogue:
the reference serves this family through HF inside its Train workers —
train/huggingface/huggingface_trainer.py; there is no in-tree CUDA
Llama, so this module is the TPU-native implementation of the same
capability):

  - RMSNorm (f32 accumulation), rotary position embeddings, SwiGLU MLP,
    grouped-query attention (n_kv_heads <= n_heads), no biases
  - bfloat16 activations, f32 params; attention backend selectable:
    "flash" (pallas), "ring" (sp-axis ring attention for long
    context), "reference"
  - weight layouts follow the MeshSpec tp rules (fused qkv shards the
    head dim, out/down projections shard the input dim) like gpt2.py
  - HF Llama checkpoint import via transformers when weights are local
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_seq_len: int = 2048
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32          # < n_heads => grouped-query attention
    ffn_hidden: Optional[int] = None  # default: SwiGLU 8/3 * dim rounded
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention_backend: str = "flash"  # flash | ring | reference
    ring_axis: str = "sp"

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def ffn_dim(self) -> int:
        if self.ffn_hidden is not None:
            return self.ffn_hidden
        # llama-2 sizing: 2/3 * 4d, rounded up to a multiple of 256
        h = int(2 * (4 * self.dim) / 3)
        return (h + 255) // 256 * 256

    @classmethod
    def llama2_7b(cls):
        return cls()

    @classmethod
    def llama2_13b(cls):
        return cls(dim=5120, n_layers=40, n_heads=40, n_kv_heads=40)

    @classmethod
    def tiny(cls, vocab_size: int = 512):  # tests: GQA exercised
        return cls(vocab_size=vocab_size, max_seq_len=256, dim=128,
                   n_layers=2, n_heads=8, n_kv_heads=2,
                   dtype=jnp.float32, attention_backend="reference")


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        # f32 accumulation regardless of activation dtype
        scale = self.param("weight", nn.initializers.ones, (x.shape[-1],))
        xf = x.astype(jnp.float32)
        normed = xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps)
        return (normed * scale).astype(x.dtype)


def rope_frequencies(head_dim: int, max_seq_len: int,
                     theta: float) -> jnp.ndarray:
    """[S, D/2] complex rotation angles, precomputed once per model."""
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    t = np.arange(max_seq_len)
    freqs = np.outer(t, inv)                    # [S, D/2]
    return jnp.asarray(np.stack([np.cos(freqs), np.sin(freqs)], -1),
                       jnp.float32)             # [S, D/2, 2]


def apply_rope(x: jnp.ndarray, freqs: jnp.ndarray,
               positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """x: [B,H,S,D]; rotate (first-half, second-half) feature pairs by
    position angle — the rotate-half convention HF Llama checkpoints
    are permuted for, so imported weights work unmodified.

    ``positions`` ([B, S] int32, optional) gives each token its
    absolute position explicitly — incremental decode rotates the new
    tokens by their true offsets instead of 0..S-1."""
    B, H, S, D = x.shape
    if positions is None:
        cos = freqs[:S, :, 0][None, None]       # [1,1,S,D/2]
        sin = freqs[:S, :, 1][None, None]
    else:
        per = jnp.take(freqs, positions, axis=0)  # [B,S,D/2,2]
        cos = per[..., 0][:, None]              # [B,1,S,D/2]
        sin = per[..., 1][:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    r1 = xf1 * cos - xf2 * sin
    r2 = xf2 * cos + xf1 * sin
    return jnp.concatenate([r1, r2], axis=-1).astype(x.dtype)


class LlamaAttention(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, freqs, kv_cache=None, seq_lengths=None,
                 valid=None, positions=None, layer=None):
        cfg = self.config
        B, S, E = x.shape
        hd = cfg.head_dim
        q = nn.Dense(cfg.n_heads * hd, use_bias=False, dtype=cfg.dtype,
                     name="wq")(x)
        k = nn.Dense(cfg.n_kv_heads * hd, use_bias=False, dtype=cfg.dtype,
                     name="wk")(x)
        v = nn.Dense(cfg.n_kv_heads * hd, use_bias=False, dtype=cfg.dtype,
                     name="wv")(x)
        q = q.reshape(B, S, cfg.n_heads, hd).transpose(0, 2, 1, 3)
        k = k.reshape(B, S, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
        v = v.reshape(B, S, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
        q = apply_rope(q, freqs, positions)
        k = apply_rope(k, freqs, positions)
        if kv_cache is not None:
            # incremental decode: cache holds the UN-replicated kv
            # heads (GQA broadcast happens inside decode_attention)
            from ray_tpu.ops.attention import cached_attention
            y, new_cache = cached_attention(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), kv_cache, seq_lengths,
                valid=valid, layer=layer)
            y = y.reshape(B, S, cfg.n_heads * hd)
            return (nn.Dense(cfg.dim, use_bias=False, dtype=cfg.dtype,
                             name="wo")(y), new_cache)
        if cfg.n_kv_heads != cfg.n_heads:
            # grouped-query: broadcast each kv head over its query group
            rep = cfg.n_heads // cfg.n_kv_heads
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        if cfg.attention_backend == "ring":
            from ray_tpu.ops.ring_attention import ring_attention
            y = ring_attention(q, k, v, axis_name=cfg.ring_axis,
                               causal=True)
        elif cfg.attention_backend == "flash":
            from ray_tpu.ops.attention import flash_attention
            y = flash_attention(q, k, v, causal=True)
        else:
            from ray_tpu.ops.attention import attention_reference
            y = attention_reference(q, k, v, causal=True)
        y = y.transpose(0, 2, 1, 3).reshape(B, S, cfg.n_heads * hd)
        return nn.Dense(cfg.dim, use_bias=False, dtype=cfg.dtype,
                        name="wo")(y)


class LlamaMLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        gate = nn.Dense(cfg.ffn_dim, use_bias=False, dtype=cfg.dtype,
                        name="w1")(x)
        up = nn.Dense(cfg.ffn_dim, use_bias=False, dtype=cfg.dtype,
                      name="w3")(x)
        return nn.Dense(cfg.dim, use_bias=False, dtype=cfg.dtype,
                        name="w2")(nn.silu(gate) * up)


class LlamaBlock(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, freqs, kv_cache=None, seq_lengths=None,
                 valid=None, positions=None, layer=None):
        cfg = self.config
        if kv_cache is not None:
            y, new_cache = LlamaAttention(cfg, name="attention")(
                RMSNorm(cfg.norm_eps, name="attention_norm")(x), freqs,
                kv_cache=kv_cache, seq_lengths=seq_lengths,
                valid=valid, positions=positions, layer=layer)
            x = x + y
            x = x + LlamaMLP(cfg, name="feed_forward")(
                RMSNorm(cfg.norm_eps, name="ffn_norm")(x))
            return x, new_cache
        x = x + LlamaAttention(cfg, name="attention")(
            RMSNorm(cfg.norm_eps, name="attention_norm")(x), freqs)
        x = x + LlamaMLP(cfg, name="feed_forward")(
            RMSNorm(cfg.norm_eps, name="ffn_norm")(x))
        return x


class LlamaModel(nn.Module):
    """Decoder LM: tokens -> logits (f32)."""
    config: LlamaConfig
    # serving: the blocks' parameters stacked on a leading layer axis
    # under "layers" and ONE block's program looped over it (see GPT2)
    stacked: bool = False

    @nn.compact
    def __call__(self, input_ids, kv_cache=None, seq_lengths=None,
                 valid=None):
        """Full forward — or, with ``kv_cache``, one incremental step
        (prefill at ``seq_lengths == 0``, then single-token decodes):
        tokens are appended to the caches (a list of per-layer caches,
        or the serving pool: one dict for all layers) and rotated by
        their TRUE absolute positions; returns ``(logits, new_cache)``.
        ``valid`` marks real tokens when S is padded to a bucket."""
        cfg = self.config
        x = nn.Embed(cfg.vocab_size, cfg.dim,
                     dtype=cfg.dtype, name="tok_embeddings")(input_ids)
        freqs = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                 cfg.rope_theta)
        incremental = kv_cache is not None
        positions = None
        if incremental:
            S = input_ids.shape[1]
            positions = seq_lengths[:, None] + jnp.arange(S)[None, :]
            if valid is not None:
                positions = jnp.where(valid, positions, 0)

        def block(h, carry, i):
            x, cache = carry
            if incremental:
                return h(x, freqs, kv_cache=cache, seq_lengths=seq_lengths,
                         valid=valid, positions=positions, layer=i), None
            return (h(x, freqs), None), None

        if self.stacked:
            (x, kv_cache), _ = nn.scan(
                block, variable_axes={"params": 0},
                split_rngs={"params": True})(
                    LlamaBlock(cfg, name="layers"), (x, kv_cache),
                    jnp.arange(cfg.n_layers))
        else:
            for i in range(cfg.n_layers):
                (x, kv_cache), _ = block(
                    LlamaBlock(cfg, name=f"layers_{i}"), (x, kv_cache), i)
        x = RMSNorm(cfg.norm_eps, name="norm")(x)
        logits = nn.Dense(cfg.vocab_size, use_bias=False,
                          dtype=jnp.float32, name="output")(x)
        return (logits, kv_cache) if incremental else logits


def init_kv_cache(cfg: LlamaConfig, batch_size: int, max_len: int):
    """Per-layer contiguous KV caches ([B, S_max, Hkv, D] token-major,
    GQA: the un-replicated kv heads) for incremental decode."""
    shape = (batch_size, max_len, cfg.n_kv_heads, cfg.head_dim)
    return [{"k": jnp.zeros(shape, cfg.dtype),
             "v": jnp.zeros(shape, cfg.dtype)}
            for _ in range(cfg.n_layers)]


def causal_lm_loss(logits, input_ids):
    """Next-token cross-entropy (f32), mean over B*(S-1)."""
    targets = input_ids[:, 1:]
    logits = logits[:, :-1].astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return jnp.mean(nll)


def import_hf_llama(model_name_or_path: str, cfg: LlamaConfig):
    """Map a HF LlamaForCausalLM state dict onto this module's params
    (gated on transformers + local weights; mirrors
    models/gpt2.py's HF import)."""
    import torch  # noqa: F401 — transformers loads via torch
    from transformers import LlamaForCausalLM
    hf = LlamaForCausalLM.from_pretrained(model_name_or_path)
    sd = {k: v.detach().cpu().numpy() for k, v in hf.state_dict().items()}

    def dense(w):  # torch [out,in] -> flax kernel [in,out]
        return jnp.asarray(w.T)

    params = {"tok_embeddings": {
        "embedding": jnp.asarray(sd["model.embed_tokens.weight"])}}
    for i in range(cfg.n_layers):
        p = f"model.layers.{i}."
        params[f"layers_{i}"] = {
            "attention_norm": {
                "weight": jnp.asarray(sd[p + "input_layernorm.weight"])},
            "ffn_norm": {
                "weight": jnp.asarray(
                    sd[p + "post_attention_layernorm.weight"])},
            "attention": {
                "wq": {"kernel": dense(sd[p + "self_attn.q_proj.weight"])},
                "wk": {"kernel": dense(sd[p + "self_attn.k_proj.weight"])},
                "wv": {"kernel": dense(sd[p + "self_attn.v_proj.weight"])},
                "wo": {"kernel": dense(sd[p + "self_attn.o_proj.weight"])},
            },
            "feed_forward": {
                "w1": {"kernel": dense(sd[p + "mlp.gate_proj.weight"])},
                "w3": {"kernel": dense(sd[p + "mlp.up_proj.weight"])},
                "w2": {"kernel": dense(sd[p + "mlp.down_proj.weight"])},
            },
        }
    params["norm"] = {"weight": jnp.asarray(sd["model.norm.weight"])}
    params["output"] = {"kernel": dense(sd["lm_head.weight"])}
    return {"params": params}
