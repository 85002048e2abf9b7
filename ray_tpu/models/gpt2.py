"""GPT-2 family (flax linen), TPU-first with mesh-aware attention.

Benchmark parity target: the reference's HF GPT-2 fine-tune config
(reference: train/huggingface/huggingface_trainer.py + BASELINE.json
"HF GPT-2 causal-LM fine-tune"). Native flax implementation:

  - bfloat16 activations, f32 params/softmax accumulation
  - attention backend selectable: "flash" (pallas kernel on TPU),
    "ring" (sp-axis ring attention for long context), "reference"
  - weights laid out for the MeshSpec tp rules (qkv fused kernel shards on
    the head dim; out-projection shards the input dim — mesh.py _tp_hint)
  - HF GPT-2 checkpoint import (transformers is in-image) for fine-tune parity
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    attention_backend: str = "flash"  # flash | ring | reference
    ring_axis: str = "sp"

    @classmethod
    def small(cls):  # gpt2 124M
        return cls()

    @classmethod
    def medium(cls):
        return cls(n_embd=1024, n_layer=24, n_head=16)

    @classmethod
    def large(cls):
        return cls(n_embd=1280, n_layer=36, n_head=20)

    @classmethod
    def tiny(cls, vocab_size: int = 512):  # tests
        return cls(vocab_size=vocab_size, n_positions=256, n_embd=128,
                   n_layer=2, n_head=4, dtype=jnp.float32,
                   attention_backend="reference")


class StackedDense(nn.Module):
    """``nn.Dense`` of one layer of a stack: the served form's matrices
    stay whole, ``kernel`` [layers, K, features] and ``bias`` [layers,
    features] float32 as stored, and a block's product names its layer
    (``ops.linear.stacked_linear``: on the chip a kernel that reads the
    layer's tiles where they lie and rounds them in VMEM)."""
    features: int
    layers: int
    dtype: Any

    @nn.compact
    def __call__(self, x, layer):
        from ray_tpu.ops.linear import stacked_linear
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(batch_axis=(0,)),
            (self.layers, x.shape[-1], self.features))
        bias = self.param("bias", nn.initializers.zeros,
                          (self.layers, self.features))
        return stacked_linear(x.astype(self.dtype), kernel, bias, layer)


def linear_path(cfg: "GPT2Config", params, rows: int) -> str:
    """What the products of ``GPT2(cfg, stacked=True)`` run for a step of
    ``rows`` rows over ``params``: ``ops.linear.stacked_linear_path``, the
    question ``StackedDense`` asks while the step is traced, put to every
    stack of the blocks with the rows it will be handed (for the adapter's
    dispatch span). ``"kernel"`` or ``"xla"`` where a block's products
    agree, else ``"mixed"``."""
    from ray_tpu.ops import linear
    said = {linear.stacked_linear_path(
                jax.ShapeDtypeStruct((rows, w.shape[1]), cfg.dtype), w)
            for path, w in jax.tree_util.tree_leaves_with_path(
                params["params"]["h"]) if path[-1].key == "kernel"}
    return said.pop() if len(said) == 1 else "mixed"


class StackedLayerNorm(nn.Module):
    """``nn.LayerNorm(dtype=float32)`` of one layer of a stack: ``scale``
    and ``bias`` [layers, E], the layer's rows sliced."""
    layers: int

    @nn.compact
    def __call__(self, x, layer):
        shape = (self.layers, x.shape[-1])
        scale = self.param("scale", nn.initializers.ones, shape)
        bias = self.param("bias", nn.initializers.zeros, shape)
        y = nn.LayerNorm(dtype=jnp.float32, use_scale=False,
                         use_bias=False)(x)
        return y * scale[layer] + bias[layer]


def _dense(cfg, features: int, name: str, layers, x, layer):
    """A block's product: ``nn.Dense``, or (``layers``: the served form)
    layer ``layer`` of the stack."""
    if layers is None:
        return nn.Dense(features, dtype=cfg.dtype, name=name)(x)
    return StackedDense(features, layers, cfg.dtype, name=name)(x, layer)


def _layer_norm(name: str, layers, x, layer):
    if layers is None:
        return nn.LayerNorm(dtype=jnp.float32, name=name)(x)
    return StackedLayerNorm(layers, name=name)(x, layer)


class CausalSelfAttention(nn.Module):
    config: GPT2Config
    layers: Optional[int] = None    # the served form: the stack's depth

    @nn.compact
    def __call__(self, x, deterministic: bool = True, kv_cache=None,
                 seq_lengths=None, valid=None, layer=None):
        cfg = self.config
        B, S, E = x.shape
        head_dim = cfg.n_embd // cfg.n_head
        qkv = _dense(cfg, 3 * cfg.n_embd, "c_attn", self.layers, x, layer)

        def heads(t):  # [B,S,E] -> [B,H,S,D]
            return t.reshape(B, S, cfg.n_head, head_dim).transpose(0, 2, 1, 3)

        if kv_cache is not None:
            # incremental decode (docs/LLM_SERVING.md): append this
            # call's kv into the cache (contiguous or paged) and attend
            # the S new queries against the whole cached prefix
            from ray_tpu.ops.attention import cached_attention
            tok = lambda t: t.reshape(B, S, cfg.n_head, head_dim)  # noqa: E731
            q, k, v = jnp.split(qkv, 3, axis=-1)
            y, new_cache = cached_attention(
                tok(q), tok(k), tok(v), kv_cache, seq_lengths,
                valid=valid, layer=layer)
            y = y.reshape(B, S, E)
            y = _dense(cfg, cfg.n_embd, "c_proj", self.layers, y, layer)
            return (nn.Dropout(cfg.dropout)(y, deterministic),
                    new_cache)
        if cfg.attention_backend == "flash":
            # the kernels cut their blocks from ``qkv`` where it lies and
            # write [B, S, E] (or, where the shapes say so, lay the heads
            # out as below: ops/attention.py:packed_heads)
            from ray_tpu.ops.attention import flash_attention_packed
            y = flash_attention_packed(qkv, cfg.n_head, causal=True)
        else:
            q, k, v = (heads(t) for t in jnp.split(qkv, 3, axis=-1))
            if cfg.attention_backend == "ring":
                from ray_tpu.ops.ring_attention import ring_attention
                y = ring_attention(q, k, v, axis_name=cfg.ring_axis,
                                   causal=True)
            else:
                from ray_tpu.ops.attention import attention_reference
                y = attention_reference(q, k, v, causal=True)
            y = y.transpose(0, 2, 1, 3).reshape(B, S, E)
        y = _dense(cfg, cfg.n_embd, "c_proj", self.layers, y, layer)
        y = nn.Dropout(cfg.dropout)(y, deterministic=deterministic)
        return y


class MLP(nn.Module):
    config: GPT2Config
    layers: Optional[int] = None

    @nn.compact
    def __call__(self, x, deterministic: bool = True, layer=None):
        cfg = self.config
        h = _dense(cfg, 4 * cfg.n_embd, "c_fc", self.layers, x, layer)
        h = nn.gelu(h, approximate=True)
        h = _dense(cfg, cfg.n_embd, "c_proj", self.layers, h, layer)
        return nn.Dropout(cfg.dropout)(h, deterministic=deterministic)


class Block(nn.Module):
    config: GPT2Config
    # the served form: this many blocks' parameters on a leading layer
    # axis, and ``layer`` says which of them this call is
    layers: Optional[int] = None

    @nn.compact
    def __call__(self, x, deterministic: bool = True, kv_cache=None,
                 seq_lengths=None, valid=None, layer=None):
        cfg, n = self.config, self.layers
        if kv_cache is not None:
            y, new_cache = CausalSelfAttention(cfg, n, name="attn")(
                _layer_norm("ln_1", n, x, layer),
                deterministic, kv_cache=kv_cache,
                seq_lengths=seq_lengths, valid=valid, layer=layer)
            x = x + y
            x = x + MLP(cfg, n, name="mlp")(
                _layer_norm("ln_2", n, x, layer), deterministic, layer)
            return x, new_cache
        x = x + CausalSelfAttention(cfg, n, name="attn")(
            _layer_norm("ln_1", n, x, layer), deterministic, layer=layer)
        x = x + MLP(cfg, n, name="mlp")(
            _layer_norm("ln_2", n, x, layer), deterministic, layer)
        return x


class GPT2(nn.Module):
    config: GPT2Config
    # serving: the blocks' parameters stacked on a leading layer axis
    # under "h" (not "h_0" .. "h_{L-1}") and ONE block's program looped
    # over it, so compile time and program size do not grow with depth
    stacked: bool = False

    @nn.compact
    def __call__(self, input_ids, deterministic: bool = True,
                 positions: Optional[jnp.ndarray] = None,
                 kv_cache=None, seq_lengths=None, valid=None):
        """Full forward (logits) — or, with ``kv_cache``, one
        incremental step: the S tokens of ``input_ids`` are appended to
        the caches (``init_kv_cache``'s list of per-layer caches, or
        the serve LLM engine's paged pool, one dict for all layers:
        ``ops.attention.cached_attention`` takes either with the layer's
        index) holding ``seq_lengths`` prior tokens, and the return
        value is ``(logits, new_kv_cache)``. Prefill is the
        ``seq_lengths == 0`` case; decode passes one token at a time.
        ``valid`` marks real tokens when S is padded to a bucket."""
        cfg = self.config
        B, S = input_ids.shape
        incremental = kv_cache is not None
        if positions is None:
            if incremental:
                positions = seq_lengths[:, None] + jnp.arange(S)[None, :]
                if valid is not None:
                    positions = jnp.where(valid, positions, 0)
            else:
                positions = jnp.arange(S)[None, :]
        wte = nn.Embed(cfg.vocab_size, cfg.n_embd,
                       dtype=cfg.dtype, name="wte")
        wpe = nn.Embed(cfg.n_positions, cfg.n_embd,
                       dtype=cfg.dtype, name="wpe")
        if self.stacked:
            # the rows taken from the tables as stored, and only they cast
            # (``nn.Embed`` casts the table: 50,257 rows to look up a few)
            x = wte.embedding[input_ids].astype(cfg.dtype) \
                + wpe.embedding[positions].astype(cfg.dtype)
        else:
            x = wte(input_ids) + wpe(positions)
        x = nn.Dropout(cfg.dropout)(x, deterministic=deterministic)

        def block(h, carry, i):
            x, cache = carry
            if incremental:
                return h(x, deterministic, kv_cache=cache,
                         seq_lengths=seq_lengths, valid=valid, layer=i), None
            return (h(x, deterministic, layer=i), None), None

        if self.stacked:
            # the stacks go to the loop WHOLE and every product names its
            # layer: over the loop's own slice of a stack the compiler
            # would cast all of it ahead of the loop (``ops.linear``)
            (x, kv_cache), _ = nn.scan(
                block, variable_broadcast="params",
                split_rngs={"params": False})(
                    Block(cfg, cfg.n_layer, name="h"), (x, kv_cache),
                    jnp.arange(cfg.n_layer))
        else:
            for i in range(cfg.n_layer):
                (x, kv_cache), _ = block(
                    Block(cfg, name=f"h_{i}"), (x, kv_cache), i)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_f")(x)
        # weight-tied LM head
        with jax.named_scope("lm_head"):
            logits = wte.attend(x.astype(jnp.float32))
        return (logits, kv_cache) if incremental else logits


def init_kv_cache(cfg: GPT2Config, batch_size: int, max_len: int):
    """Per-layer contiguous KV caches for incremental decode
    ([B, S_max, H, D] token-major — the layout ops.attention's cached
    paths share with the paged pool)."""
    hd = cfg.n_embd // cfg.n_head
    shape = (batch_size, max_len, cfg.n_head, hd)
    return [{"k": jnp.zeros(shape, cfg.dtype),
             "v": jnp.zeros(shape, cfg.dtype)}
            for _ in range(cfg.n_layer)]


@jax.named_scope("loss")
def causal_lm_loss(logits, labels, ignore_index: int = -100):
    """Next-token cross entropy; labels == input_ids shifted by the caller
    or equal to input_ids (then shifting happens here).

    Written as ``logsumexp - gathered_logit`` rather than
    ``take_along_axis(log_softmax(...))``: the latter materializes the
    full [B, S, V] log-probability array (3.3 GB/step at the GPT-2
    bench shape) only to gather one column per token, while reductions
    and gathers over the raw logits fuse without that round trip.  The
    exp-sum accumulates in f32 even for bf16 logits (bf16 accumulation
    over a 50k vocab loses the loss signal)."""
    shift_logits = logits[:, :-1]
    shift_labels = labels[:, 1:]
    mask = (shift_labels != ignore_index)
    safe = jnp.where(mask, shift_labels, 0)
    m = jax.lax.stop_gradient(jnp.max(shift_logits, axis=-1))
    sumexp = jnp.sum(
        jnp.exp((shift_logits - m[..., None]).astype(jnp.float32)),
        axis=-1)
    lse = m.astype(jnp.float32) + jnp.log(sumexp)
    ll = jnp.take_along_axis(shift_logits, safe[..., None],
                             axis=-1)[..., 0].astype(jnp.float32)
    nll = lse - ll
    total = jnp.sum(nll * mask)
    count = jnp.maximum(jnp.sum(mask), 1)
    return total / count


def load_hf_gpt2_params(model_name: str = "gpt2",
                        config: Optional[GPT2Config] = None):
    """Import HuggingFace GPT-2 weights into this module's param tree
    (fine-tune parity with the reference's HF trainer path)."""
    from transformers import GPT2LMHeadModel
    hf = GPT2LMHeadModel.from_pretrained(model_name)
    sd = {k: v.detach().numpy() for k, v in hf.state_dict().items()}
    cfg = config or GPT2Config()
    p: dict = {"wte": {"embedding": sd["transformer.wte.weight"]},
               "wpe": {"embedding": sd["transformer.wpe.weight"]},
               "ln_f": {"scale": sd["transformer.ln_f.weight"],
                        "bias": sd["transformer.ln_f.bias"]}}
    for i in range(cfg.n_layer):
        hfp = f"transformer.h.{i}."
        p[f"h_{i}"] = {
            "ln_1": {"scale": sd[hfp + "ln_1.weight"],
                     "bias": sd[hfp + "ln_1.bias"]},
            "ln_2": {"scale": sd[hfp + "ln_2.weight"],
                     "bias": sd[hfp + "ln_2.bias"]},
            "attn": {
                "c_attn": {"kernel": sd[hfp + "attn.c_attn.weight"],
                           "bias": sd[hfp + "attn.c_attn.bias"]},
                "c_proj": {"kernel": sd[hfp + "attn.c_proj.weight"],
                           "bias": sd[hfp + "attn.c_proj.bias"]},
            },
            "mlp": {
                "c_fc": {"kernel": sd[hfp + "mlp.c_fc.weight"],
                         "bias": sd[hfp + "mlp.c_fc.bias"]},
                "c_proj": {"kernel": sd[hfp + "mlp.c_proj.weight"],
                           "bias": sd[hfp + "mlp.c_proj.bias"]},
            },
        }
    return jax.tree_util.tree_map(jnp.asarray, {"params": p})
