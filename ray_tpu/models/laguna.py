"""Laguna family (flax linen): grouped-query attention whose head count,
rotary encoding and reach are a function of the layer's type (full
layers and sliding-window layers mixed), a sigmoid gate a head on the
attention's output, routed SwiGLU experts beside a shared one.

Source: poolside/Laguna-XS.2 ``config.json`` (``model_type`` ``laguna``).
Pre-norm RMSNorm with a residual round the attention and round the
feed-forward:

  h = x + Attn_l(RMSNorm(x))     ``H_l`` query heads of ``head_dim`` over
                                 ``num_key_value_heads`` key/value heads
                                 (``num_attention_heads_per_layer``); q and
                                 k rotated at the token's absolute position
                                 by the layer type's ``rope_parameters``
                                 (full layers: YaRN over the first
                                 ``partial_rotary_factor`` of a head, the
                                 rest untouched; sliding layers: plain
                                 rotary over the whole head); causal, and a
                                 sliding layer's token at ``p`` sees ``p -
                                 sliding_window + 1 .. p`` only; each head's
                                 output times ``sigmoid(x W_g)`` (``gating``:
                                 one value a head, from the normed input)
  y = h + FFN_l(RMSNorm(h))      ``mlp_layer_types``: a dense SwiGLU, or
                                 ``num_experts`` sigmoid-scored experts,
                                 ``num_experts_per_tok`` a token, beside a
                                 shared expert
                                 (parallel/moe.py:RoutedExperts)

One module serves both forms, as the Kimi models do. The training form,
``model(ids)``, is a full forward over whole sequences. The served form,
``model(ids, cache=..., seq_lengths=..., valid=...)``, is one incremental
step over what ``cache_spec`` states: K and V pools of the full layers
(``k_full`` / ``v_full``, every position kept, one table a sequence) and
of the sliding layers (``k_window`` / ``v_window``, ``window`` =
``sliding_window``: a ring of ``window / block_size + 1`` pages a
sequence, ``serve/llm/kv_cache.py``), rows of ``num_key_value_heads x
head_dim`` values, and no per-sequence state. A step of one token a row
attends over the pages where they lie (``ops.attention.
paged_attention_decode``, with ``window`` over the ring); a step of more
is a prompt from an empty cache, which attends over its own keys and
values in blocks (``ops.attention.prefill_attention``) and writes a
sliding layer's ring only the rows a later step can read. Which
attention runs follows from what the call can observe
(``paged_decode_path``, ``prefill_attention_path``).

Weights are stored and multiplied in ``dtype`` (bfloat16 as served);
norms, the router, the gate, rotary angles and the softmax are float32.

Device-trace scopes: ``attn_full/{qkv,norm,rope,write,attend,gate,out}``
(``norm``: a family with a query/key norm) and
``attn_window/{...}``, ``moe/router``, ``moe/experts``, ``moe/shared``,
``mlp``, ``lm_head``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.mla import RMSNorm, YarnRope, dense
from ray_tpu.ops import attention as A
from ray_tpu.parallel.moe import RoutedExperts, SwiGLU

FULL, SLIDING = "full_attention", "sliding_attention"

_ROPE_PARAMETERS = {
    FULL: {"rope_type": "yarn", "rope_theta": 500000.0, "factor": 64.0,
           "original_max_position_embeddings": 4096, "beta_fast": 64.0,
           "beta_slow": 1.0, "attention_factor": 1.4158883083359672,
           "partial_rotary_factor": 0.5},
    SLIDING: {"rope_type": "default", "rope_theta": 10000.0,
              "partial_rotary_factor": 1.0},
}


def _frozen(x):
    """Lists and dicts of a configuration file as hashable tuples."""
    if isinstance(x, dict):
        return tuple(sorted((k, _frozen(v)) for k, v in x.items()))
    if isinstance(x, (list, tuple)):
        return tuple(_frozen(v) for v in x)
    return x


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    # attention
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    num_attention_heads_per_layer: Optional[Tuple[int, ...]] = None
    layer_types: Optional[Tuple[str, ...]] = None   # None: F S S S, repeated
    sliding_window: int = 512
    gating: bool = True
    rope_parameters: Any = dataclasses.field(
        default_factory=lambda: _ROPE_PARAMETERS)
    # feed-forward
    intermediate_size: int = 8192
    mlp_layer_types: Optional[Tuple[str, ...]] = None  # None: dense, sparse..
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    moe_routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 4096         # what a served sequence may reach
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        # a configuration file gives lists and dicts; the object is hashed
        n = self.num_hidden_layers
        types = self.layer_types or tuple(
            FULL if i % 4 == 0 else SLIDING for i in range(n))
        heads = self.num_attention_heads_per_layer or tuple(
            self.num_attention_heads if t == FULL
            else self.num_attention_heads * 4 // 3 for t in types)
        mlps = self.mlp_layer_types or tuple(
            "dense" if i == 0 else "sparse" for i in range(n))
        for name, value in (("layer_types", types), ("mlp_layer_types", mlps),
                            ("num_attention_heads_per_layer", heads),
                            ("rope_parameters", self.rope_parameters)):
            value = _frozen(value)
            if name != "rope_parameters" and len(value) != n:
                raise ValueError(f"{name} names {len(value)} layers of {n}")
            object.__setattr__(self, name, value)

    @property
    def n_layers(self) -> int:
        return self.num_hidden_layers

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

    def rope_of(self, kind: str) -> "PartialRope":
        p = dict(dict(self.rope_parameters)[kind])
        dim = int(self.head_dim * p.get("partial_rotary_factor", 1.0))
        if p.get("rope_type", "default") == "yarn":
            blend = YarnRope(
                dim, float(p["rope_theta"]), float(p["factor"]),
                int(p["original_max_position_embeddings"]),
                float(p["beta_fast"]), float(p["beta_slow"]))
        else:
            blend = YarnRope(dim, float(p["rope_theta"]))
        return PartialRope(blend, float(p.get("attention_factor", 1.0)))

    @classmethod
    def tiny(cls, vocab_size: int = 512, **kw):       # tests
        """Two full and three sliding layers, a window shorter than the
        tests' prompts, the YaRN blend inside the 4 rotated pairs."""
        base = dict(
            vocab_size=vocab_size, hidden_size=64, num_hidden_layers=5,
            num_attention_heads=6, num_key_value_heads=2, head_dim=16,
            num_attention_heads_per_layer=(6, 8, 8, 8, 6),
            layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL),
            mlp_layer_types=("dense",) + ("sparse",) * 4,
            sliding_window=32,
            rope_parameters={
                FULL: {"rope_type": "yarn", "rope_theta": 100.0,
                       "factor": 8.0,
                       "original_max_position_embeddings": 64,
                       "beta_fast": 8.0, "beta_slow": 1.0,
                       "attention_factor": 1.2079441541679836,
                       "partial_rotary_factor": 0.5},
                SLIDING: {"rope_type": "default", "rope_theta": 100.0,
                          "partial_rotary_factor": 1.0}},
            intermediate_size=128, num_experts=16, num_experts_per_tok=4,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            max_seq_len=256, dtype=jnp.float32)
        base.update(kw)
        return cls(**base)


@dataclasses.dataclass(frozen=True)
class PartialRope:
    """Rotary encoding of the first ``blend.dim`` values of a head, in
    halves: ``(x[i], x[i + dim / 2])`` is turned by ``pos *
    inv_freq[i]``, with cos and sin multiplied by ``attention_factor``
    (YaRN's; on the rotated values only); the values past ``dim`` pass
    untouched. The frequencies are ``YarnRope``'s (the one blend in the
    tree)."""
    blend: YarnRope
    attention_factor: float = 1.0

    def cos_sin(self, positions):
        """positions [B, S] -> (cos, sin) [B, S, 1, dim / 2] float32."""
        angle = positions.astype(jnp.float32)[..., None] \
            * self.blend.inv_freq()
        return (jnp.cos(angle)[:, :, None] * self.attention_factor,
                jnp.sin(angle)[:, :, None] * self.attention_factor)

    def rotate(self, x, cos_sin):
        """x [B, S, H, head_dim] -> the same shape and dtype."""
        half = self.blend.dim // 2
        cos, sin = cos_sin
        xf = x.astype(jnp.float32)
        a, b = xf[..., :half], xf[..., half:2 * half]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                                xf[..., 2 * half:]], axis=-1).astype(x.dtype)


def cache_spec(cfg: LagunaConfig) -> Dict[str, Any]:
    """What a served sequence keeps between steps, for the adapter: K and
    V pools of the full layers (every position) and of the sliding
    layers (``window``: a ring a sequence), no state."""
    row = cfg.num_key_value_heads * cfg.head_dim
    pages = {}
    for kind, name, window in ((FULL, "full", None),
                               (SLIDING, "window", cfg.sliding_window)):
        n = len(cfg.layers_of(kind))
        for side in "kv":
            if n:
                pages[f"{side}_{name}"] = dict(
                    {"layers": n, "row": row, "dtype": cfg.dtype,
                     "head_dim": cfg.head_dim, "q_heads": min(
                         cfg.num_attention_heads_per_layer[i]
                         for i in cfg.layers_of(kind))},
                    **({} if window is None else {"window": window}))
    return {
        "expert_counts": (sum(t == "sparse" for t in cfg.mlp_layer_types),
                          cfg.num_experts),
        # ``moe.expert_product``'s arguments beside a step's tokens
        "routed_experts": (cfg.num_experts_per_tok, cfg.num_experts,
                           cfg.num_experts, cfg.hidden_size, jnp.dtype(cfg.dtype).itemsize),
        "pages": pages,
        "state": {},
    }


class LagunaAttention(nn.Module):
    """One layer's attention: ``heads`` query heads; ``window`` None (a
    full layer) or the positions a sliding layer reads. ``config`` is a
    ``LagunaConfig``, or another family's with the same names
    (``models/smallthinker.py``: its ``rope_of`` gives None for a layer
    type without rotary, and it has no gate; ``models/lfm2.py``: its
    ``qk_norm`` puts q and k under an RMSNorm over a head's values, one
    learned gain of ``head_dim`` each, before the rotary)."""
    config: Any
    heads: int
    window: Optional[int]

    @nn.compact
    def __call__(self, x, k_pages=None, v_pages=None, block_tables=None,
                 seq_lengths=None, valid=None, layer=None):
        """x [B, S, D] (normed). Without pages: causal attention over the
        sequence's own tokens. With them (this layer kind's pools and
        tables; ``layer`` its index among its kind): the new tokens' K
        and V rows are written and the queries attend; a step of more
        than one token a row starts from an empty cache. Returns
        (y, k_pages, v_pages)."""
        cfg = self.config
        B, S, D = x.shape
        H, Hkv, d = self.heads, cfg.num_key_value_heads, cfg.head_dim
        dt = cfg.dtype
        kind = FULL if self.window is None else SLIDING
        scope = "attn_full" if self.window is None else "attn_window"
        rope = cfg.rope_of(kind)
        xb = x.astype(dt)
        with jax.named_scope(f"{scope}/qkv"):
            q = (xb @ dense(self, "q_proj", (D, H * d), dt)
                 ).reshape(B, S, H, d)
            k = (xb @ dense(self, "k_proj", (D, Hkv * d), dt)
                 ).reshape(B, S, Hkv, d)
            v = (xb @ dense(self, "v_proj", (D, Hkv * d), dt)
                 ).reshape(B, S, Hkv, d)
        served = k_pages is not None
        at = jnp.arange(S)[None, :] + (
            seq_lengths[:, None] if served else jnp.zeros((B, 1), jnp.int32))
        if getattr(cfg, "qk_norm", False):
            with jax.named_scope(f"{scope}/norm"):
                q = RMSNorm(cfg.rms_norm_eps, name="q_norm")(q).astype(dt)
                k = RMSNorm(cfg.rms_norm_eps, name="k_norm")(k).astype(dt)
        if rope is not None:        # (None: no position encoding, NoPE)
            with jax.named_scope(f"{scope}/rope"):
                turn = rope.cos_sin(at)
                q, k = rope.rotate(q, turn), rope.rotate(k, turn)
        q_pos = at if valid is None else jnp.where(valid, at, -1)
        if not served or S > 1:
            with jax.named_scope(f"{scope}/attend"):
                y = A.prefill_attention(q, k, v, q_pos, window=self.window)
        if served:
            n_new = S if valid is None else jnp.sum(
                valid.astype(jnp.int32), axis=1)
            with jax.named_scope(f"{scope}/write"):
                k_pages, v_pages = self._write(
                    k, v, k_pages, v_pages, block_tables, seq_lengths,
                    valid, n_new, layer)
            if S == 1:
                # one token a row: on the chip the live pages (the ring's
                # last ``window`` positions) read where they lie
                attend = A.paged_attention_decode if A.paged_decode_path(
                    H, d, k_pages, S, layer) == "paged_kernel" \
                    else A.paged_attention_reference
                with jax.named_scope(f"{scope}/attend"):
                    y = attend(q[:, 0], k_pages, v_pages, block_tables,
                               seq_lengths + n_new, layer=layer,
                               window=self.window)[:, None]
        if cfg.gating:
            with jax.named_scope(f"{scope}/gate"):
                gate = jax.nn.sigmoid(jnp.matmul(
                    xb, dense(self, "g_proj", (D, H), dt),
                    preferred_element_type=jnp.float32))
                y = (y.astype(jnp.float32) * gate[..., None]).astype(dt)
        with jax.named_scope(f"{scope}/out"):
            y = y.reshape(B, S, H * d).astype(dt)
            return jnp.matmul(y, dense(self, "o_proj", (H * d, D), dt),
                              preferred_element_type=jnp.float32), \
                k_pages, v_pages

    def _write(self, k, v, k_pages, v_pages, tables, seq_lengths, valid,
               n_new, layer):
        """The new rows into their pages. A sliding layer's prompt
        writes its ring the last ``window`` rows only: the others would
        be overwritten, or are never read again."""
        S, w = k.shape[1], self.window
        if w is None or S <= w:
            return A.append_kv_pages(
                k, v, k_pages, v_pages, tables, seq_lengths, valid=valid,
                layer=layer, ring=w is not None)
        start = jnp.maximum(n_new - w, 0)                       # [B]
        idx = start[:, None] + jnp.arange(w)[None, :]           # [B, w] < S
        take = idx[:, :, None, None]
        return A.append_kv_pages(
            jnp.take_along_axis(k, take, axis=1),
            jnp.take_along_axis(v, take, axis=1), k_pages, v_pages, tables,
            seq_lengths + start, valid=idx < n_new[:, None], layer=layer,
            ring=True)


class LagunaBlock(nn.Module):
    config: LagunaConfig
    index: int

    @nn.compact
    def __call__(self, x, attn_kwargs, valid=None):
        cfg, i = self.config, self.index
        window = cfg.sliding_window \
            if cfg.layer_types[i] == SLIDING else None
        h = RMSNorm(cfg.rms_norm_eps, name="attn_norm")(x)
        y, k_pages, v_pages = LagunaAttention(
            cfg, cfg.num_attention_heads_per_layer[i], window, name="attn")(
                h, valid=valid, **attn_kwargs)
        x = x + y.astype(x.dtype)
        h = RMSNorm(cfg.rms_norm_eps, name="ffn_norm")(x)
        if cfg.mlp_layer_types[i] == "sparse":
            y, counts = RoutedExperts(
                cfg.num_experts, cfg.moe_intermediate_size,
                cfg.num_experts_per_tok,
                scaling=cfg.moe_routed_scaling_factor, renormalize=True,
                shared_d_ff=cfg.shared_expert_intermediate_size,
                dtype=cfg.dtype, name="moe")(h, valid=valid)
        else:
            with jax.named_scope("mlp"):
                y = SwiGLU(cfg.intermediate_size, cfg.dtype, name="mlp")(h)
            counts = None
        return x + y.astype(x.dtype), k_pages, v_pages, counts


class LagunaModel(nn.Module):
    config: Any                 # LagunaConfig (or a subclass's own)
    block = LagunaBlock         # what a subclass replaces: one layer

    @nn.compact
    def __call__(self, input_ids, cache=None, seq_lengths=None, valid=None,
                 logits_at=None):
        """Logits [B, S, V] of a full forward; or, with ``cache``
        (``cache_spec``'s pools, ``block_tables`` [B, NB] and
        ``window_tables`` {window: [B, ring]}), one incremental step:
        ``seq_lengths`` [B] the tokens cached before this call (zeros
        where ``S > 1``: a prompt starts from an empty cache), ``valid``
        [B, S] the real tokens of a padded bucket. Returns ``(logits, new
        cache, expert_counts)``, expert_counts [sparse layers, experts]
        int32. ``logits_at`` ([B] int) keeps one position a row before
        the head."""
        cfg = self.config
        dt = cfg.dtype
        embed = self.param("embed", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.hidden_size), dt)
        # the residual stream is float32, every product takes bfloat16
        # operands (as models/kimi_linear.py, and for its reason)
        x = embed[input_ids].astype(jnp.float32)
        served = cache is not None
        cache = dict(cache) if served else None
        nth = {FULL: 0, SLIDING: 0}         # a layer's index among its kind
        counts = []
        for i, kind in enumerate(cfg.layer_types):
            kw: Dict[str, Any] = {}
            name = "full" if kind == FULL else "window"
            if served:
                kw = dict(
                    k_pages=cache[f"k_{name}"], v_pages=cache[f"v_{name}"],
                    block_tables=cache["block_tables"] if kind == FULL
                    else cache["window_tables"][cfg.sliding_window],
                    seq_lengths=seq_lengths, layer=nth[kind])
            x, k_pages, v_pages, c = self.block(
                cfg, i, name=f"layers_{i}")(x, kw, valid=valid)
            if served:
                cache[f"k_{name}"], cache[f"v_{name}"] = k_pages, v_pages
            nth[kind] += 1
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
        return logits, cache, counts
