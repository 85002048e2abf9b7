"""Multi-head latent attention (MLA) as a flax mixer, for every model
that has it (``models/kimi_linear.py``, ``models/kimi_k2.py``,
``models/longcat_flash.py``).

What a configuration has or has not decides the form, nothing else:

  ``q_lora_rank``   None: the queries come from one matrix ``q_proj``;
                    a rank: ``q = RMSNorm(x W_qa) W_qb`` (a low-rank
                    query, DeepSeek-V2's)
  ``rope``          None: no position enters (Kimi-Linear's
                    ``mla_use_nope``: the 64 "rope" values of q and k
                    are carried as they are); a ``YarnRope``: those
                    values are rotated at the token's absolute position,
                    k's once, before its row is cached
  ``q_scale``,      absent or 1: nothing; else (LongCat-Flash's
  ``latent_scale``  ``mla_scale_q_lora`` / ``mla_scale_kv_lora``) ``q`` is
                    multiplied by ``q_scale`` after its up-projection
                    (both parts) and the normalised latent ``c`` by
                    ``latent_scale`` before anything reads it
  ``prompt_logits_bytes``  absent: ``ops.attention.LATENT_LOGITS_BYTES``;
                    else the float32 logits of one block of a prompt's
                    queries above which the keys are walked in blocks
                    inside one kernel (``latent_prefill_attention``)

The cached row is ``(c, RoPE(k_r))``: the normalised compressed latent
(``kv_lora_rank`` values, times ``latent_scale``: the row as ``W_kvb``
multiplies it, so that every attention path reads the pool as it is) and
the key part all heads share, in whole lanes of 128 (``lanes``). A decode
step (one token a row over pages) attends in the absorbed form: on the
chip one Pallas kernel over the row's live pages where they lie
(``ops.attention.latent_attention_decode``), off it over the rows
gathered to the padded context; everything else gathers and builds every
head's keys and values (``ops.attention.latent_attention``). Which it is
follows from what the call can observe
(``ops.attention.latent_decode_path``).

Device-trace scopes (inside the block's scope ``mla``): ``mla/q_lora``
(``mla/q`` without the bottleneck), ``mla/rope``, ``mla/write`` (the new
rows into their pages), ``mla/attend`` (the absorbed query and the
kernel, or the gather of the context's rows and the attention),
``mla/out``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.ops import attention as A


def lanes(n: int) -> int:
    """A page row is whole lanes of 128, so that the chip keeps the pool
    in the order scatter and gather index it (a row of 576 made it lay
    the whole pool out anew twice a step: compiled for the described
    chip, PR 28)."""
    return -(-n // 128) * 128


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps) * scale


def dense(mod, name, shape, dtype, std=0.02):
    return mod.param(name, nn.initializers.normal(std), shape, dtype)


@dataclasses.dataclass(frozen=True)
class YarnRope:
    """Rotary position encoding with YaRN's frequency blend, by the
    source's ``rope_theta`` and ``rope_scaling`` keys. ``dim`` values are
    rotated in interleaved pairs ``(x[2i], x[2i+1])`` (DeepSeek-V3's
    pairing) by ``pos * inv_freq[i]``."""
    dim: int
    theta: float
    factor: float = 1.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def ramp_dims(self):
        """(low, high): the pair indices between which the blend runs:
        where ``original_max_position_embeddings`` positions hold
        ``beta_fast`` and ``beta_slow`` turns, rounded outwards."""
        def at(turns):
            return self.dim * math.log(
                self.original_max_position_embeddings
                / (turns * 2 * math.pi)) / (2 * math.log(self.theta))
        low = max(math.floor(at(self.beta_fast)), 0)
        high = min(math.ceil(at(self.beta_slow)), self.dim - 1)
        return low, high

    def inv_freq(self):
        """[dim / 2] float32: ``theta^(-2i/dim)`` below ``low``, that
        over ``factor`` above ``high``, a linear blend between."""
        i = jnp.arange(self.dim // 2, dtype=jnp.float32)
        f = self.theta ** (-2.0 * i / self.dim)
        if self.factor == 1.0:
            return f
        low, high = self.ramp_dims()
        ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
        return f / self.factor * ramp + f * (1.0 - ramp)

    @staticmethod
    def _mscale(factor, m):
        return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0

    @property
    def cos_sin_scale(self) -> float:
        """What YaRN multiplies cos and sin by (1 where ``mscale`` =
        ``mscale_all_dim``)."""
        return self._mscale(self.factor, self.mscale) \
            / self._mscale(self.factor, self.mscale_all_dim)

    @property
    def softmax_mscale(self) -> float:
        """``m``: the softmax scale is multiplied by ``m * m``."""
        return self._mscale(self.factor, self.mscale_all_dim)

    def cos_sin(self, positions):
        """positions [B, S] -> (cos, sin) [B, S, dim / 2] float32 of each
        pair's angle at each position."""
        angle = positions.astype(jnp.float32)[..., None] * self.inv_freq()
        return (jnp.cos(angle) * self.cos_sin_scale,
                jnp.sin(angle) * self.cos_sin_scale)

    def rotate(self, x, cos_sin):
        """x [B, S, ..., dim] -> the same shape and dtype, each pair
        turned by its position's angle (float32)."""
        lead = (*x.shape[:2], *([1] * (x.ndim - 3)), self.dim // 2)
        cos, sin = (t.reshape(lead) for t in cos_sin)
        pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], self.dim // 2, 2)
        a, b = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
        return out.reshape(x.shape).astype(x.dtype)


def yarn_rope(dim: int, theta: float, scaling: Optional[dict]) -> YarnRope:
    """From the source's ``rope_theta`` and ``rope_scaling`` (None or a
    dict of type ``yarn``)."""
    if not scaling:
        return YarnRope(dim, float(theta))
    kind = scaling.get("type", scaling.get("rope_type"))
    if kind != "yarn":
        raise ValueError(f"rope_scaling type {kind!r}: only yarn is written")
    keys = ("factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "mscale", "mscale_all_dim")
    return YarnRope(dim, float(theta),
                    **{k: scaling[k] for k in keys if k in scaling})


class MLAMixer(nn.Module):
    """``config`` names ``num_attention_heads``, ``kv_lora_rank``,
    ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
    ``rms_norm_eps``, ``dtype``, ``q_lora_rank`` (or None) and ``rope``
    (a ``YarnRope`` or None); it may name ``q_scale`` and
    ``latent_scale`` (floats; absent = 1), ``latent_norm_eps`` (the
    two inner norms'; absent = ``rms_norm_eps``) and
    ``prompt_logits_bytes`` (absent: ``ops.attention``'s)."""
    config: object

    @nn.compact
    def __call__(self, x, pages=None, block_tables=None, seq_lengths=None,
                 valid=None, layer=None):
        """x [B, S, D]. Without ``pages``: causal attention over the
        sequence's own tokens. With them: the new tokens' latent rows
        are written to layer ``layer`` of the pool and the queries
        attend to what the block tables reach. Returns (y, pages)."""
        cfg = self.config
        B, S, D = x.shape
        H, R = cfg.num_attention_heads, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
            cfg.v_head_dim
        dt = cfg.dtype
        rope, q_rank = cfg.rope, cfg.q_lora_rank
        eps = getattr(cfg, "latent_norm_eps", cfg.rms_norm_eps)
        xb = x.astype(dt)
        with jax.named_scope("mla/q_lora" if q_rank else "mla/q"):
            if q_rank:
                c_q = RMSNorm(eps, name="q_norm")(
                    xb @ dense(self, "q_a", (D, q_rank), dt))
                q = c_q.astype(dt) @ dense(self, "q_b",
                                           (q_rank, H * (dn + dr)), dt)
            else:
                q = xb @ dense(self, "q_proj", (D, H * (dn + dr)), dt)
            q = q.reshape(B, S, H, dn + dr)
            if getattr(cfg, "q_scale", 1.0) != 1.0:
                q = q * jnp.asarray(cfg.q_scale, q.dtype)
        kv = xb @ dense(self, "kv_a", (D, R + dr), dt)
        c = RMSNorm(eps, name="kv_norm")(kv[..., :R])
        if getattr(cfg, "latent_scale", 1.0) != 1.0:
            c = c * cfg.latent_scale            # float32, before it is cast
        q_rope = sm_scale = None
        if rope is None:
            latent = jnp.concatenate([c.astype(dt), kv[..., R:]], axis=-1)
        else:
            with jax.named_scope("mla/rope"):
                at = jnp.arange(S)[None, :] if pages is None \
                    else seq_lengths[:, None] + jnp.arange(S)[None, :]
                turn = rope.cos_sin(jnp.broadcast_to(at, (B, S)))
                q_rope = rope.rotate(q[..., dn:], turn)
                latent = jnp.concatenate(
                    [c.astype(dt), rope.rotate(kv[..., R:], turn)], axis=-1)
            sm_scale = (dn + dr) ** -0.5 * rope.softmax_mscale ** 2
        w_kvb = dense(self, "kv_b", (R, H * (dn + dv)), dt
                      ).reshape(R, H, dn + dv)
        in_place = A.latent_decode_path(pages, R, S, layer) == "latent_kernel"
        if pages is None:
            context = latent
            q_pos = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        else:
            with jax.named_scope("mla/write"):
                row = jnp.pad(latent, ((0, 0), (0, 0),
                                       (0, pages.shape[-1] - R - dr)))
                pages = A.append_latent_pages(row, pages, block_tables,
                                              seq_lengths, valid, layer)
            if not in_place:
                with jax.named_scope("mla/attend"):
                    context = A.paged_gather(pages, block_tables,
                                             layer)[..., :R + dr]
            q_pos = seq_lengths[:, None] + jnp.arange(S)[None, :]
            if valid is not None:
                q_pos = jnp.where(valid, q_pos, -1)
        with jax.named_scope("mla/attend"):
            if in_place:
                # one token a row on the chip: the row's live pages, read
                # where they lie (``valid`` can only mark whole rows; a
                # padding row's length is 0). The absorbed query as the
                # pool holds a row: (q_n W_uk | q_r | 0)
                q_abs = jnp.concatenate([
                    jnp.einsum("bhd,rhd->bhr", q[:, 0, :, :dn],
                               w_kvb[..., :dn]),
                    (q[..., dn:] if q_rope is None else q_rope)[:, 0],
                    jnp.zeros((B, H, row.shape[-1] - R - dr), dt)], axis=-1)
                out = A.latent_attention_decode(
                    q_abs.astype(pages.dtype), pages, block_tables,
                    jnp.max(q_pos, axis=1) + 1, rank=R, layer=layer,
                    sm_scale=sm_scale or (dn + dr) ** -0.5)
                y = jnp.einsum("bhr,rhd->bhd", out.astype(dt),
                               w_kvb[..., dn:])
            else:
                y = A.latent_attention(
                    q[..., :dn], q[..., dn:] if q_rope is None else q_rope,
                    context, w_kvb, q_pos, v_dim=dv,
                    absorbed=pages is not None and S == 1,
                    sm_scale=sm_scale,
                    logits_bytes=getattr(cfg, "prompt_logits_bytes", None))
        with jax.named_scope("mla/out"):
            y = y.reshape(B, S, H * dv).astype(dt)
            return jnp.matmul(y, dense(self, "o_proj", (H * dv, D), dt),
                              preferred_element_type=jnp.float32), pages
