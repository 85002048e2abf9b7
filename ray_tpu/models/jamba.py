"""Jamba family (flax linen): Mamba-1 layers with a few attention layers
among them, a dense SwiGLU in every layer, the head tied to the token
table.

Source: ai21labs/AI21-Jamba2-3B ``config.json`` (``model_type``
``jamba``). Layer ``i`` is attention where ``i % attn_layer_period ==
attn_layer_offset`` and Mamba otherwise (the family's rule; of the
published 28 layers: 7 and 21). ``num_experts`` 1: every feed-forward
part is a plain SwiGLU, no router exists. Pre-norm RMSNorm with a
residual round the mixer and round the feed-forward, a final RMSNorm,
logits against the token table. With ``n`` the mixer's normed input,
``d_in = mamba_expand x hidden_size``, ``N = mamba_d_state``, ``R =
mamba_dt_rank``:

  Mamba      [u | z] = n W_in                 no bias
             u = silu(conv_K(u) + b_conv)     causal, depthwise; the last
                                              K - 1 rows are carried
             [t | B | C] = u W_x              R + N + N, no bias
             t, B, C under an RMSNorm each    (the family's addition)
             dt = softplus(t W_dt + b_dt)     float32
             h <- exp(dt A) h + dt B u,  y = h C + D u    (``ops/ssm.py``:
                                              A = -exp(A_log) a channel
                                              AND a state)
             out = (y * silu(z)) W_out        no norm between
  Attention  ``models/laguna.py``'s, read with this family's numbers:
             ``num_attention_heads`` query heads over
             ``num_key_value_heads``, no position encoding (the Mamba
             layers carry order), no gate, no bias
  SwiGLU     (silu(n' W_g) * (n' W_u)) W_d    ``parallel/moe.py:SwiGLU``

A run of Mamba layers between two attention layers is ONE block's program
looped over the run's parameters, which are stacked on a leading axis
(``mamba_0``, ``mamba_1``, ... ; the attention layers are ``attn_0``,
``attn_1``, ...): a step's program holds one Mamba layer a run and
compiles in the time of a handful of layers, whatever the depth (as
``models/gpt2.py``'s ``stacked`` form, and for its reason).

One module serves both forms. ``model(ids)`` is a full forward (the scan
from a zero state, causal attention over the sequence's own keys). The
served form, ``model(ids, cache=..., seq_lengths=..., valid=...)``, is
one incremental step over what ``cache_spec`` states for the adapter
(serve/llm/model_runner.py):

  pages   ``k_pages`` / ``v_pages`` [n_attn, P, bs, Hkv x head_dim], for
          the attention layers alone
  state   a slot a running sequence for the Mamba layers:
          ``mamba_state`` [n_mamba, slots, N, d_in] float32 (channels
          minor: ``ops/ssm.py`` says why) and ``mamba_conv`` [n_mamba,
          slots, K - 1, d_in], the last K - 1 pre-convolution rows (four
          axes, so that the chip may keep a tap's rows [slots, d_in] as
          whole tiles, which is what it chooses; as [slots, (K - 1) x
          d_in] it laid the whole array out anew on the way into every
          full decode step and back: compiled for the described chip,
          PR 48)

Weights are stored and multiplied in ``dtype`` (bfloat16 as served);
norms, ``A_log``, ``D``, ``dt``'s bias, the recurrence and the residual
stream are float32.

Device-trace scopes: ``mamba/{in_proj,conv,x_proj,step,scan,out_proj}``,
``attn_full/{qkv,write,attend,out}``, ``mlp``, ``lm_head``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.laguna import LagunaAttention
from ray_tpu.models.mla import RMSNorm, dense as _dense
from ray_tpu.ops import linear_attention as LA
from ray_tpu.ops import ssm
from ray_tpu.parallel.moe import SwiGLU

MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    num_hidden_layers: int = 28
    attn_layer_offset: int = 7
    attn_layer_period: int = 14
    # Mamba-1
    mamba_expand: int = 2
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    # attention (no position encoding)
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    head_dim: Optional[int] = None          # None: hidden_size / heads
    # feed-forward
    intermediate_size: int = 8192
    num_experts: int = 1
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = True
    max_seq_len: int = 4096         # what a served sequence may reach
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(
                self, "head_dim", self.hidden_size // self.num_attention_heads)
        if self.num_experts != 1 or self.mamba_proj_bias \
                or not self.tie_word_embeddings:
            raise ValueError(
                "this file holds the family's dense members: num_experts 1, "
                "mamba_proj_bias false, a tied head")

    def layer_kinds(self) -> Tuple[str, ...]:
        """``attention`` where ``i % attn_layer_period ==
        attn_layer_offset``, else ``mamba``, for layers 0 ..
        num_hidden_layers - 1."""
        return tuple(
            ATTENTION if i % self.attn_layer_period == self.attn_layer_offset
            else MAMBA for i in range(self.num_hidden_layers))

    def runs(self) -> Tuple[Tuple[str, int], ...]:
        """The layers as runs: (``mamba``, how many in a row) and
        (``attention``, 1), in order."""
        out = []
        for kind in self.layer_kinds():
            if kind == MAMBA and out and out[-1][0] == MAMBA:
                out[-1] = (MAMBA, out[-1][1] + 1)
            else:
                out.append((kind, 1))
        return tuple(out)

    n_layers = property(lambda self: self.num_hidden_layers)
    d_inner = property(lambda self: self.mamba_expand * self.hidden_size)

    # ---- what models/laguna.py's attention reads of a config ----
    gating = False

    def rope_of(self, kind: str):
        return None                 # no position encoding

    @classmethod
    def tiny(cls, vocab_size: int = 512, **kw):       # tests
        """M A M M A M: runs of one, two and one Mamba layers round two
        attention layers of five query heads over one."""
        base = dict(
            vocab_size=vocab_size, hidden_size=64, num_hidden_layers=6,
            attn_layer_offset=1, attn_layer_period=3, mamba_dt_rank=8,
            num_attention_heads=5, num_key_value_heads=1, head_dim=16,
            intermediate_size=128, max_seq_len=256, dtype=jnp.float32)
        base.update(kw)
        return cls(**base)


def cache_spec(cfg: JambaConfig) -> Dict[str, Any]:
    """What a served sequence keeps between steps, for the adapter: K and
    V pages for the attention layers alone, a state slot and a
    convolution tail for the Mamba layers."""
    kinds = cfg.layer_kinds()
    n_mamba = kinds.count(MAMBA)
    page = {"layers": kinds.count(ATTENTION),
            "row": cfg.num_key_value_heads * cfg.head_dim,
            "dtype": cfg.dtype, "head_dim": cfg.head_dim,
            "q_heads": cfg.num_attention_heads}
    return {
        "pages": {"k_pages": dict(page), "v_pages": dict(page)},
        "state": {
            # (``recurrence``: a decode step runs ``ssm.mamba_decode_path``'s
            # choice over this array)
            "mamba_state": {"shape": (n_mamba, cfg.mamba_d_state,
                                      cfg.d_inner),
                            "dtype": jnp.float32, "recurrence": "mamba"},
            "mamba_conv": {"shape": (n_mamba, cfg.mamba_d_conv - 1,
                                     cfg.d_inner), "dtype": cfg.dtype}},
    }


def _dt_bias_init(key, shape):
    """softplus^-1 of a time step log-uniform in [0.001, 0.1] (the
    family's initialiser)."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    jnp.log(0.001), jnp.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class MambaMixer(nn.Module):
    config: JambaConfig

    @nn.compact
    def __call__(self, x, state=None, conv_tail=None, valid=None,
                 pool=None):
        """x [B, S, D] (normed); state [B, N, d_in] and conv_tail [B,
        K - 1, d_in] are what the rows' sequences carried here (None: the
        start of a sequence). Returns (y, new state, new tail). A served
        decode step (one token a row) hands ``pool = (mamba_state, layer,
        slots)`` in ``state``'s place, ``ssm.mamba_decode_step``'s
        arguments, and gets the pool back in the new state's place."""
        cfg = self.config
        B, S, D = x.shape
        d_in, N, R, K = (cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank,
                         cfg.mamba_d_conv)
        dt_, f32 = cfg.dtype, jnp.float32
        if state is None and pool is None:
            state = jnp.zeros((B, N, d_in), f32)
            conv_tail = jnp.zeros((B, K - 1, d_in), dt_)
        with jax.named_scope("mamba/in_proj"):
            uz = jnp.matmul(x.astype(dt_),
                            _dense(self, "in_proj", (D, 2 * d_in), dt_),
                            preferred_element_type=f32)
            # the convolution's rows are what the tail holds of them: the
            # activations' dtype, multiplied and summed in float32
            u, z = uz[..., :d_in].astype(dt_).astype(f32), uz[..., d_in:]
        with jax.named_scope("mamba/conv"):
            # one token a row: the new tail is the old one moved up a row,
            # or the old one (a select; the general form gathers the last
            # K - 1 real rows of a padded prompt, and that gather read
            # 2.5 ms of a 256-row decode step: my chip run, PR 48)
            n_new = None if valid is None or S == 1 else \
                jnp.sum(valid.astype(jnp.int32), axis=1)
            u, new_tail = LA.short_conv(
                u, conv_tail, _dense(self, "conv", (K, d_in), dt_, std=0.5),
                n_new, bias=self.param("conv_bias", nn.initializers.zeros,
                                       (d_in,), dt_)
                if cfg.mamba_conv_bias else None)
            if valid is not None and S == 1:
                new_tail = jnp.where(valid[:, :, None], new_tail,
                                     conv_tail.astype(new_tail.dtype))
            u = nn.silu(u)
        with jax.named_scope("mamba/x_proj"):
            tbc = jnp.matmul(u.astype(dt_),
                             _dense(self, "x_proj", (d_in, R + 2 * N), dt_),
                             preferred_element_type=f32)
            t = RMSNorm(cfg.rms_norm_eps, name="dt_norm")(tbc[..., :R])
            Bm = RMSNorm(cfg.rms_norm_eps, name="b_norm")(tbc[..., R:R + N])
            Cm = RMSNorm(cfg.rms_norm_eps, name="c_norm")(tbc[..., R + N:])
            dt = jax.nn.softplus(
                jnp.matmul(t.astype(dt_),
                           _dense(self, "dt_proj", (R, d_in), dt_,
                                  std=R ** -0.5),
                           preferred_element_type=f32)
                + self.param("dt_bias", _dt_bias_init, (d_in,)))
            if valid is not None:   # an empty position leaves the state
                dt = jnp.where(valid[..., None], dt, 0.0)
        # channels minor, as the state ([N, d_in]: ops/ssm.py)
        a_log = self.param(
            "A_log", lambda key, shape: jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=f32))[:, None], shape),
            (N, d_in))
        skip = self.param("D", nn.initializers.ones, (d_in,), f32)
        A = -jnp.exp(a_log)
        if S == 1:
            one = (u[:, 0], dt[:, 0], Bm[:, 0], Cm[:, 0], A, skip)
            with jax.named_scope("mamba/step"):
                y, new_state = ssm.mamba_step(*one, state) \
                    if pool is None else ssm.mamba_decode_step(*one, *pool)
                y = y[:, None]
        else:
            with jax.named_scope("mamba/scan"):
                y, new_state = ssm.mamba_scan(u, dt, Bm, Cm, A, skip, state)
        with jax.named_scope("mamba/out_proj"):
            return (jnp.matmul((y * nn.silu(z)).astype(dt_),
                               _dense(self, "out_proj", (d_in, D), dt_),
                               preferred_element_type=f32),
                    new_state, new_tail)


def _feed_forward(cfg, x):
    h = RMSNorm(cfg.rms_norm_eps, name="ffn_norm")(x)
    with jax.named_scope("mlp"):
        y = SwiGLU(cfg.intermediate_size, cfg.dtype, name="mlp")(h)
    return x + y.astype(x.dtype)


class MambaBlock(nn.Module):
    """One Mamba layer, in the form a run's loop takes: ``carry`` is the
    residual stream and, served, the two state arrays; ``layer`` this
    layer's index among the Mamba layers; ``slots`` [B] int the rows'
    state slots (None: row r is slot r + 1)."""
    config: JambaConfig

    @nn.compact
    def __call__(self, carry, layer, slots=None, valid=None):
        cfg = self.config
        x, state, conv = carry
        B, S, _ = x.shape
        served = state is not None
        kw: Dict[str, Any] = {"valid": valid}
        at = slice(1, 1 + B) if slots is None else slots
        h = RMSNorm(cfg.rms_norm_eps, name="mixer_norm")(x)
        with jax.named_scope("mamba"):
            # reading and writing the rows' state lies inside the
            # recurrence's scope (its roofline share counts the state in
            # and out once: a write outside the scope read 161% of it,
            # PERF.md, PR 28)
            if served:
                if S == 1:
                    kw.update(pool=(state, layer, slots))
                else:
                    with jax.named_scope("mamba/scan"):
                        kw.update(state=state[layer, at])
                with jax.named_scope("mamba/conv"):
                    kw.update(conv_tail=conv[layer, at])
            y, new_state, new_tail = MambaMixer(cfg, name="mixer")(h, **kw)
            if served:
                if S == 1:
                    state = new_state
                else:
                    with jax.named_scope("mamba/scan"):
                        state = state.at[layer, at].set(new_state)
                with jax.named_scope("mamba/conv"):
                    conv = conv.at[layer, at].set(
                        new_tail.astype(conv.dtype))
        x = _feed_forward(cfg, x + y.astype(x.dtype))
        return (x, state, conv), None


class AttentionBlock(nn.Module):
    config: JambaConfig

    @nn.compact
    def __call__(self, x, attn_kwargs, valid=None):
        cfg = self.config
        h = RMSNorm(cfg.rms_norm_eps, name="mixer_norm")(x)
        y, k_pages, v_pages = LagunaAttention(
            cfg, cfg.num_attention_heads, None, name="attn")(
                h, valid=valid, **attn_kwargs)
        return _feed_forward(cfg, x + y.astype(x.dtype)), \
            k_pages, v_pages


class JambaModel(nn.Module):
    config: JambaConfig

    @nn.compact
    def __call__(self, input_ids, cache=None, seq_lengths=None, valid=None,
                 logits_at=None):
        """Logits [B, S, V] of a full forward; or, with ``cache``
        (``{"k_pages", "v_pages", "block_tables", "mamba_state",
        "mamba_conv", "slots"}``: ``cache_spec``; ``slots`` [B] is each
        row's state slot; without it row r IS slot r + 1, a full decode
        batch, whose state is read and written where it lies), one
        incremental step: ``seq_lengths`` [B] the tokens cached before
        this call, ``valid`` [B, S] the real tokens of a padded bucket (a
        padded position or row leaves state and tail untouched). Returns
        ``(logits, new cache)``. ``logits_at`` ([B] int) keeps one
        position a row before the head."""
        cfg = self.config
        dt_ = cfg.dtype
        embed = self.param("embed", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.hidden_size), dt_)
        # the residual stream is float32, every product takes ``dtype``
        # operands (as models/kimi_linear.py, and for its reason)
        x = embed[input_ids].astype(jnp.float32)
        served = cache is not None
        cache = dict(cache) if served else {}
        state, conv = cache.get("mamba_state"), cache.get("mamba_conv")
        i_mamba = i_attn = 0
        for i, (kind, n) in enumerate(cfg.runs()):
            if kind == MAMBA:
                run = nn.scan(
                    MambaBlock, variable_axes={"params": 0},
                    split_rngs={"params": True}, length=n,
                    in_axes=(0, nn.broadcast, nn.broadcast))(
                        cfg, name=f"mamba_{i - i_attn}")
                (x, state, conv), _ = run(
                    (x, state, conv), i_mamba + jnp.arange(n),
                    cache.get("slots"), valid)
                i_mamba += n
                continue
            kw: Dict[str, Any] = {}
            if served:
                kw = dict(k_pages=cache["k_pages"], v_pages=cache["v_pages"],
                          block_tables=cache["block_tables"],
                          seq_lengths=seq_lengths, layer=i_attn)
            x, k_pages, v_pages = AttentionBlock(
                cfg, name=f"attn_{i_attn}")(x, kw, valid=valid)
            if served:
                cache["k_pages"], cache["v_pages"] = k_pages, v_pages
            i_attn += 1
        x = RMSNorm(cfg.rms_norm_eps, name="final_norm")(x)
        if logits_at is not None:
            x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
        with jax.named_scope("lm_head"):    # the token table, tied
            logits = jnp.einsum("bsd,vd->bsv", x.astype(dt_), embed,
                                preferred_element_type=jnp.float32)
        if not served:
            return logits
        return logits, dict(cache, mamba_state=state, mamba_conv=conv)
