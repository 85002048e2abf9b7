"""Operations and bytes a Kimi-Linear decode step needs, from shapes and
the step's own counters (``benchmark/harness/costs.py``'s conventions: a
multiply-add is 2 FLOPs, every byte is moved once, nothing recomputed).

``c`` is the configuration file's ``model.kwargs`` (the source's key
names; ``experts_held`` = [first, count]; ``vocab_size`` the slice held).
Weights are bfloat16 (2 bytes), the KDA state float32.
"""

from __future__ import annotations

from typing import Dict

W_BYTES = 2         # weights and pages as stored
STATE_BYTES = 4     # the KDA state


def layer_kinds(c) -> list:
    n = c["num_hidden_layers"]
    kda = set(c["kda_layers"])
    return ["kda" if i in kda else "mla" for i in range(1, n + 1)]


def kda_mixer_params(c) -> int:
    D, H, d = c["hidden_size"], c["kda_num_heads"], c["kda_head_dim"]
    r = c.get("kda_gate_rank", d)
    return (3 * D * H * d + H * d * D            # q, k, v and o
            + 2 * (D * r + r * H * d)            # decay and output gates
            + D * H)                             # beta


def mla_mixer_params(c) -> int:
    D, H = c["hidden_size"], c["num_attention_heads"]
    R, dn, dr, dv = (c["kv_lora_rank"], c["qk_nope_head_dim"],
                     c["qk_rope_head_dim"], c["v_head_dim"])
    return D * H * (dn + dr) + D * (R + dr) + R * H * (dn + dv) + H * dv * D


def expert_params(c) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def dense_ffn_params(c) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def routed_layers(c) -> int:
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def always_read_params(c) -> int:
    """Matrix weights every decode step multiplies whatever the routing:
    the mixers, the dense feed-forward of the leading layers, each routed
    layer's router and shared expert(s), and the head. (The token table
    is gathered, a row a token: counted with the activations, not here.)"""
    kinds = layer_kinds(c)
    D = c["hidden_size"]
    return (kinds.count("kda") * kda_mixer_params(c)
            + kinds.count("mla") * mla_mixer_params(c)
            + c["first_k_dense_replace"] * dense_ffn_params(c)
            + routed_layers(c) * (D * c["num_experts"]
                                  + c["num_shared_experts"]
                                  * expert_params(c))
            + D * c["vocab_size"])


def param_count(c) -> int:
    """Every stored matrix weight of the share held (norm gains, biases
    and the convolution taps are O(width) and left out)."""
    held = c["experts_held"][1] if c.get("experts_held") \
        else c["num_experts"]
    return (always_read_params(c) + routed_layers(c) * held
            * expert_params(c) + c["vocab_size"] * c["hidden_size"])


def kda_state_bytes(c, n_seqs: int) -> float:
    """The recurrent state of ``n_seqs`` sequences, all KDA layers."""
    H, d = c["kda_num_heads"], c["kda_head_dim"]
    return float(layer_kinds(c).count("kda") * n_seqs * H * d * d
                 * STATE_BYTES)


def kda_recurrence_cost(c, n_seqs: int) -> Dict[str, float]:
    """One token a sequence through every KDA layer's recurrence: the
    state read once and written once; per state element a decay, two
    products with k and q, and the rank-one update (8 FLOPs)."""
    state = kda_state_bytes(c, n_seqs)
    return {"bytes": 2.0 * state, "flops": 8.0 * state / STATE_BYTES}


def moe_experts_cost(c, experts_touched: int, assignments: int
                     ) -> Dict[str, float]:
    """The routed experts of one step, all routed layers together:
    ``experts_touched`` (expert, layer) pairs that got a token, their
    weights read once; ``assignments`` (token, expert) pairs held here,
    each three products of hidden x width; activations in and out of
    every assignment."""
    per = expert_params(c)
    D = c["hidden_size"]
    return {"bytes": float(experts_touched * per * W_BYTES
                           + assignments * 2 * D * W_BYTES),
            "flops": 2.0 * assignments * per}


def latent_bytes(c, live_tokens: int) -> float:
    """The cached latent rows the MLA layers read: kv_lora_rank + rope
    values a token a layer (the pool's row is padded to whole lanes; the
    padding is not needed and not counted)."""
    return float(layer_kinds(c).count("mla") * live_tokens
                 * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * W_BYTES)


def mla_decode_flops(c, live_tokens: int) -> float:
    """Absorbed decode attention: scores against and sums of the cached
    rows, every head."""
    H = c["num_attention_heads"]
    R, dr = c["kv_lora_rank"], c["qk_rope_head_dim"]
    return 2.0 * layer_kinds(c).count("mla") * live_tokens * H * (2 * R + dr)


def decode_step_cost(c, n_seqs: int, live_tokens: int,
                     experts_touched: int, assignments: int
                     ) -> Dict[str, float]:
    """A whole decode step of ``n_seqs`` sequences whose contexts add up
    to ``live_tokens``."""
    moe = moe_experts_cost(c, experts_touched, assignments)
    kda = kda_recurrence_cost(c, n_seqs)
    always = always_read_params(c)
    return {
        "bytes": always * W_BYTES + moe["bytes"] + kda["bytes"]
        + latent_bytes(c, live_tokens),
        "flops": 2.0 * always * n_seqs + moe["flops"] + kda["flops"]
        + mla_decode_flops(c, live_tokens)}
