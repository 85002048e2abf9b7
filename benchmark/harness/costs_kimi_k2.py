"""Operations and bytes a Kimi-K2 step needs, from the configuration's
fields and the step's own counters (``benchmark/harness/costs.py``'s
conventions: a multiply-add is 2 FLOPs, every byte is moved once, nothing
recomputed, never a count of what the implementation does).

``c`` is the configuration file's ``model.kwargs`` (the source's key
names; ``experts_held`` = [first, count]; ``vocab_size`` the slice held).
Weights and cached latent rows are bfloat16 (2 bytes), the router's
matrix float32.
"""

from __future__ import annotations

from typing import Dict

W_BYTES = 2         # weights and pages as stored
ROUTER_BYTES = 4    # the router's matrix is float32


def mla_params(c) -> int:
    """One layer's attention matrices: q down and up (or one q matrix),
    kv down, kv up, out."""
    D, H = c["hidden_size"], c["num_attention_heads"]
    R, dn, dr, dv = (c["kv_lora_rank"], c["qk_nope_head_dim"],
                     c["qk_rope_head_dim"], c["v_head_dim"])
    rq = c.get("q_lora_rank")
    q = D * rq + rq * H * (dn + dr) if rq else D * H * (dn + dr)
    return q + D * (R + dr) + R * H * (dn + dv) + H * dv * D


def expert_params(c) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def dense_ffn_params(c) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def routed_layers(c) -> int:
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def always_multiplied_params(c) -> int:
    """Matrix weights every token is multiplied by whatever the routing,
    the router and the head apart: attention, the dense feed-forward of
    the leading layers, each routed layer's shared expert(s)."""
    return (c["num_hidden_layers"] * mla_params(c)
            + c["first_k_dense_replace"] * dense_ffn_params(c)
            + routed_layers(c) * c["n_shared_experts"] * expert_params(c))


def router_params(c) -> int:
    return routed_layers(c) * c["hidden_size"] * c["n_routed_experts"]


def head_params(c) -> int:
    return c["hidden_size"] * c["vocab_size"]


def param_count(c) -> int:
    """Every stored matrix weight of the share held (norm gains and the
    selection bias are O(width) and left out)."""
    held = c["experts_held"][1] if c.get("experts_held") \
        else c["n_routed_experts"]
    return (always_multiplied_params(c) + router_params(c)
            + routed_layers(c) * held * expert_params(c)
            + 2 * head_params(c))


def latent_row_bytes(c) -> int:
    """One cached token of one layer: kv_lora_rank + rope values (the
    pool pads a row to whole lanes; the padding is not needed and not
    counted)."""
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * W_BYTES


def moe_experts_cost(c, experts_touched: float, assignments: float
                     ) -> Dict[str, float]:
    """The routed experts of one step, all routed layers together, as
    ``costs_kimi_linear.moe_experts_cost`` counts: ``experts_touched``
    (expert, layer) pairs that got a token, their weights read once;
    ``assignments`` (token, expert) pairs held here, each three products
    of hidden x width; activations in and out of every assignment."""
    per = expert_params(c)
    D = c["hidden_size"]
    return {"bytes": float(experts_touched * per * W_BYTES
                           + assignments * 2 * D * W_BYTES),
            "flops": 2.0 * assignments * per}


def mla_attend_cost(c, n_seqs: float, live_tokens: float
                    ) -> Dict[str, float]:
    """A decode step's attention proper, all layers, in the absorbed
    form: the running sequences' live latent rows read once a layer;
    per head the scores against a row (rank + rope multiply-adds) and the
    probabilities' sum of its ``c`` (rank), and the two absorptions (the
    query through W_uk, the sum through W_uv: kv_b's weights once)."""
    L, H = c["num_hidden_layers"], c["num_attention_heads"]
    R, dn, dr, dv = (c["kv_lora_rank"], c["qk_nope_head_dim"],
                     c["qk_rope_head_dim"], c["v_head_dim"])
    kv_b = R * H * (dn + dv)
    return {"bytes": float(L * (live_tokens * latent_row_bytes(c)
                                + kv_b * W_BYTES)),
            "flops": 2.0 * L * (live_tokens * H * (2 * R + dr)
                                + n_seqs * kv_b)}


def decode_step_cost(c, n_seqs: float, live_tokens: float,
                     experts_touched: float, assignments: float
                     ) -> Dict[str, float]:
    """A whole decode step of ``n_seqs`` sequences whose contexts add up
    to ``live_tokens``: the weights read whatever the routing, the
    touched experts, the live latent rows once a layer."""
    moe = moe_experts_cost(c, experts_touched, assignments)
    always = always_multiplied_params(c) + head_params(c)
    L, H = c["num_hidden_layers"], c["num_attention_heads"]
    R, dr = c["kv_lora_rank"], c["qk_rope_head_dim"]
    return {
        "bytes": always * W_BYTES + router_params(c) * ROUTER_BYTES
        + moe["bytes"] + L * live_tokens * latent_row_bytes(c),
        "flops": 2.0 * (always + router_params(c)) * n_seqs + moe["flops"]
        + 2.0 * L * live_tokens * H * (2 * R + dr)}


def prefill_flops(c, prompt_tokens: float, assignments: float,
                  prompt_tokens_sq: float = None) -> float:
    """A prompt of ``prompt_tokens`` new tokens from an empty cache, one
    program: every token through the always-multiplied weights and the
    router, ``assignments`` (token, expert) pairs through an expert, the
    head for one row, and causal attention counted once (a token's
    scores and sums over the tokens up to it: n (n + 1) / 2 pairs, each
    nope + rope + v multiply-adds a head). For the mean of several
    prompts give their mean length and mean square length."""
    n = float(prompt_tokens)
    sq = n * n if prompt_tokens_sq is None else float(prompt_tokens_sq)
    H = c["num_attention_heads"]
    per_pair = c["qk_nope_head_dim"] + c["qk_rope_head_dim"] \
        + c["v_head_dim"]
    return (2.0 * (always_multiplied_params(c) + router_params(c)) * n
            + 2.0 * assignments * expert_params(c)
            + 2.0 * head_params(c)
            + 2.0 * c["num_hidden_layers"] * H * per_pair
            * (sq + n) / 2.0)
