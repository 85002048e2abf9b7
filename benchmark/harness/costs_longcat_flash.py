"""Operations and bytes a LongCat-Flash step needs, from the
configuration's fields and the step's own counters
(``benchmark/harness/costs.py``'s conventions: a multiply-add is 2 FLOPs,
every byte is moved once, nothing recomputed, never a count of what the
implementation does).

``c`` is the configuration file's ``model.kwargs`` (the source's key
names; ``n_routed_experts`` the router's 512 real outputs,
``zero_expert_num`` its 256 zero-compute ones, ``experts_held`` = [first,
count]; ``vocab_size`` the slice held). A logical layer is two attention
sublayers and two dense SwiGLUs beside one routed product. Weights and
cached latent rows are bfloat16 (2 bytes), the router's matrix float32. An
assignment to a zero-compute expert needs no weight and no product: it is
counted nowhere here.
"""

from __future__ import annotations

from typing import Dict

# an attention sublayer's matrices and a cached row are Kimi-K2's, by the
# same keys (q down and up, kv down, kv up, out; rank + rope values)
from benchmark.harness.costs_kimi_k2 import (  # noqa: F401
    ROUTER_BYTES, W_BYTES, latent_row_bytes, mla_params)


def expert_params(c) -> int:
    return 3 * c["hidden_size"] * c["expert_ffn_hidden_size"]


def dense_ffn_params(c) -> int:
    return 3 * c["hidden_size"] * c["ffn_hidden_size"]


def sublayers(c) -> int:
    """Attention sublayers, each with rows of its own in the pool."""
    return 2 * c["num_layers"]


def always_multiplied_params(c) -> int:
    """Matrix weights every token is multiplied by whatever the routing,
    the router and the head apart: two attentions and two dense SwiGLUs a
    layer."""
    return sublayers(c) * (mla_params(c) + dense_ffn_params(c))


def router_params(c) -> int:
    return c["num_layers"] * c["hidden_size"] * (
        c["n_routed_experts"] + c["zero_expert_num"])


def head_params(c) -> int:
    return c["hidden_size"] * c["vocab_size"]


def param_count(c) -> int:
    """Every stored matrix weight of the share held (norm gains and the
    selection bias are O(width) and left out)."""
    held = c["experts_held"][1] if c.get("experts_held") \
        else c["n_routed_experts"]
    return (always_multiplied_params(c) + router_params(c)
            + c["num_layers"] * held * expert_params(c)
            + 2 * head_params(c))


def moe_experts_cost(c, experts_touched: float, assignments: float
                     ) -> Dict[str, float]:
    """The routed experts of one step, all layers together, as
    ``costs_kimi_k2.moe_experts_cost`` counts: ``experts_touched``
    (expert, layer) pairs that got a token, their weights read once;
    ``assignments`` REAL (token, expert) pairs held here, each three
    products of hidden x width; activations in and out of every
    assignment."""
    per = expert_params(c)
    D = c["hidden_size"]
    return {"bytes": float(experts_touched * per * W_BYTES
                           + assignments * 2 * D * W_BYTES),
            "flops": 2.0 * assignments * per}


def mla_attend_cost(c, n_seqs: float, live_tokens: float
                    ) -> Dict[str, float]:
    """A decode step's attention proper, all sublayers, in the absorbed
    form: the running sequences' live latent rows read once a sublayer;
    per head the scores against a row (rank + rope multiply-adds) and the
    probabilities' sum of its ``c`` (rank), and the two absorptions (the
    query through W_uk, the sum through W_uv: kv_b's weights once)."""
    L, H = sublayers(c), c["num_attention_heads"]
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
    touched experts, the live latent rows once a sublayer."""
    moe = moe_experts_cost(c, experts_touched, assignments)
    always = always_multiplied_params(c) + head_params(c)
    L, H = sublayers(c), c["num_attention_heads"]
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
    router, ``assignments`` real (token, expert) pairs through an expert,
    the head for one row, and causal attention counted once a sublayer
    (n (n + 1) / 2 pairs, each nope + rope + v multiply-adds a head). For
    the mean of several prompts give their mean length and mean square
    length."""
    n = float(prompt_tokens)
    sq = n * n if prompt_tokens_sq is None else float(prompt_tokens_sq)
    H = c["num_attention_heads"]
    per_pair = c["qk_nope_head_dim"] + c["qk_rope_head_dim"] \
        + c["v_head_dim"]
    return (2.0 * (always_multiplied_params(c) + router_params(c)) * n
            + 2.0 * assignments * expert_params(c)
            + 2.0 * head_params(c)
            + 2.0 * sublayers(c) * H * per_pair * (sq + n) / 2.0)
