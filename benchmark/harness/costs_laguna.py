"""Operations and bytes a Laguna step needs, from the configuration's
fields and the step's own counters (``benchmark/harness/costs.py``'s
conventions: a multiply-add is 2 FLOPs, every byte is moved once, nothing
recomputed, never a count of what the implementation does).

``c`` is the configuration file's ``model.kwargs`` (the source's key
names; the per-layer lists cut to the layers held). Weights and cached K
and V rows are bfloat16 (2 bytes), the router's matrix float32.
"""

from __future__ import annotations

from typing import Dict

# the routed experts are counted as the accepted metric counts them: one
# count in the tree (moe_experts_roofline_share.serve reads this cell too)
from benchmark.harness.costs_kimi_linear import expert_params, \
    moe_experts_cost

W_BYTES = 2         # weights and pages as stored
ROUTER_BYTES = 4    # the router's matrix is float32
FULL, SLIDING = "full_attention", "sliding_attention"


def layers_of(c, kind: str):
    return [i for i, t in enumerate(c["layer_types"]) if t == kind]


def attn_params(c, layer: int) -> int:
    """One layer's attention matrices: q, k, v, the gate (one value a
    head), out."""
    D, d = c["hidden_size"], c["head_dim"]
    H, Hkv = c["num_attention_heads_per_layer"][layer], \
        c["num_key_value_heads"]
    return D * H * d + 2 * D * Hkv * d + D * H + H * d * D


def shared_params(c) -> int:
    return 3 * c["hidden_size"] * c["shared_expert_intermediate_size"]


def dense_ffn_params(c) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def sparse_layers(c) -> int:
    return sum(t == "sparse" for t in c["mlp_layer_types"])


def always_multiplied_params(c) -> int:
    """Matrix weights every token is multiplied by whatever the routing,
    the router and the head apart: attention, the dense feed-forwards,
    each sparse layer's shared expert."""
    n = c["num_hidden_layers"]
    return (sum(attn_params(c, i) for i in range(n))
            + (n - sparse_layers(c)) * dense_ffn_params(c)
            + sparse_layers(c) * shared_params(c))


def router_params(c) -> int:
    return sparse_layers(c) * c["hidden_size"] * c["num_experts"]


def head_params(c) -> int:
    return c["hidden_size"] * c["vocab_size"]


def param_count(c) -> int:
    """Every stored matrix weight (norm gains and the selection bias are
    O(width) and left out)."""
    return (always_multiplied_params(c) + router_params(c)
            + sparse_layers(c) * c["num_experts"] * expert_params(c)
            + 2 * head_params(c))


def kv_row_bytes(c) -> int:
    """One cached token of one layer: K and V of every key/value head."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * W_BYTES


def attend_cost(c, kind: str, n_seqs: float, tokens: float
                ) -> Dict[str, float]:
    """A decode step's attention proper in the layers of one kind:
    ``tokens`` cached rows read (the rows' live contexts summed for the
    full layers, what their windows hold for the sliding ones), K and V
    once a layer; a row's query in and its output out; per query head a
    score against a key and a probability against a value (2 x head_dim
    multiply-adds a token)."""
    d = c["head_dim"]
    heads = [c["num_attention_heads_per_layer"][i]
             for i in layers_of(c, kind)]
    return {"bytes": float(len(heads) * tokens * kv_row_bytes(c)
                           + sum(heads) * n_seqs * 2 * d * W_BYTES),
            "flops": 4.0 * d * sum(heads) * tokens}


def decode_step_cost(c, n_seqs: float, live_tokens: float,
                     window_tokens: float, experts_touched: float,
                     assignments: float) -> Dict[str, float]:
    """A whole decode step of ``n_seqs`` sequences whose contexts add up
    to ``live_tokens`` and whose windows hold ``window_tokens``: the
    weights read whatever the routing, the touched experts, the live K
    and V of the full layers, the window's of the sliding ones."""
    moe = moe_experts_cost(c, experts_touched, assignments)
    full = attend_cost(c, FULL, n_seqs, live_tokens)
    window = attend_cost(c, SLIDING, n_seqs, window_tokens)
    always = always_multiplied_params(c) + head_params(c)
    return {
        "bytes": always * W_BYTES + router_params(c) * ROUTER_BYTES
        + moe["bytes"] + full["bytes"] + window["bytes"],
        "flops": 2.0 * (always + router_params(c)) * n_seqs + moe["flops"]
        + full["flops"] + window["flops"]}


def prefill_attention_flops(c, prompt_tokens: float,
                            prompt_tokens_sq: float = None) -> float:
    """Causal attention of a prompt of ``n`` tokens from an empty cache,
    counted once: a full layer's token sees the tokens up to it (n (n +
    1) / 2 pairs), a sliding layer's the last ``sliding_window`` of them
    (W (W + 1) / 2 + (n - W) W pairs for n >= W); a pair is 2 x head_dim
    multiply-adds a query head. For the mean of several prompts give
    their mean length and mean square length (the sliding layers' count
    is linear in n past the window)."""
    n = float(prompt_tokens)
    sq = n * n if prompt_tokens_sq is None else float(prompt_tokens_sq)
    W = float(c["sliding_window"])
    pairs = {FULL: (sq + n) / 2.0,
             SLIDING: (sq + n) / 2.0 if n < W
             else W * (W + 1) / 2.0 + (n - W) * W}
    return sum(4.0 * c["head_dim"] * pairs[kind]
               * sum(c["num_attention_heads_per_layer"][i]
                     for i in layers_of(c, kind))
               for kind in (FULL, SLIDING))


def prefill_flops(c, prompt_tokens: float, assignments: float,
                  prompt_tokens_sq: float = None) -> float:
    """A prompt of ``prompt_tokens`` new tokens from an empty cache, one
    program: every token through the always-multiplied weights and the
    router, ``assignments`` (token, expert) pairs through an expert, the
    head for one row, and the attention (``prefill_attention_flops``)."""
    n = float(prompt_tokens)
    return (2.0 * (always_multiplied_params(c) + router_params(c)) * n
            + 2.0 * assignments * expert_params(c)
            + 2.0 * head_params(c)
            + prefill_attention_flops(c, n, prompt_tokens_sq))
