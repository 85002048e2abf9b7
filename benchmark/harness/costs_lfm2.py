"""Operations and bytes an LFM2-MoE step needs, from the configuration's
fields and the step's own counters (``benchmark/harness/costs.py``'s
conventions: a multiply-add is 2 FLOPs, every byte is moved once, nothing
recomputed, never a count of what the implementation does: the routed
product is counted as ROUTED, the real (token, expert) pairs' FLOPs and
the touched experts' weights once, whichever form multiplies them).

``c`` is the configuration file's ``model.kwargs`` (the source's key
names; ``layer_types`` whole, the first ``num_hidden_layers`` kept).
Weights, the cached K and V rows and the convolution tail are bfloat16 (2
bytes); the norms' gains, the router and its bias are float32.
"""

from __future__ import annotations

from typing import Dict

W_BYTES = 2         # weights, pages and the convolution tail as stored
CONV, ATTENTION = "conv", "full_attention"


def layers(c, kind: str) -> int:
    return list(c["layer_types"])[:c["num_hidden_layers"]].count(kind)


def routed_layers(c) -> int:
    return c["num_hidden_layers"] - min(c["num_dense_layers"],
                                        c["num_hidden_layers"])


def dense_layers(c) -> int:
    return c["num_hidden_layers"] - routed_layers(c)


def head_dim(c) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def conv_params(c) -> int:
    """One convolution operator's matrices: W_in (d -> 3d) and W_out."""
    return 4 * c["hidden_size"] ** 2


def conv_taps(c) -> int:
    return c["conv_L_cache"] * c["hidden_size"]


def attn_params(c) -> int:
    D, d = c["hidden_size"], head_dim(c)
    return 2 * D * c["num_attention_heads"] * d \
        + 2 * D * c["num_key_value_heads"] * d


def mlp_params(c) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c) -> int:
    """A routed layer's router and its selection bias (float32)."""
    return c["hidden_size"] * c["num_experts"] + c["num_experts"]


def table_params(c) -> int:
    """The token table, which is the head too (tied: stored once)."""
    return c["hidden_size"] * c["vocab_size"]


def multiplied_params(c) -> int:
    """Matrix weights EVERY token is multiplied by, the head and the
    routed experts apart: the operators, the dense feed-forward parts,
    the routers."""
    return (layers(c, CONV) * conv_params(c)
            + layers(c, ATTENTION) * attn_params(c)
            + dense_layers(c) * mlp_params(c)
            + routed_layers(c) * c["hidden_size"] * c["num_experts"])


def param_count(c) -> int:
    """Every stored parameter (the program's tree, leaf by leaf)."""
    norms = (2 * c["num_hidden_layers"] + 1) * c["hidden_size"] \
        + layers(c, ATTENTION) * 2 * head_dim(c)
    return (layers(c, CONV) * (conv_params(c) + conv_taps(c))
            + layers(c, ATTENTION) * attn_params(c)
            + dense_layers(c) * mlp_params(c)
            + routed_layers(c) * (c["num_experts"] * expert_params(c)
                                  + router_params(c))
            + table_params(c) + norms)


def float32_params(c) -> int:
    """The leaves stored in float32: norm gains, routers, biases."""
    return (2 * c["num_hidden_layers"] + 1) * c["hidden_size"] \
        + layers(c, ATTENTION) * 2 * head_dim(c) \
        + routed_layers(c) * router_params(c)


def weight_bytes(c) -> int:
    """The stored tree in bytes."""
    return (param_count(c) - float32_params(c)) * W_BYTES \
        + float32_params(c) * 4


def resident_bytes(c) -> int:
    """What EVERY step reads whatever it routes: the weights less the
    routed experts."""
    return weight_bytes(c) - routed_layers(c) * c["num_experts"] \
        * expert_params(c) * W_BYTES


def conv_tail_bytes(c, n_seqs: float) -> float:
    """The convolution tails of ``n_seqs`` sequences, held once."""
    return float(layers(c, CONV) * n_seqs * (c["conv_L_cache"] - 1)
                 * c["hidden_size"] * W_BYTES)


def conv_cost(c, tokens: float, rows: float) -> Dict[str, float]:
    """The convolution operators of ``tokens`` tokens in ``rows``
    sequences, all convolution layers: W_in, W_out and the taps once a
    layer, the rows' tails in and out, a token's input in and its output
    out; per token the two products and, a channel, two gates and K
    multiply-adds."""
    D, K, n = c["hidden_size"], c["conv_L_cache"], layers(c, CONV)
    return {"bytes": float(n * (conv_params(c) + conv_taps(c)) * W_BYTES
                           + 2.0 * conv_tail_bytes(c, rows)
                           + n * tokens * 2 * D * W_BYTES),
            "flops": n * tokens * (2.0 * conv_params(c) + (2 * K + 2) * D)}


def moe_experts_cost(c, experts_touched: float, assignments: float
                     ) -> Dict[str, float]:
    """The routed experts of one step, all routed layers together, as
    ROUTED: ``experts_touched`` (expert, layer) pairs that got a token,
    their weights read once; ``assignments`` real (token, expert) pairs,
    each three products of hidden x width; activations in and out of
    every assignment. Rows that an expert multiplies without having been
    sent them are work done and not required."""
    per = expert_params(c)
    return {"bytes": float(experts_touched * per * W_BYTES
                           + assignments * 2 * c["hidden_size"] * W_BYTES),
            "flops": 2.0 * assignments * per}


def kv_row_bytes(c) -> int:
    """One cached token of one layer: K and V of every key/value head."""
    return 2 * c["num_key_value_heads"] * head_dim(c) * W_BYTES


def attend_cost(c, n_seqs: float, live_tokens: float) -> Dict[str, float]:
    """A decode step's attention proper: ``live_tokens`` cached rows read
    (the rows' live contexts summed), K and V once an attention layer; a
    row's query in and its output out; per query head a score against a
    key and a probability against a value."""
    d, H = head_dim(c), c["num_attention_heads"]
    n = layers(c, ATTENTION)
    return {"bytes": float(n * live_tokens * kv_row_bytes(c)
                           + n * H * n_seqs * 2 * d * W_BYTES),
            "flops": 4.0 * d * n * H * live_tokens}


def decode_step_cost(c, n_seqs: float, live_tokens: float,
                     experts_touched: float, assignments: float
                     ) -> Dict[str, float]:
    """A whole decode step of ``n_seqs`` sequences whose contexts add up
    to ``live_tokens``: every weight outside the experts read once (the
    table as the head), the touched experts' once, every sequence's tail
    in and out, the live K and V of the attention layers; every token
    through every matrix outside the experts and through its own routed
    pairs."""
    moe = moe_experts_cost(c, experts_touched, assignments)
    attend = attend_cost(c, n_seqs, live_tokens)
    return {
        "bytes": resident_bytes(c) + moe["bytes"]
        + 2.0 * conv_tail_bytes(c, n_seqs) + attend["bytes"],
        "flops": 2.0 * (multiplied_params(c) + table_params(c)) * n_seqs
        + moe["flops"] + attend["flops"]}


def prefill_attention_flops(c, tokens: float, rows: float = 1.0) -> float:
    """Causal attention of ``rows`` prompts of ``tokens`` tokens in all,
    from an empty cache, counted once: a token sees the tokens of its own
    prompt up to it. The prompts' own lengths are not on the spans, so
    this is the least the pairs can be, every prompt ``tokens / rows``
    long."""
    n = float(tokens)
    pairs = (n * n / max(rows, 1.0) + n) / 2.0
    return 4.0 * head_dim(c) * pairs * c["num_attention_heads"] \
        * layers(c, ATTENTION)


def prefill_flops(c, tokens: float, rows: float = 1.0) -> float:
    """A prefill step of ``rows`` prompts, ``tokens`` real tokens in all,
    from an empty cache: every token through every matrix outside the
    experts and through ``num_experts_per_tok`` experts a routed layer,
    the head for one position a prompt, the attention."""
    n = float(tokens)
    return (2.0 * multiplied_params(c) * n
            + 2.0 * routed_layers(c) * c["num_experts_per_tok"]
            * expert_params(c) * n
            + 2.0 * table_params(c) * rows
            + prefill_attention_flops(c, n, rows))
