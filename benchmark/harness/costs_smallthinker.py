"""Operations and bytes a SmallThinker step needs, from the
configuration's fields and the step's own counters
(``benchmark/harness/costs.py``'s conventions: a multiply-add is 2 FLOPs,
every byte is moved once, nothing recomputed, never a count of what the
implementation does).

``c`` is the configuration file's ``model.kwargs`` (the source's key
names; the two layouts as published, of which the first
``num_hidden_layers`` entries are the layers held). Weights and cached K
and V rows are bfloat16 (2 bytes), the router's matrix float32. No layer
has a gate on its heads, a shared expert or a dense feed-forward.
"""

from __future__ import annotations

from typing import Dict

W_BYTES = 2         # weights and pages as stored
ROUTER_BYTES = 4    # the router's matrix is float32
FULL, WINDOW = "full", "window"


def layers_of(c, kind: str):
    """The held layers of one kind, by ``sliding_window_layout``."""
    held = c["sliding_window_layout"][:c["num_hidden_layers"]]
    return [i for i, w in enumerate(held) if bool(w) == (kind == WINDOW)]


def attn_params(c) -> int:
    """One layer's attention matrices: q, k, v, out (every layer alike)."""
    D, d = c["hidden_size"], c["head_dim"]
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    return D * H * d + 2 * D * Hkv * d + H * d * D


def expert_params(c) -> int:
    return 3 * c["hidden_size"] * c["moe_ffn_hidden_size"]


def router_params(c) -> int:
    return c["num_hidden_layers"] * c["hidden_size"] \
        * c["moe_num_primary_experts"]


def head_params(c) -> int:
    return c["hidden_size"] * c["vocab_size"]


def always_multiplied_params(c) -> int:
    """Matrix weights every token is multiplied by whatever the routing,
    the router and the head apart: the attention's."""
    return c["num_hidden_layers"] * attn_params(c)


def param_count(c) -> int:
    """Every stored matrix weight (norm gains and the selection bias are
    O(width) and left out)."""
    return (always_multiplied_params(c) + router_params(c)
            + c["num_hidden_layers"] * c["moe_num_primary_experts"]
            * expert_params(c) + 2 * head_params(c))


def kv_row_bytes(c) -> int:
    """One cached token of one layer: K and V of every key/value head."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * W_BYTES


def moe_experts_cost(c, experts_touched: float, assignments: float
                     ) -> Dict[str, float]:
    """The routed experts of one step, all layers together:
    ``experts_touched`` (expert, layer) pairs that got a token, their
    weights read once; ``assignments`` (token, expert) pairs, each three
    products of hidden x width; activations in and out of every
    assignment."""
    per = expert_params(c)
    return {"bytes": float(experts_touched * per * W_BYTES
                           + assignments * 2 * c["hidden_size"] * W_BYTES),
            "flops": 2.0 * assignments * per}


def attend_cost(c, kind: str, n_seqs: float, tokens: float
                ) -> Dict[str, float]:
    """A decode step's attention proper in the layers of one kind:
    ``tokens`` cached rows read (the rows' live contexts summed for the
    full layers; for the window layers what each row really reads,
    ``min(length, sliding_window_size)`` summed), K and V once a layer; a
    row's query in and its output out; per query head a score against a
    key and a probability against a value (2 x head_dim multiply-adds a
    token)."""
    d, H = c["head_dim"], c["num_attention_heads"]
    n = len(layers_of(c, kind))
    return {"bytes": float(n * tokens * kv_row_bytes(c)
                           + n * H * n_seqs * 2 * d * W_BYTES),
            "flops": 4.0 * d * n * H * tokens}


def decode_step_cost(c, n_seqs: float, live_tokens: float,
                     window_tokens: float, experts_touched: float,
                     assignments: float) -> Dict[str, float]:
    """A whole decode step of ``n_seqs`` sequences whose contexts add up
    to ``live_tokens`` and whose windows hold ``window_tokens``: the
    weights read whatever the routing (attention, routers, head), the
    touched experts, the live K and V of the full layers, the window's of
    the window layers."""
    moe = moe_experts_cost(c, experts_touched, assignments)
    full = attend_cost(c, FULL, n_seqs, live_tokens)
    window = attend_cost(c, WINDOW, n_seqs, window_tokens)
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
    1) / 2 pairs), a window layer's the last ``W`` of them (W (W + 1) / 2
    + (n - W) W pairs for n >= W); a pair is 2 x head_dim multiply-adds a
    query head. For the mean of several prompts give their mean length
    and mean square length; THE WINDOW LAYERS' COUNT IS THEN AN UPPER
    ESTIMATE where some prompts are under the window and some over (it is
    taken at the mean length), which the cell's mix is: the reader says
    so."""
    n = float(prompt_tokens)
    sq = n * n if prompt_tokens_sq is None else float(prompt_tokens_sq)
    W = float(c["sliding_window_size"])
    pairs = {FULL: (sq + n) / 2.0,
             WINDOW: (sq + n) / 2.0 if n < W
             else W * (W + 1) / 2.0 + (n - W) * W}
    return sum(4.0 * c["head_dim"] * pairs[kind] * c["num_attention_heads"]
               * len(layers_of(c, kind)) for kind in (FULL, WINDOW))


def prefill_flops(c, prompt_tokens: float, assignments: float,
                  prompt_tokens_sq: float = None) -> float:
    """A prompt of ``prompt_tokens`` new tokens from an empty cache, one
    program: every token through the attention's weights and the router,
    ``assignments`` (token, expert) pairs through an expert, the head for
    one row, and the attention (``prefill_attention_flops``)."""
    n = float(prompt_tokens)
    return (2.0 * (always_multiplied_params(c) + router_params(c)) * n
            + 2.0 * assignments * expert_params(c)
            + 2.0 * head_params(c)
            + prefill_attention_flops(c, n, prompt_tokens_sq))
