"""Operations and bytes a Jamba step needs, from the configuration's fields
and the step's own counters (``benchmark/harness/costs.py``'s
conventions: a multiply-add is 2 FLOPs, every byte is moved once, nothing
recomputed, never a count of what the implementation does).

``c`` is the configuration file's ``model.kwargs`` (the source's key
names). Weights, the cached K and V rows and the convolution tail are
bfloat16 (2 bytes); ``A_log``, ``D``, ``dt``'s bias, the norms' gains and
the Mamba state are float32.
"""

from __future__ import annotations

from typing import Dict

W_BYTES = 2         # weights, pages and the convolution tail as stored
STATE_BYTES = 4     # the Mamba state, and the recurrence's operands
MAMBA, ATTENTION = "mamba", "attention"
# per state element and token: dt A, its exp, the decay's product, dt u B,
# their sum, the product with C and its sum over the states
STEP_FLOPS = 7.0


def layers(c, kind: str) -> int:
    n = sum(i % c["attn_layer_period"] == c["attn_layer_offset"]
            for i in range(c["num_hidden_layers"]))
    return n if kind == ATTENTION else c["num_hidden_layers"] - n


def d_inner(c) -> int:
    return c["mamba_expand"] * c["hidden_size"]


def head_dim(c) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def mamba_params(c) -> int:
    """One Mamba mixer's matrices: W_in, W_x, W_dt, W_out."""
    D, d_in = c["hidden_size"], d_inner(c)
    R, N = c["mamba_dt_rank"], c["mamba_d_state"]
    return D * 2 * d_in + d_in * (R + 2 * N) + R * d_in + d_in * D


def mamba_small_params(c) -> int:
    """... and what is O(width) of it: the K taps and their bias
    (bfloat16), ``A_log`` [N, d_in], ``D``, ``dt``'s bias, the three inner
    norms' gains (float32)."""
    d_in, N = d_inner(c), c["mamba_d_state"]
    return (c["mamba_d_conv"] + 1) * d_in + N * d_in + 2 * d_in \
        + c["mamba_dt_rank"] + 2 * N


def attn_params(c) -> int:
    D, d = c["hidden_size"], head_dim(c)
    return 2 * D * c["num_attention_heads"] * d \
        + 2 * D * c["num_key_value_heads"] * d


def mlp_params(c) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def table_params(c) -> int:
    """The token table, which is the head too (tied: stored once)."""
    return c["hidden_size"] * c["vocab_size"]


def multiplied_params(c) -> int:
    """Matrix weights every token is multiplied by, the head apart."""
    return (layers(c, MAMBA) * mamba_params(c)
            + layers(c, ATTENTION) * attn_params(c)
            + c["num_hidden_layers"] * mlp_params(c))


def param_count(c) -> int:
    """Every stored parameter (the program's tree, leaf by leaf)."""
    norms = (2 * c["num_hidden_layers"] + 1) * c["hidden_size"]
    return multiplied_params(c) + table_params(c) + norms \
        + layers(c, MAMBA) * mamba_small_params(c)


def weight_bytes(c) -> int:
    """The stored tree in bytes: matrices, taps and table bfloat16, the
    rest float32."""
    d_in = d_inner(c)
    taps = layers(c, MAMBA) * (c["mamba_d_conv"] + 1) * d_in
    two = multiplied_params(c) + table_params(c) + taps
    return two * W_BYTES + (param_count(c) - two) * 4


def mamba_state_bytes(c, n_seqs: float) -> float:
    """The recurrent state of ``n_seqs`` sequences, all Mamba layers."""
    return float(layers(c, MAMBA) * n_seqs * d_inner(c)
                 * c["mamba_d_state"] * STATE_BYTES)


def conv_tail_bytes(c, n_seqs: float) -> float:
    """The convolution tails of ``n_seqs`` sequences, held once."""
    return float(layers(c, MAMBA) * n_seqs * (c["mamba_d_conv"] - 1)
                 * d_inner(c) * W_BYTES)


def mamba_step_cost(c, n_seqs: float) -> Dict[str, float]:
    """One token a sequence through every Mamba layer's recurrence: the
    state read once and written once, the step's operands in (u and dt a
    channel, B and C a state) and its output out in float32, ``A`` once a
    layer; ``STEP_FLOPS`` a state element."""
    state = mamba_state_bytes(c, n_seqs)
    d_in, N = d_inner(c), c["mamba_d_state"]
    vectors = layers(c, MAMBA) * STATE_BYTES * (
        n_seqs * (3 * d_in + 2 * N) + N * d_in)
    return {"bytes": 2.0 * state + vectors,
            "flops": STEP_FLOPS * state / STATE_BYTES}


def mamba_scan_cost(c, tokens: float, rows: float) -> Dict[str, float]:
    """A prompt's recurrence, all Mamba layers, as the algorithm needs
    it: each of the ``rows`` sequences' states in once and out once, a
    token's operands in and its output out, ``STEP_FLOPS`` a state
    element a token (a loop that carries the state through memory moves
    it once a position: how it is done, not what is required)."""
    d_in, N = d_inner(c), c["mamba_d_state"]
    n = layers(c, MAMBA)
    return {"bytes": 2.0 * mamba_state_bytes(c, rows)
            + n * STATE_BYTES * (tokens * (3 * d_in + 2 * N) + N * d_in),
            "flops": STEP_FLOPS * n * tokens * d_in * N}


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


def decode_step_cost(c, n_seqs: float, live_tokens: float
                     ) -> Dict[str, float]:
    """A whole decode step of ``n_seqs`` sequences whose contexts add up
    to ``live_tokens``: every weight read once (the table as the head),
    every sequence's Mamba state and convolution tail in and out, the live
    K and V of the attention layers."""
    step = mamba_step_cost(c, n_seqs)
    attend = attend_cost(c, n_seqs, live_tokens)
    return {
        "bytes": weight_bytes(c) + step["bytes"]
        + 2.0 * conv_tail_bytes(c, n_seqs) + attend["bytes"],
        "flops": 2.0 * (multiplied_params(c) + table_params(c)) * n_seqs
        + step["flops"] + attend["flops"]}


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
    from an empty cache: every token through every matrix, the head for
    one position a prompt, the Mamba recurrence and the attention."""
    n = float(tokens)
    return (2.0 * multiplied_params(c) * n
            + 2.0 * table_params(c) * rows
            + mamba_scan_cost(c, n, rows)["flops"]
            + prefill_attention_flops(c, n, rows))
