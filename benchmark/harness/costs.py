"""Operations and bytes the algorithm needs, from shapes alone.

These are the yardstick for every share of a peak the benchmark prints,
so they live here where a PR that claims a gain cannot change them.
Conventions: a multiply-add is 2 FLOPs; the backward pass of a matrix
product costs twice its forward; recomputed operations are not counted
(so a share of peak built on these is a lower bound on what the
hardware did, and cannot pass 100% honestly).
"""

from __future__ import annotations

from typing import Dict


def gpt2_matmul_params(n_layer: int, n_embd: int, vocab_size: int) -> int:
    """Parameters that take part in a matrix product per token: the
    blocks' four projections (12 E^2 a layer) and the tied head (V E).
    The embedding look-ups are gathers and the biases and gains are
    O(E): neither is counted."""
    return n_layer * 12 * n_embd * n_embd + vocab_size * n_embd


def gpt2_param_count(n_layer: int, n_embd: int, vocab_size: int,
                     n_positions: int) -> int:
    """Every stored parameter (weights, biases, gains, both tables)."""
    per_layer = (12 * n_embd * n_embd      # qkv, proj, fc, fc_proj
                 + 13 * n_embd)            # their biases + two LayerNorms
    return (n_layer * per_layer + vocab_size * n_embd
            + n_positions * n_embd + 2 * n_embd)


def causal_attention_flops_fwd(batch: int, seq: int, n_embd: int) -> float:
    """QK^T and PV of one layer over the causal half: 2 * (2 S^2 E) / 2."""
    return 2.0 * batch * seq * seq * n_embd


def gpt2_train_step_flops(n_layer: int, n_embd: int, vocab_size: int,
                          batch: int, seq: int) -> float:
    """6 N tokens + causal attention forward and backward, no recompute."""
    dense = 6.0 * gpt2_matmul_params(n_layer, n_embd, vocab_size) \
        * batch * seq
    attn = 3.0 * causal_attention_flops_fwd(batch, seq, n_embd) * n_layer
    return dense + attn


def flash_attention_train_cost(n_layer: int, n_embd: int, batch: int,
                               seq: int, bytes_per_el: int = 2
                               ) -> Dict[str, float]:
    """What the attention kernels of one training step must do, summed
    over layers. FLOPs: forward 2 products, backward 4 (dV, dP, dQ, dK;
    the recomputation of the scores that a flash backward does is not
    counted), causal half. Bytes: forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv; each [B, S, E]
    once."""
    fwd = causal_attention_flops_fwd(batch, seq, n_embd)
    flops = n_layer * 3.0 * fwd
    tensor = batch * seq * n_embd * bytes_per_el
    bytes_ = n_layer * (4 + 8) * tensor
    return {"flops": flops, "bytes": float(bytes_)}


def roofline_least_seconds(flops: float, bytes_: float, peaks) -> Dict:
    """The least time the chip could take, and which bound sets it."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}


def kv_page_bytes(n_layer: int, n_embd: int, block_size: int,
                  bytes_per_el: int = 2) -> int:
    """One page of the pool: K and V, every layer."""
    return n_layer * 2 * block_size * n_embd * bytes_per_el


def decode_step_bytes(n_layer: int, n_embd: int, vocab_size: int,
                      n_positions: int, param_bytes: int,
                      live_tokens: int, n_seqs: int, block_size: int,
                      kv_bytes_per_el: int = 2) -> float:
    """Bytes one decode step must move: every weight as stored, once,
    plus the live KV pages of the running sequences, once. ``live_tokens``
    is the sum of their context lengths; each sequence's last page is
    counted whole (half a page a sequence on average)."""
    weights = gpt2_param_count(n_layer, n_embd, vocab_size, n_positions) \
        * param_bytes
    pages = (live_tokens + n_seqs * block_size / 2.0) / block_size
    return weights + pages * kv_page_bytes(n_layer, n_embd, block_size,
                                           kv_bytes_per_el)
